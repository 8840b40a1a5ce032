"""Pallas TPU kernels for the paper's compute hot-spots.

``mttkrp3``/``mttkrpn`` — the blocked MTTKRP (Algorithm 2 adapted to VMEM +
MXU); ``multi_ttm`` — the blocked Kronecker-weight Multi-TTM (the
Tucker/HOSVD kernel, arXiv:2207.10437); ``ssd_intra`` — the fused
intra-chunk SSD contraction (same blocking discipline, §Perf Cell B). ``ops`` wraps with layout (the 3-way
kernel reads X in place; N > 3 reads a mode-first copy), padding where a
shape needs it, and VMEM-budget block planning; ``ref`` holds the jnp oracles.
All validated in interpret mode on CPU; compiled via Mosaic on TPU.
"""

from .ops import (
    BlockPlan,
    MultiTTMPlan,
    choose_blocks,
    choose_multi_ttm_blocks,
    mttkrp_canonical_pallas,
    mttkrp_pallas,
    mttkrp_partial_canonical_pallas,
    multi_ttm_canonical_pallas,
)
from .ref import mttkrp_ref
from .ssd_intra import ssd_intra_pallas, ssd_intra_ref

__all__ = [
    "BlockPlan",
    "MultiTTMPlan",
    "choose_blocks",
    "choose_multi_ttm_blocks",
    "mttkrp_canonical_pallas",
    "mttkrp_pallas",
    "mttkrp_partial_canonical_pallas",
    "multi_ttm_canonical_pallas",
    "mttkrp_ref",
    "ssd_intra_pallas",
    "ssd_intra_ref",
]
