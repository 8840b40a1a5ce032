"""Pairwise contractions for the plain references, at a stated precision.

``"highest"`` is fp32 at ``Precision.HIGHEST``.  ``"high"`` is the
nearest precision below it, three bf16 passes: each fp32 operand is split
into a bf16 head and a bf16 tail, and the tail-times-tail product is
dropped, as ``Precision.HIGH`` runs an fp32 dot on the MXU.  It is written
out so that it means the same on every backend, the CPU included.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high")


def split_bf16(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    """fp32 ``a`` as a bf16 head plus a bf16 tail (16 significant bits)."""
    head = a.astype(jnp.bfloat16)
    tail = (a - head.astype(jnp.float32)).astype(jnp.bfloat16)
    return head, tail


def einsum2(spec: str, a, b, precision: str) -> jax.Array:
    """``jnp.einsum(spec, a, b)`` in fp32 at ``precision``.  Under
    ``"high"`` an operand may be given already split, as a pair."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision="highest",
                          preferred_element_type=jnp.float32)
    if precision != "high":
        raise ValueError(f"precision must be one of {PRECISIONS}")
    ah, al = a if isinstance(a, tuple) else split_bf16(a)
    bh, bl = b if isinstance(b, tuple) else split_bf16(b)

    # a product of two bf16 values is exact in fp32: the MXU runs each
    # such dot in one pass; XLA's CPU has no bf16 dot, so it takes them
    # as fp32 values, which gives the same sums
    wide = jax.default_backend() == "cpu"

    def dot(p, q):
        if wide:
            p, q = p.astype(jnp.float32), q.astype(jnp.float32)
        return jnp.einsum(spec, p, q, precision="highest",
                          preferred_element_type=jnp.float32)

    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def prepare(x: jax.Array, precision: str):
    """The tensor as the contractions take it: split once under
    ``"high"`` so the sweeps do not split it again."""
    return split_bf16(x) if precision == "high" else x


def ttm_chain(x, mats: dict, precision: str) -> jax.Array:
    """Contract mode ``k`` of ``x`` with ``mats[k]`` (``I_k x R_k``) for
    every key, one TTM at a time in the order given; the contracted mode
    keeps its place, now of extent ``R_k``."""
    letters = "abcdefgh"
    n = (x[0] if isinstance(x, tuple) else x).ndim
    out = x
    for k, m in mats.items():
        src = letters[:n]
        dst = src[:k] + "z" + src[k + 1:]
        out = einsum2(f"{src},{letters[k]}z->{dst}", out, m, precision)
    return out
