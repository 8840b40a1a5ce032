"""engine_idle_share (%): the share of the traced window in which the device
is idle and the innermost open program span is one of (repro.mttkrp.*,
repro.multi_ttm.*, repro.contract_partial*, repro.fused_pair,
repro.engine.*): the engine's host work around each contraction: plan and
tune-cache resolution, relayout (transpose, padding, cropping) dispatch.
Averaged over the chips; nothing where the program opens no such span."""

from benchlib import owners


def read(run):
    return owners.share(run, owners.ENGINE)
