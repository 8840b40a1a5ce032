"""multi_ttm_roofline_share (%): the least time of the Multi-TTMs the
window asked for, over the device time of the Pallas kernels that ran in
it.

A Multi-TTM keeping mode k contracts every other mode j of the tensor with
an ``I_j x R_j`` matrix.  Its flops are those of the cheapest order of
single TTMs (each 2 x current size x R_j); its bytes are the tensor read
once, the matrices, and the output written.  Least time is the larger of
flops / ceiling and bytes / HBM bandwidth.
"""

import itertools
import math

from benchlib import xtrace

KIND = "multi_ttm"


def work(shape, ranks, keep, itemsize=4):
    """(flops, bytes) of one Multi-TTM."""
    others = [j for j in range(len(shape)) if j != keep]
    best = None
    for order in itertools.permutations(others):
        cur, flops = list(shape), 0
        for j in order:
            flops += 2 * math.prod(cur) * ranks[j]
            cur[j] = ranks[j]
        best = flops if best is None else min(best, flops)
    out = math.prod(shape[k] if k == keep else ranks[k]
                    for k in range(len(shape)))
    mats = sum(shape[j] * ranks[j] for j in others)
    return best, itemsize * (math.prod(shape) + mats + out)


def least_time(call, peaks):
    flops, nbytes = work(call["shape"], call["ranks"], call["keep"],
                         call.get("itemsize", 4))
    return max(flops / peaks["f32_highest_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    calls = [c for c in run.work if c["kind"] == KIND]
    if run.trace is None or not calls:
        return None
    kernel_ns = sum(xtrace.length(xtrace.ops(run.trace, d, ("kernel",)))
                    for d in run.trace["devices"])
    if kernel_ns == 0:
        return None
    least = sum(c["count"] * least_time(c, run.peaks) for c in calls)
    return 100.0 * least / (kernel_ns * 1e-9)
