"""mttkrp_roofline_share (%): the least time of the MTTKRPs the window
asked for, over the device time of the Pallas kernels that ran in it.

Least time is the larger of flops / ceiling and bytes / HBM bandwidth.
The work counts the algorithm, whatever implements it: an MTTKRP of an
``I_0 x ... x I_{N-1}`` tensor at rank R does 2 R prod(I) flops (the
Khatri-Rao row times the tensor entry, summed) and reads the tensor once,
reads the other factors and writes the output.  The ceiling is fp32 at
``Precision.HIGHEST``, six bf16 passes (peaks.json).
"""

import math

from benchlib import xtrace

KIND = "mttkrp"


def work(shape, rank, mode, itemsize=4):
    """(flops, bytes) of one MTTKRP."""
    size = math.prod(shape)
    flops = 2 * rank * size
    factors = sum(shape[k] * rank for k in range(len(shape)) if k != mode)
    out = shape[mode] * rank
    return flops, itemsize * (size + factors + out)


def least_time(call, peaks):
    flops, nbytes = work(call["shape"], call["rank"], call["mode"],
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
