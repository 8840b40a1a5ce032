"""non_kernel_busy_share (%): the share of the traced window in which the
device runs ops and none of them is a Pallas (Mosaic) kernel: the engine's
relayout copies and the driver's Gram/solve/normalize/fit tail.  Averaged
over the chips."""

from benchlib import xtrace


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    lo, hi = xtrace.window(run.trace)

    def share(dev):
        busy = xtrace.ops(run.trace, dev)
        kern = xtrace.ops(run.trace, dev, ("kernel",))
        return 100.0 * xtrace.length(xtrace.subtract(busy, kern)) / (hi - lo)

    return xtrace.per_device_mean(run.trace, share)
