"""device_idle_share (%): the share of the traced window in which no op
runs on the device, averaged over the chips.  1 - union of the device's op
intervals / window."""

from benchlib import xtrace


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    lo, hi = xtrace.window(run.trace)

    def idle(dev):
        return 100.0 * (1 - xtrace.length(xtrace.union(
            xtrace.ops(run.trace, dev))) / (hi - lo))

    return xtrace.per_device_mean(run.trace, idle)
