"""kernel_launch_idle_share (%): the share of the traced window in which the
device is idle and the innermost open program span is one of
(repro.kernel.*): each eager Pallas kernel call: its trace, lowering,
compile or cache lookup and enqueue.  Averaged over the chips; nothing
where the program opens no such span."""

from benchlib import owners


def read(run):
    return owners.share(run, owners.KERNELS)
