"""driver_idle_share (%): the share of the traced window in which the device
is idle and the innermost open program span is one of (repro.cp_als*,
repro.cp_als_batched*, repro.tucker*): the drivers' own host work: sweep
loop glue, each mode update's Gram/solve/normalize tail, the fit and its
sync, HOSVD and eigh dispatch.  Averaged over the chips; nothing where the
program opens no such span."""

from benchlib import owners


def read(run):
    return owners.share(run, owners.DRIVERS)
