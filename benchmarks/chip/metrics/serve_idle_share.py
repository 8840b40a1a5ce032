"""serve_idle_share (%): the share of the traced window in which the device
is idle and the innermost open program span is one of (repro.serve.*): the
server's flush: per-request padding, stacking, initial factors, crops and
reads.  Averaged over the chips; nothing where the program opens no such
span."""

from benchlib import owners


def read(run):
    return owners.share(run, owners.SERVING)
