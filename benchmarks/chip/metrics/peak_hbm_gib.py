"""peak_hbm_gib (GiB): the device allocator's ``peak_bytes_in_use`` on the
fullest chip, read after the window and before the reference runs."""


def read(run):
    return run.peak_bytes / 2**30
