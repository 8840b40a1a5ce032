"""solve_s (s): the whole window's time, by the host's clock, over the
decompositions completed in it (requests, where a unit serves several)."""


def read(run):
    return run.window_s / max(run.units, 1)
