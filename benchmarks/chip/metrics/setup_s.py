"""setup_s (s): process start to the first timed unit, by the host's
clock: data made from the seed, the warm pass over the window's shapes
and every compile."""


def read(run):
    return run.setup_s
