"""Process start to the opening of the measured window: worker start,
weights, every step's first call (compile or cache load), warm-up and the
traffic's lead-in."""


def read(run):
    return run["setup_s"]
