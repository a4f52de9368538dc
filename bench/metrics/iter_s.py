"""iter_s: host seconds of the whole measured window over the simulated
iterations completed in it."""


def read(rec):
    return rec["window_s"] / len(rec["requests"])
