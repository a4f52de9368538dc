"""iter_s.hybrid: ``iter_s`` of the hybrid cell, host seconds of the whole
measured window over the simulated iterations completed in it.  A metric
of its own because the hybrid cell's runs spread several times wider than
the others' and need a wider bound."""


def read(rec):
    return rec["window_s"] / len(rec["requests"])
