"""iter_s_p90: 90th percentile of the host seconds of every request of
the window (linear interpolation between order statistics)."""
import numpy as np


def read(rec):
    return float(np.percentile([r["host_s"] for r in rec["requests"]], 90))
