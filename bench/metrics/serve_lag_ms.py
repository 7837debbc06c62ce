"""95th percentile of how late the open-loop generator issued a request
against its due time, in ms (host clock)."""
import numpy as np


def read(run):
    lag = getattr(run, "lag_s", None)
    return None if lag is None or not len(lag) else float(
        np.percentile(lag, 95) * 1e3)
