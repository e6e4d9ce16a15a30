"""How late the open-loop generator sent its requests: the 99th percentile
of submit time minus due time over the window, in ms (host clock)."""

import numpy as np


def read(run):
    lg = run["log"]
    idx = lg.attempted()
    lag = (lg.submit[idx] - lg.due[idx]) * 1e3
    lag = lag[~np.isnan(lag)]
    return float(np.percentile(lag, 99)) if len(lag) else None
