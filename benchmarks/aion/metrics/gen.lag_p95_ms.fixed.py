"""How late the open-loop generator sent its steps: the 95th percentile
of send time minus due time over the window's steps, in ms. A starved
generator would read as a fast engine without it."""

import numpy as np


def read(rec):
    lag = rec["gen_lag_s"]
    if not lag:
        return None
    return float(np.percentile(np.asarray(lag), 95)) * 1e3
