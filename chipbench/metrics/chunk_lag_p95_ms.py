"""95th percentile, over every chunk of the window, of the time from the
chunk's push to its hit output being ready on the host: the staleness a live
consumer of the stream sees."""
import numpy as np


def read(run):
    lags = run.window.get("lags_s")
    return float(np.percentile(lags, 95)) * 1e3 if lags else None
