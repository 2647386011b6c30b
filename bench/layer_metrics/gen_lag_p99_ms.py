"""99th percentile, in ms, of how late the load generator sent each request
after its due time, so that a starved generator is not read as a fast
server."""

import numpy as np


def read(rec):
    lags = rec["window"].get("lags_s")
    if lags is None or not len(lags):
        return None
    return 1e3 * float(np.quantile(lags, 0.99, method="inverted_cdf"))
