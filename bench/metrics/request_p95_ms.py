"""request_p95_ms: 95th percentile, over every request of the window, of
the time from the call until its results are on the host."""

import numpy as np


def read(ctx):
    reqs = ctx["requests"]
    if not reqs:
        return None
    lat = np.asarray([end - start for start, end, _ in reqs])
    return 1e3 * float(np.percentile(lat, 95))
