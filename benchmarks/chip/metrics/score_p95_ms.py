"""95th percentile, over every request due in the window, of the time
from its due time in the open-loop schedule to its result on the host
(nearest rank; a failed, refused or unanswered request is infinitely
late)."""

import math


def read(run):
    lat = run.window.get("latency_s")
    if lat is None or not len(lat):
        return None
    v = sorted(float(x) for x in lat)[math.ceil(0.95 * len(lat)) - 1]
    return v * 1e3
