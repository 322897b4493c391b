"""Median seconds from when a request was due to when its response was
visible in done/, over the requests due inside the window (harness clock)."""
from vftbench import stats


def read(m):
    return stats.percentile(m.latencies(), 50.0)
