"""Median ``latency_s`` (claim to response, the program's clock) of the
responses that became visible inside the window."""
from vftbench import stats


def read(m):
    return stats.median([r["latency_s"] for r in m.responses
                         if "latency_s" in r])
