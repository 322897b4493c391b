"""Median ``wait_s`` (submit to claim, the program's clock) of the responses
that became visible inside the window."""
from vftbench import stats


def read(m):
    return stats.median([r["wait_s"] for r in m.responses if "wait_s" in r])
