"""Units served per second of the window, on the harness's clock: the
least-squares slope of cumulative units against time over the window, counting
a request whole at the instant its response became visible in done/
(``stats.tapered_rate``). The plain count over the window's length is printed
beside it: responses are few and large and come in clusters (six workers take
turns on one device), so the count moves by a whole cluster with where an edge
of the window happens to fall."""
from vftbench import stats


def read(m):
    if not m.completions or m.window_s <= 0:
        return None
    print(f"vftbench: units_per_s: plain count {m.units() / m.window_s:.4f} "
          f"units/s ({m.units()} units in {m.window_s:.3f} s)")
    return stats.tapered_rate(m.completions, m.t0, m.t1)
