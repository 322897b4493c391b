"""90th percentile of the same latencies as request_s_p50: the highest
percentile that keeps ten samples beyond it at a hundred requests."""
from vftbench import stats


def read(m):
    samples = m.latencies()
    print(f"vftbench: request_s_p90: {len(samples)} requests due in the "
          f"window, {stats.samples_beyond(len(samples), 90.0)} beyond the "
          "percentile")
    return stats.percentile(samples, 90.0)
