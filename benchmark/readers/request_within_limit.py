"""Share of the requests due inside the window whose response was visible
within the mix's ``latency_limit_s`` of when they were due (harness clock). A
failed or unanswered request counts as the drain limit, so it misses."""


def read(m):
    samples = m.latencies()
    if not samples or not m.latency_limit_s:
        return None
    within = sum(1 for s in samples if s <= m.latency_limit_s)
    print(f"vftbench: request_within_limit: {within} of {len(samples)} "
          f"requests within {m.latency_limit_s} s")
    return 100.0 * within / len(samples)
