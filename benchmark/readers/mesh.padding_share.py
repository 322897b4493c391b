"""Share of the rows dispatched inside the window that were padding: one
minus rows over rows after ``bucket_batch_size``, counted by the harness's
wrapper around ``runner.dispatch``."""


def read(m):
    inside = [(rows, padded) for at, rows, padded in m.dispatches
              if m.t0 <= at < m.t1]
    padded = sum(p for _, p in inside)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(r for r, _ in inside) / padded)
