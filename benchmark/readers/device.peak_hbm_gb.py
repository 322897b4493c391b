"""Peak bytes held on the fullest chip over the whole process, warm-up
included, in GB: ``memory_stats()``'s ``peak_bytes_in_use`` (parameters,
inputs, outputs) plus ``peak_bytes_reserved``, where this backend books the
temporaries of the largest program it ran (``device.memory_peak_bytes``).
``peak_bytes_in_use`` alone, which ISSUE 22 named, leaves the temporaries
out: 0.33 of 1.98 GB for the 128-clip r21d program."""


def read(m):
    return m.memory_peak_bytes / 1e9 if m.memory_peak_bytes else None
