"""Process start to the first instant of the measured window (harness clock):
imports, corpus, extractor construction, warm-up and, in open and closed
loops, the ramp."""


def read(m):
    return m.setup_s
