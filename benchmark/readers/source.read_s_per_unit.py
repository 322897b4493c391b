"""Seconds inside the cv2 read and the grab()-skip of dropped frames
(the program's ``decode.read`` and ``decode.skip`` spans, children of its
``decode`` stage) inside the window, all threads, per unit completed in it."""
from vftbench import timeline


def read(m):
    return timeline.span_s_per_unit(m, "decode.read", "decode.skip")
