"""Host seconds spent building a device batch (the program's
``batch.assemble``, ``packer.stack`` and ``mesh.pad`` spans: the extractors'
``np.stack``s, the packer's group stack, the pad to the wire bucket) inside
the window, all threads, per unit."""
from vftbench import timeline


def read(m):
    return timeline.span_s_per_unit(m, "batch.assemble", "packer.stack",
                                    "mesh.pad")
