"""StageProfiler ``write`` seconds inside the window per unit: serialise,
hash, write, fsync and rename of every artifact."""


def read(m):
    return m.per_unit(m.stage_s("write"))
