"""Per flood: the seconds of the program's own ``run_dispatch`` spans in the
traced window (from a run-to-* entry until its jitted loop call returns:
the stats check, retrace, lowering, cache load and launch), over the
window's floods, in ms."""

import program


def read(stats, trace):
    return program.per_flood_ms(stats, trace, "run_dispatch")
