"""Per flood: the seconds of the program's own ``sim_attach`` spans in the
traced window (``attach_simulation``: the run key and the protocol's
initial state), over the window's floods, in ms."""

import program


def read(stats, trace):
    return program.per_flood_ms(stats, trace, "sim_attach")
