"""Mean wall wait of a ticket from ``submit`` until the service's admit
phase gives it a lane (``serve_queue_wait_seconds``), over the tickets
admitted in the window: the service's own ``tick_phases()["queue_wait"]``
totals, differenced across the window, in ms."""

import program


def read(stats, trace):
    program.traced_window(trace)  # prints the window's idle_by_span
    return program.queue_wait_ms(stats)
