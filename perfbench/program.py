"""The program's own spans and counters, for the per-layer readers.

A reader sees ``(stats, trace)``: the driver's window stats and the
reduction of the traced window (``tracing.reduce``). What the program
measures of itself is read where it already lands:

- its spans: ``telemetry/spans.py`` mirrors every span onto the
  profiler's host timeline as ``graftscope.<span>``, so they are in the
  run's own ``.xplane.pb`` (the newest under ``harness.TRACE_DIR``, taken
  only if its window is the one ``trace`` was reduced from), summed over
  the window;
- the serving plane's queue wait: ``SimService.tick_phases()`` carries
  ``queue_wait`` totals, and the serve driver keeps its readings at the
  window's open and close (``phases0``, ``phases1``).

A program without them (an older one) leaves its metrics out: the readers
return ``None``. The first reader of a traced run also prints the window's
device-idle gaps by the innermost program span over each (``# idle_by_span``
on stderr); gaps under none go to one residue row.
"""

from __future__ import annotations

import glob
import heapq
import os
import time
from collections import defaultdict

import harness
import tracing

#: A program span's name on the profiler timeline is this prefix and its
#: own name (``telemetry/spans.py``'s ``PROFILER_PREFIX``, copied: the
#: benchmark also reads traces of a program older than that constant).
PREFIX = "graftscope."
RESIDUE = "no program span"

_read: dict = {}


def innermost(idle_gaps, events, residue: str = RESIDUE) -> dict:
    """Seconds of the ``idle_gaps`` (ns) by the innermost (shortest) of
    ``events`` covering each gap's middle; gaps under none go to
    ``residue``. A sweep over the gaps in order, with the events begun so
    far in a heap by length; an event that ended before one gap's middle
    covers no later."""
    inner = sorted(events)
    idle, begun, i = defaultdict(float), [], 0
    for gs, ge in idle_gaps:
        mid = (gs + ge) / 2
        while i < len(inner) and inner[i][0] <= mid:
            s, e, n = inner[i]
            heapq.heappush(begun, (e - s, e, n))
            i += 1
        while begun and begun[0][1] < mid:
            heapq.heappop(begun)
        idle[begun[0][2] if begun else residue] += (ge - gs) / 1e9
    return dict(idle)


def window_spans(device_ops: dict, host_events: list) -> dict:
    """Of the ``perfbench_window``: its length, each program span's
    seconds inside it (``spans``, by name without the prefix) and its
    device-idle seconds by innermost program span (``idle_by_span``,
    largest first), on the first device as ``tracing.reduce`` takes it."""
    a, b = tracing.window_bounds(host_events)
    ours = [(s, e, n[len(PREFIX):]) for s, e, n in host_events
            if n.startswith(PREFIX) and e > a and s < b]
    seconds = defaultdict(float)
    for s, e, n in ours:
        seconds[n] += (min(e, b) - max(s, a)) / 1e9
    first = sorted(device_ops)[0]
    idle = innermost(
        tracing.gaps(tracing.merge((s, e) for s, e, _ in device_ops[first]),
                     a, b), ours)
    return {"window_s": (b - a) / 1e9, "spans": dict(seconds),
            "idle_by_span": sorted(([n, t] for n, t in idle.items()),
                                   key=lambda nt: -nt[1])}


def newest_xplane():
    paths = glob.glob(os.path.join(harness.TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def traced_window(trace):
    """:func:`window_spans` of the run's own trace, read once a process;
    ``None`` without a reduced trace, or when the newest ``.xplane.pb``
    holds another window than ``trace``'s."""
    if not trace:
        return None
    path = newest_xplane()
    if path is None:
        return None
    if path not in _read:
        t0 = time.perf_counter()
        try:
            got = window_spans(*tracing.read_events(path))
        except ValueError:  # no window: not this run's trace
            got = None
        else:
            harness.log(f"idle_by_span {got['idle_by_span']} (trace re-read "
                        f"in {time.perf_counter() - t0:.3f} s)")
        _read[path] = got
    got = _read[path]
    return got if got and got["window_s"] == trace["window_s"] else None


def per_flood_ms(stats: dict, trace, span: str):
    """A program span's seconds inside the traced window over the window's
    floods, in ms; ``None`` when the trace holds no such span or no flood
    ran."""
    got, floods = traced_window(trace), stats.get("n_floods")
    if got is None or not floods or not got["spans"].get(span):
        return None
    return got["spans"][span] / floods * 1e3


def queue_wait_ms(stats: dict):
    """Mean submit-to-lane wall of the tickets admitted between the serve
    driver's two ``tick_phases()`` readings, in ms; ``None`` when the
    service keeps no such totals or admitted nothing in between."""
    q0 = (stats.get("phases0") or {}).get("queue_wait")
    q1 = (stats.get("phases1") or {}).get("queue_wait")
    if not q0 or not q1 or q1["count"] <= q0["count"]:
        return None
    return (q1["total_s"] - q0["total_s"]) / (q1["count"] - q0["count"]) * 1e3
