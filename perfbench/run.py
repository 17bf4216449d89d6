"""Run one benchmark cell once, in this process, on the chips it asks for.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (graph, compile or cache hit, warm-up) is timed from the start of
the process to the first timed operation. The cell's driver then measures
for ``--seconds``; the window's answers are checked against the plain
reference once the window has closed and the program's state is freed.
The last stdout line is the result; the numbers compared, each beside its
limit, are the last lines on stderr and the last key of the result.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

T_PROCESS = harness.process_start()
#: Persistent-cache lookups seen so far, by outcome (``count_compiles``).
COMPILES: dict = {}


def program_counters() -> dict:
    """The program's counters and histograms (``/metrics``,
    ``telemetry.default_registry()``), flat: ``name{label=value,...}`` to
    its value, a histogram as its ``_sum`` and ``_count``."""
    from p2pnetwork_tpu import telemetry

    out = {}
    for name, family in telemetry.default_registry().snapshot().items():
        if family["type"] not in ("counter", "histogram"):
            continue
        for sample in family["samples"]:
            labels = ",".join(f"{k}={v}" for k, v in
                              sorted(sample["labels"].items()))
            labels = f"{{{labels}}}" if labels else ""
            if "count" in sample:
                out[f"{name}_sum{labels}"] = sample["sum"]
                out[f"{name}_count{labels}"] = sample["count"]
            else:
                out[f"{name}{labels}"] = sample["value"]
    return out


def moved(before: dict, after: dict) -> dict:
    """The counters that moved between two :func:`program_counters`
    readings, by how much."""
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def count_compiles() -> None:
    import jax

    def on_event(event: str, **kw) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            name = event.rsplit("/", 1)[-1]
            COMPILES[name] = COMPILES.get(name, 0) + 1
    jax.monitoring.register_event_listener(on_event)


class Context:
    """What a driver gets: the cell, its files, the program's graph, the
    benchmark's overlay, the ring the graph is placed on (``mesh``, ``None``
    on one chip), the tracer, and the seams it reports through."""

    def __init__(self, spec, cell, config, traffic, seed, seconds, trace):
        import tracing

        self.spec, self.cell = spec, cell
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = int(seed), float(seconds)
        self.tracer = tracing.Tracer(trace, os.path.join(
            harness.TRACE_DIR, cell["name"]))
        self.graph = self.overlay = self.mesh = None
        self.setup_s = None
        self.memory_peak_bytes = None
        self.graph_info = {}
        self.compiles_at_open = {}
        self.window_compiles = {}
        self.counters_at_open = {}
        self.window_counters = {}
        self.released_at = None
        self.layout_cache = True

    def window_open(self) -> None:
        self.compiles_at_open = dict(COMPILES)
        self.counters_at_open = program_counters()

    def compiles_in_window(self) -> dict:
        """Cache hits and misses (misses compile) between the window's open
        and close: a miss there is a compile inside the window."""
        return {k: COMPILES.get(k, 0) - self.compiles_at_open.get(k, 0)
                for k in ("cache_hits", "cache_misses")}

    def mark(self, what: str) -> None:
        harness.log(f"{time.perf_counter() - T_PROCESS:.3f} s: {what}")

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - T_PROCESS
        self.mark("set-up done")

    def window_closed(self) -> None:
        self.memory_peak_bytes = harness.memory_peak_bytes(self.cell["chips"])
        self.window_compiles = self.compiles_in_window()
        self.window_counters = moved(self.counters_at_open,
                                     program_counters())

    def release_program(self) -> None:
        """Drop the program's graph and state before the reference runs."""
        self.graph = None
        gc.collect()
        self.released_at = time.perf_counter()


def per_layer(ctx, result: dict, trace_numbers) -> dict:
    """Each per-layer metric of the cell, from its reader; a reader that
    finds nothing to read returns ``None`` and the metric is left out."""
    out = {}
    for m in harness.cell_metrics(ctx.spec, ctx.cell["name"], "per_layer"):
        reader = harness.load_module("metrics", m["name"])
        value = reader.read(result["stats"], trace_numbers)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def build_graph(ctx) -> None:
    """The ring the configuration places its overlay on (refused before
    any graph is built where it does not fit the cell's chips), then the
    program's graph and the benchmark's overlay."""
    import graphs

    ctx.mesh = graphs.mesh(ctx.config, int(ctx.cell["chips"]))
    ctx.graph, ctx.overlay, ctx.graph_info = graphs.build(
        ctx.config, on_host=ctx.mesh is not None, cache=ctx.layout_cache)
    ctx.mark(f"graph {json.dumps(ctx.graph_info)}")


def run_cell(ctx) -> dict:
    """Drive the cell; returns the result line's fields."""
    import check

    build_graph(ctx)
    driver = harness.load_module("drivers", ctx.traffic["driver"])
    result = driver.run(ctx)
    result["stats"]["check_s"] = time.perf_counter() - ctx.released_at
    result["stats"]["counters"] = ctx.window_counters
    correct, shown = check.verdict(result["numbers"])

    device = dict(ctx.device)
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"]}
    if ctx.tracer.enabled:
        import tracing

        numbers = tracing.reduce(*tracing.read_events(ctx.tracer.xplane()),
                                 chips=ctx.cell["chips"])
        device["busy_s"] = numbers["busy_s"]
        device["window_s"] = numbers["window_s"]
        line["metrics"] = per_layer(ctx, result, numbers)
        line["breakdown"] = numbers["breakdown"]
    else:
        metrics = {}
        for m in harness.cell_metrics(ctx.spec, ctx.cell["name"],
                                      "end_to_end"):
            value = ctx.setup_s if m["name"] == "setup_s" \
                else result["end_to_end"][m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
    line["device"] = device
    stats = {k: v for k, v in result["stats"].items()
             if k not in ("floods", "phases0", "phases1")}
    stats["window_compiles"] = ctx.window_compiles
    stats["host_peak_rss_bytes"] = \
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    harness.log(f"stats {json.dumps(stats)}")
    line["checks"] = shown
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    harness.configure_process()
    spec = harness.benchmark_spec()
    cell, config, traffic = harness.load_cell(spec, args.workload)
    try:
        device = harness.require_chips(int(cell["chips"]))
    except harness.NoAccelerator as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    count_compiles()
    ctx = Context(spec, cell, config, traffic, args.seed, args.seconds,
                  bool(args.trace))
    ctx.device = device
    line = run_cell(ctx)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"check correct {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
