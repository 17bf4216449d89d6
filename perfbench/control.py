"""The control: the reference put in the program's place with one
guarantee broken, to show that the comparison in ``check.py`` fails it.

The configurations state their guarantee as "reaches the coverage target
in the BFS's rounds, with the BFS's seen set (and message count)". The
tempting shortcut is a flood that stops one round early: the control
answers each checked flood or ticket with the reference's state one round
before the target was met.

    python perfbench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

runs the cell's set-up once, then for each seed one window of the program,
and prints per seed the numbers the program's answers give and those the
control's give (the readings the limits in ``check.py`` were set from).
It needs the chips the cell asks for; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check  # noqa: E402
import harness  # noqa: E402
from reference import bfs  # noqa: E402


def _early(overlay, source: int, target: float, max_rounds: int):
    """``(rounds, seen, messages)`` one round short of the reference."""
    dist = bfs.bfs_levels(overlay.edges, int(source))
    rounds, _ = bfs.reference_run(dist, overlay.n, target, max_rounds)
    early = max(rounds - 1, 0)
    seen = (dist >= 0) & (dist <= early)
    return early, seen, bfs.messages(dist, overlay.out_degree, early)


def flood_items(items, overlay, target: float, max_rounds: int, n_padded: int):
    out = []
    for it in items:
        rounds, seen, msgs = _early(overlay, it["source"], target, max_rounds)
        padded = np.zeros(n_padded, bool)
        padded[:seen.size] = seen
        out.append({"source": it["source"], "rounds": rounds,
                    "messages": msgs, "seen": padded})
    return out


def ticket_records(records, overlay, target: float, max_rounds: int,
                   n_padded: int):
    out = []
    for rec in records:
        rounds, seen, _ = _early(overlay, rec["source"], target, max_rounds)
        padded = np.zeros(n_padded, bool)
        padded[:seen.size] = seen
        out.append({**rec, "status": "done", "rounds": rounds,
                    "seen_count": int(seen.sum()),
                    "seen_sha256": bfs.seen_sha256(padded)})
    return out


def readings(driver, kind: str, seed: int, seconds: float) -> dict:
    """One window of the program and the control's answers in its place."""
    ctx = driver.ctx
    if kind == "flood":
        win = driver.window(seed, seconds)
        prog = driver.check(win["items"])
        ctrl = check.floods(
            flood_items(win["items"], ctx.overlay, driver.target,
                        driver.max_rounds, ctx.graph.n_nodes_padded),
            ctx.overlay, driver.target, driver.max_rounds)
        work = {"floods": len(win["floods"])}
    else:
        win = driver.window(seed, seconds)
        prog = driver.check(seed, win)
        max_rounds = int(driver.service_cfg["max_ticket_rounds"])
        swapped = dict(win)
        swapped["records"] = ticket_records(
            [r for r in win["records"] if r is not None], ctx.overlay,
            driver.target, max_rounds, driver.n_padded)
        swapped["accepted"] = swapped["records"]
        ctrl = driver.check(seed, swapped)
        work = {k: win["stats"][k] for k in ("offered", "shed")}
    return {"seed": seed, "program": {"correct": check.verdict(prog)[0], **prog},
            "control": {"correct": check.verdict(ctrl)[0], **ctrl}, **work}


def main(argv=None) -> int:
    import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    harness.configure_process()
    spec = harness.benchmark_spec()
    cell, config, traffic = harness.load_cell(spec, args.workload)
    try:
        device = harness.require_chips(int(cell["chips"]))
    except harness.NoAccelerator as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = run.Context(spec, cell, config, traffic, seeds[0], args.seconds,
                      False)
    ctx.device = device
    run.build_graph(ctx)
    kind = traffic["driver"]
    driver = harness.load_module("drivers", kind).Driver(ctx)
    try:
        driver.setup(seeds[0])
        for seed in seeds:
            print(json.dumps(readings(driver, kind, seed, args.seconds)),
                  flush=True)
    finally:
        driver.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
