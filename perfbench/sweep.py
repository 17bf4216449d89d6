"""The knee sweep of an open-loop cell: one set-up, then one window at each
offered rate, each drained before the next.

    python perfbench/sweep.py --workload ws-1m.serve --rates 10,20,30 \
        --seconds 30 --seed 7

The knee is the highest offered rate at which nothing is shed and the
queue does not grow over the window (``queue_at_close`` stays near zero and
the latency does not climb with the rate's window). The cell's mix then
offers 0.8 times it, fixed in its traffic file; the benchmark's own runs
never sweep. Needs the chips the cell asks for.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    import check
    import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    harness.configure_process()
    spec = harness.benchmark_spec()
    cell, config, traffic = harness.load_cell(spec, args.workload)
    try:
        device = harness.require_chips(int(cell["chips"]))
    except harness.NoAccelerator as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    ctx = run.Context(spec, cell, config, traffic, args.seed, args.seconds,
                      False)
    ctx.device = device
    run.build_graph(ctx)
    driver = harness.load_module("drivers", traffic["driver"]).Driver(ctx)
    try:
        driver.setup(args.seed)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            win = driver.window(args.seed + i, args.seconds, rate)
            stats = {k: v for k, v in win["stats"].items()
                     if k not in ("phases0", "phases1")}
            correct = check.verdict(driver.check(args.seed + i, win))[0]
            print(json.dumps({"rate_per_s": rate, **win["end_to_end"],
                              **stats, "correct": correct}), flush=True)
    finally:
        driver.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
