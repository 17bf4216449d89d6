"""Closed loop of floods through the user-facing node.

One ``JaxSimNode`` is kept for the run. Each flood is what a protocol
designer writes: ``attach_simulation(graph, Flood(source=s))`` with
``Flood``'s default lowering, then ``run_until_coverage(target,
max_rounds)``. Every flood has a new source, from the configuration's
strata (``strata.py``): the same sequence of round counts for every seed,
the sources of each count picked by the seed. The window
runs from the first timed flood's start to the end of the flood in flight
when ``seconds`` have passed; ``flood_s`` is its length over the floods in
it.
"""

from __future__ import annotations

import time

import numpy as np

import check
import strata
import traffic


def flood_seconds(window_start: float, ends) -> float:
    """Window seconds over floods completed: the window closes at the end
    of the last flood, the one in flight when the time ran out."""
    if not ends:
        raise ValueError("no flood completed in the window")
    return (ends[-1] - window_start) / len(ends)


def seen_of(state):
    """A flood's seen set: the engine's ``FloodState.seen``, or the ring's
    ``(seen, frontier)`` pair, ``[shards, block]`` in node order."""
    return state[0] if isinstance(state, tuple) else state.seen


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        mix = ctx.traffic
        self.target = float(mix["coverage_target"])
        self.max_rounds = int(mix["max_rounds"])
        self.node = None
        self.strata = strata.load(ctx.config, mix)

    def flood(self, source: int) -> dict:
        from p2pnetwork_tpu.models.flood import Flood

        ring = {} if self.ctx.mesh is None else {"mesh": self.ctx.mesh}
        self.node.attach_simulation(self.ctx.graph, Flood(source=int(source)),
                                    **ring)
        return self.node.run_until_coverage(self.target,
                                            max_rounds=self.max_rounds)

    def sources(self, seed: int) -> np.ndarray:
        """The seed's sources: the first ``warmup_floods`` warm up, the
        rest are the window's."""
        return traffic.stratified_sources(traffic.rng_for(seed), self.strata,
                                          int(self.ctx.traffic["max_floods"]))

    def setup(self, seed: int) -> None:
        from p2pnetwork_tpu.sim.simnode import JaxSimNode

        self.node = JaxSimNode("127.0.0.1", 0)
        self.node.start()
        for s in self.sources(seed)[:int(self.ctx.traffic["warmup_floods"])]:
            self.flood(s)

    def window(self, seed: int, seconds: float) -> dict:
        """Floods from the seed's sources after the warm-up's until
        ``seconds`` have passed; returns their records and, for the
        sampled ones, the seen sets (each flood's is kept on the device
        until the window has closed)."""
        mix, tracer = self.ctx.traffic, self.ctx.tracer
        pool = self.sources(seed)[int(mix["warmup_floods"]):]
        floods, seen = [], []
        tracer.start()
        self.ctx.window_open()
        t0 = time.perf_counter()
        deadline = t0 + seconds
        with tracer.span("window"):
            for s in pool:
                start = time.perf_counter()
                with tracer.span("flood"):
                    summary = self.flood(s)
                end = time.perf_counter()
                floods.append({"source": int(s), "start": start, "end": end,
                               "rounds": int(summary["rounds"]),
                               "messages": int(summary["messages"])})
                seen.append(seen_of(self.node.sim_state))
                if end >= deadline:
                    break
        tracer.stop()
        if floods[-1]["end"] < deadline:
            raise RuntimeError(f"the pool of {pool.size} floods ran out "
                               "before the window closed")
        self.ctx.window_closed()
        picked = check.sample(traffic.rng_for(seed, 3), len(floods),
                              int(mix["check_sample"]))
        items = [{**floods[i], "seen": np.asarray(seen[i])} for i in picked]
        return {"t0": t0, "floods": floods, "items": items,
                "window_s": floods[-1]["end"] - t0}

    def close(self) -> None:
        if self.node is not None:
            self.node.stop()
            self.node.join()
            self.node = None

    def check(self, items) -> dict:
        return check.floods(items, self.ctx.overlay, self.target,
                            self.max_rounds)


def run(ctx) -> dict:
    driver = Driver(ctx)
    try:
        driver.setup(ctx.seed)
        ctx.setup_done()
        win = driver.window(ctx.seed, ctx.seconds)
    finally:
        driver.close()
    ctx.release_program()
    floods, t0 = win["floods"], win["t0"]
    return {
        "attempted": len(floods),
        "failed": 0,
        "end_to_end": {"flood_s": flood_seconds(t0, [f["end"] for f in floods])},
        "numbers": driver.check(win["items"]),
        "stats": {"floods": floods, "n_floods": len(floods),
                  "flood_seconds": [f["end"] - f["start"] for f in floods],
                  "window_s": win["window_s"],
                  "rounds": sum(f["rounds"] for f in floods)},
    }
