"""``correct`` on the CPU at a small size: a sound run passes; the control
and each fault the cells can have fail.

Each test drives the rest of a run (``run.run_cell``) past the harness's
look for a chip, on a 3,000-node copy of the cell's configuration."""

import copy

import jax
import numpy as np
import pytest

import control
import harness
import run
import strata
from p2pnetwork_tpu.models.flood import Flood
from p2pnetwork_tpu.models.messagebatch import BatchFlood
from p2pnetwork_tpu.ops import segment
from p2pnetwork_tpu.serve import SimService
from p2pnetwork_tpu.sim import engine

SEED = 2**31 + 99
N = 3000


def make_ctx(workload: str, seconds: float = 0.5):
    spec = harness.benchmark_spec()
    cell, config, traffic = harness.load_cell(spec, workload)
    config = copy.deepcopy(config)
    config["n"] = N
    config["service"].update(capacity=32, max_ticket_rounds=64)
    traffic = dict(traffic, check_sample=6, drain_s=10, warmup_tickets=8,
                   warmup_timeout_s=10,
                   rate_per_s=20.0)
    ctx = run.Context(spec, cell, config, traffic, SEED, seconds, False)
    ctx.device = {"platform": "cpu", "kind": "cpu", "count": 1}
    ctx.layout_cache = False
    return ctx


@pytest.fixture(autouse=True)
def small_strata(monkeypatch):
    # The committed strata are of the 1M overlays; draw the small copy's.
    real = strata.compute
    monkeypatch.setattr(strata, "load",
                        lambda config, mix: real(config, mix, 64)["by_rounds"])


@pytest.fixture(autouse=True)
def fresh_programs():
    # Faults patch traced code; compiled programs must not carry over.
    jax.clear_caches()
    yield
    jax.clear_caches()


CELLS = ["ws-1m.flood", "ba-1m.flood", "ws-1m.serve"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    line = run.run_cell(make_ctx(workload))
    assert line["correct"], line["checks"]
    assert line["checks"]["checked"]["value"] >= 1


# ------------------------------------------------------------- control


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    ctx = make_ctx(workload)
    run.build_graph(ctx)
    kind = ctx.traffic["driver"]
    driver = harness.load_module("drivers", kind).Driver(ctx)
    try:
        driver.setup(SEED)
        got = control.readings(driver, kind, SEED, 0.5)
    finally:
        driver.close()
    assert got["program"]["correct"]
    assert not got["control"]["correct"]
    assert got["control"]["rounds_wrong"] == got["control"]["checked"] > 0


# -------------------------------------------------------------- faults


def _unchanged_flood_step(monkeypatch):
    real = Flood.step

    def step(self, graph, state, key):
        _, stats = real(self, graph, state, key)
        return state, stats
    monkeypatch.setattr(Flood, "step", step)


def _unchanged_batch_step(monkeypatch):
    real = BatchFlood.step

    def step(self, graph, batch, key):
        _, stats = real(self, graph, batch, key)
        return batch, stats
    monkeypatch.setattr(BatchFlood, "step", step)


def _half_the_nodes(monkeypatch):
    real = segment.propagate_or

    def propagate_or(graph, frontier, *a, **kw):
        out = real(graph, frontier, *a, **kw)
        return out & (jax.numpy.arange(out.shape[0]) % 2 == 0)
    monkeypatch.setattr(segment, "propagate_or", propagate_or)


def _half_the_batch(monkeypatch):
    real = engine.run_batch_until_coverage

    def run_batch(*a, **kw):
        batch, out = real(*a, **kw)
        if "newly_completed_lanes" in out:
            out["newly_completed_lanes"] = out["newly_completed_lanes"][::2]
        return batch, out
    monkeypatch.setattr(engine, "run_batch_until_coverage", run_batch)


def _altered_flood_answer(monkeypatch):
    real = engine.run_until_coverage_from

    def run_until(*a, **kw):
        state, out = real(*a, **kw)
        seen = np.asarray(state.seen).copy()
        seen[int(np.flatnonzero(seen)[0])] = False
        return type(state)(seen=jax.numpy.asarray(seen),
                           frontier=state.frontier), out
    monkeypatch.setattr(engine, "run_until_coverage_from", run_until)


def _altered_ticket_answer(monkeypatch):
    real = SimService._hash_lanes

    def hash_lanes(self, lanes):
        return {lane: "0" + h[1:] for lane, h in real(self, lanes).items()}
    monkeypatch.setattr(SimService, "_hash_lanes", hash_lanes)


@pytest.mark.parametrize("workload,fault", [
    ("ws-1m.flood", _unchanged_flood_step),
    ("ws-1m.flood", _half_the_nodes),
    ("ws-1m.flood", _altered_flood_answer),
    ("ba-1m.flood", _unchanged_flood_step),
    ("ba-1m.flood", _altered_flood_answer),
    ("ws-1m.serve", _unchanged_batch_step),
    ("ws-1m.serve", _half_the_batch),
    ("ws-1m.serve", _altered_ticket_answer),
], ids=lambda v: v if isinstance(v, str) else v.__name__.strip("_"))
def test_a_fault_makes_the_run_not_correct(monkeypatch, workload, fault):
    fault(monkeypatch)
    line = run.run_cell(make_ctx(workload))
    assert not line["correct"], line["checks"]
