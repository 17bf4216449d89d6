"""Tracing / profiling for the sim backend.

The reference's entire observability story is a ``debug`` flag gating
prints (e.g. per-send compression ratios,
[ref: p2pnetwork/nodeconnection.py:57-58,79-80]) plus three message
counters [ref: node.py:64-67]. The sockets backend keeps that surface
(``Node.debug``, ``message_count_*``, ``EventLog``); this module is the sim
side (SURVEY.md section 5 "Tracing"): per-round propagation stats as
structured records, and XLA-level profiler capture.

- :func:`run_traced` — run a protocol and emit one JSON line per round
  (round index plus every device-side stat), then a summary line with the
  total wall time. All rounds execute inside one ``lax.scan``, so there is
  no per-round wall clock — stats are computed on device and tracing adds
  one transfer at the end, not one per round.
- :func:`profile` — capture an XLA profile (TensorBoard format) around a
  block, via ``jax.profiler.trace``. Regions are named on its timeline by
  ``telemetry.spans.span`` (its profiler mirror); :func:`run_traced`'s
  run is a ``run_traced`` span.
"""

from __future__ import annotations

import contextlib
import json
import time
from typing import IO, Iterator, Optional, Union

import jax
import numpy as np

from p2pnetwork_tpu import telemetry
from p2pnetwork_tpu.telemetry import jaxhooks, spans


@contextlib.contextmanager
def profile(log_dir: str) -> Iterator[None]:
    """Capture an XLA profile of the enclosed block into ``log_dir``
    (view with TensorBoard's profile plugin or Perfetto)."""
    with jax.profiler.trace(log_dir):
        yield


def _open_sink(sink: Union[str, IO, None]):
    if sink is None or hasattr(sink, "write"):
        return sink, False
    return open(sink, "a", encoding="utf-8"), True


def run_traced(
    graph,
    protocol,
    key: jax.Array,
    rounds: int,
    *,
    sink: Union[str, IO, None] = None,
    label: str = "run",
    profile_dir: Optional[str] = None,
):
    """Run ``rounds`` protocol rounds, returning ``(state, records)``.

    ``records`` is a list of dicts, one per round, each holding the round
    index plus every stat the protocol computed on device (floats). When
    ``sink`` is a path or file object, each record is also written as one
    JSON line. ``profile_dir`` additionally captures an XLA profile of the
    compiled run.

    The summary line reports through the telemetry registry (telemetry/):
    ``compile_seconds`` is the backend-compile wall time this run triggered
    (delta of ``jax_compile_seconds_total`` — 0.0 on a cache hit, and when
    jax.monitoring is unavailable), ``device_transfer_bytes`` the size of
    the stats history brought back to host.
    """
    from p2pnetwork_tpu.sim import engine

    reg = telemetry.default_registry()
    hooks_on = jaxhooks.install()  # None-subscription: follows the default
    compile_s0 = jaxhooks.compile_seconds(reg) if hooks_on else 0.0

    ctx = profile(profile_dir) if profile_dir else contextlib.nullcontext()
    t0 = time.perf_counter()
    with ctx:
        with spans.span("run_traced", label=label, rounds=rounds):
            state, stats = engine.run(graph, protocol, key, rounds)
            jax.block_until_ready(stats)
    wall_s = time.perf_counter() - t0
    compile_s = (jaxhooks.compile_seconds(reg) - compile_s0) if hooks_on \
        else 0.0

    host_stats = {k: np.asarray(v) for k, v in stats.items()}
    transfer_bytes = int(sum(v.nbytes for v in host_stats.values()))
    reg.counter(
        "sim_transfer_bytes_total",
        "Bytes moved by device->host summary transfers.").inc(transfer_bytes)
    records = []
    for i in range(rounds):
        rec = {"label": label, "round": i}
        for k, v in host_stats.items():
            rec[k] = float(v[i])  # graftlint: ignore[host-sync-in-loop] -- host_stats is numpy (single transfer above)
        records.append(rec)
    summary = {
        "label": label,
        "summary": True,
        "rounds": rounds,
        "wall_s": wall_s,
        "compile_seconds": compile_s,
        "device_transfer_bytes": transfer_bytes,
        "n_nodes": graph.n_nodes,
        "n_edges": graph.n_edges,
    }

    f, close = _open_sink(sink)
    if f is not None:
        try:
            for rec in records:
                f.write(json.dumps(rec) + "\n")
            f.write(json.dumps(summary) + "\n")
        finally:
            if close:
                f.close()
    return state, records
