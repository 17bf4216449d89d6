"""chip_smoke.py's phases at small n on the CPU, and its refusals.

The phases are the ones the chip runs at 1M nodes: the NumPy BFS reference
against the engine (hybrid and gather), SimService tickets against the
reference, and the 4-ring under both halo backends against the single
device. ``main()`` itself must refuse any platform but a TPU.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _checks(capsys):
    """The phase's check lines, by name (read once: capsys drains)."""
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return {ln["check"]: ln for ln in lines}


def test_engine_matches_bfs_reference(capsys):
    out = chip_smoke.phase_main(3000, require_kernel=False)
    assert set(out) == {"hybrid", "gather"}
    assert out["hybrid"] == out["gather"]
    checks = _checks(capsys)
    for method in ("hybrid", "gather"):
        line = checks[f"flood_{method}"]
        assert line["equals_bfs"] is True and line["rounds"] >= 1


def test_serving_tickets_match_reference(capsys):
    out = chip_smoke.phase_serving(2000, floods=12, capacity=64)
    recs = out["records"]
    assert len(recs) == 12 and all(r["status"] == "done" for r in recs)
    line = _checks(capsys)["serving"]
    assert line["tickets"] == line["equal_bfs"] == 12


def test_four_chip_phase_on_virtual_devices(capsys):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")
    out = chip_smoke.phase_four_chips(3000)
    assert out["ppermute"] == out["pallas"]
    checks = _checks(capsys)
    for comm in ("ppermute", "pallas"):
        assert checks[f"ring_{comm}"]["bit_identical_to_single"] is True


def test_reference_check_is_not_vacuous():
    # One flipped bit or one round off must fail the check.
    g, _, _ = chip_smoke.build_main_graph(1000)
    edges = chip_smoke.host_edges(g)
    rounds, seen = chip_smoke.reference_run(
        chip_smoke.bfs_levels(edges, 0), int(edges[2].sum()))
    chip_smoke.check_against_reference("ok", {"rounds": rounds}, seen,
                                       rounds, seen)
    flipped = seen.copy()
    flipped[np.flatnonzero(seen)[-1]] = False
    with pytest.raises(chip_smoke.SmokeFailure, match="seen set"):
        chip_smoke.check_against_reference("bad", {"rounds": rounds},
                                           flipped, rounds, seen)
    with pytest.raises(chip_smoke.SmokeFailure, match="rounds"):
        chip_smoke.check_against_reference("bad", {"rounds": rounds + 1},
                                           seen, rounds, seen)


def test_main_refuses_a_non_tpu_platform(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "not a TPU" in captured.err


def test_lone_copy_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_cache_left_to_the_environment(monkeypatch):
    # Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and the
    # helper sets nothing (jax.config.update is stubbed: the tests never
    # turn the persistent cache on).
    from p2pnetwork_tpu.utils import jax_env

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert jax_env.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []


def test_compile_cache_defaults_into_the_checkout(monkeypatch):
    from p2pnetwork_tpu.utils import jax_env

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = jax_env.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
        assert ".jax_cache/" in f.read().split()
