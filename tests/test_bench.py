"""The bench harness itself: stage orchestration, early headline emission,
graph caching, and failure reporting.

``bench.py`` is parsed from its last JSON stdout line. These tests pin the
1M record printed before the 10M stage starts, the per-stage child
processes under hard timeouts (the parent never touches JAX), the
build-once graph cache, and that a missing TPU or any failing stage,
method or column ends the run non-zero with an error record.

Runs tiny configs (BENCH_N_*) on the CPU backend: orchestration behavior,
not performance, is under test.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _env(cache_dir, **extra):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_N_1M": "2000",
        "BENCH_N_10M": "3000",
        # The batched message-plane column rides the 1m stage: tiny B and
        # graph so orchestration (not throughput) is what the tests pay.
        "BENCH_BATCH_N": "1500",
        "BENCH_BATCH_B": "40",
        # The serving column drives open-loop traffic through SimService
        # on the batched class: tiny capacity/rate so orchestration (not
        # sustained throughput) is what the tests pay.
        "BENCH_SERVE_CAP": "40",
        "BENCH_SERVE_TICKS": "4",
        "BENCH_SERVE_RATE": "15",
        # The queries column runs the three batched query families:
        # tiny K and a tiny chord overlay so orchestration (not the
        # 100k-node ratchet shapes) is what the tests pay — and OFF by
        # default in this suite: six extra XLA compiles per bench child
        # would tax every orchestration test, so only the shared
        # first_run fixture (which pins the published column) pays them.
        "BENCH_QUERIES": "0",
        "BENCH_QUERY_K_MINPLUS": "8",
        "BENCH_QUERY_K_PUSHSUM": "4",
        "BENCH_QUERY_K_DHT": "16",
        "BENCH_QUERY_DHT_N": "512",
        # The multichip ring column runs on the 8 virtual CPU devices the
        # suite conftest pins (XLA_FLAGS, inherited): tiny graph so the
        # tests pay orchestration, not the interpret/compile bill.
        "BENCH_MULTICHIP_N": "1024",
        # bench.py places JAX's persistent compile cache in the checkout;
        # the tests keep it off.
        "JAX_ENABLE_COMPILATION_CACHE": "false",
        "BENCH_CACHE_DIR": str(cache_dir),
        # Stage children write BENCH_TELEMETRY*.json; keep test artifacts
        # out of the repo root.
        "BENCH_TELEMETRY_DIR": str(cache_dir),
    })
    env.update({k: str(v) for k, v in extra.items()})
    # The suite conftest pins XLA_FLAGS for the 8-device mesh; children
    # inherit it harmlessly (bench uses only the default device).
    return env


def _run(cache_dir, timeout=600, **extra):
    r = subprocess.run([sys.executable, BENCH], env=_env(cache_dir, **extra),
                       capture_output=True, text=True, timeout=timeout,
                       cwd=REPO)
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    return r, [json.loads(ln) for ln in lines]


@pytest.fixture(scope="module")
def first_run(tmp_path_factory):
    cache = tmp_path_factory.mktemp("bench_cache")
    r, recs = _run(cache, BENCH_QUERIES="1")
    # Snapshot THIS run's 1M artifact: later tests re-run bench over the
    # same cache dir with the suite's default env (queries off), which
    # overwrites BENCH_TELEMETRY.json — column tests that need the
    # queries-enabled artifact read the snapshot. Guarded: a failed
    # bench child writes no artifact, and the dependent tests' own
    # returncode asserts must surface that stderr, not a copy error.
    import shutil
    if (cache / "BENCH_TELEMETRY.json").exists():
        shutil.copy(cache / "BENCH_TELEMETRY.json",
                    cache / "BENCH_TELEMETRY_first.json")
    return cache, r, recs


class TestOrchestration:
    def test_emits_headline_before_and_after_scale_stage(self, first_run):
        _, r, recs = first_run
        assert r.returncode == 0, r.stderr[-2000:]
        # Two JSON lines: the 1M-only record the moment it is measured,
        # then the merged record with scale_10M. A run killed during the
        # 10M stage still leaves the 1M record as its last line.
        assert len(recs) == 2
        early, merged = recs
        assert early["value"] is not None and early["value"] > 0
        assert "scale_10M" not in early
        assert merged["value"] == early["value"]
        assert merged["scale_10M"]["value_s"] > 0
        assert merged["vs_baseline"] == pytest.approx(1.0 / merged["value"],
                                                      rel=1e-3)

    def test_graphs_cached_on_first_run(self, first_run):
        cache, _, recs = first_run
        names = os.listdir(cache)
        assert any(n.startswith("ws_n2000") for n in names)
        assert any(n.startswith("ws_n3000") for n in names)
        assert recs[-1]["graph_cached"] is False
        assert recs[-1]["scale_10M"]["graph_cached"] is False

    def test_second_run_loads_from_cache(self, first_run):
        cache, _, _ = first_run
        r, recs = _run(cache)
        assert r.returncode == 0, r.stderr[-2000:]
        merged = recs[-1]
        assert merged["graph_cached"] is True
        assert merged["scale_10M"]["graph_cached"] is True
        assert merged["value"] > 0

    def test_cache_corruption_falls_back_to_build(self, tmp_path):
        sys.path.insert(0, REPO)
        import bench

        fp = bench._layout_fingerprint()
        (tmp_path / f"ws_n2000_k10_p0.1_s0_{fp}.npz").write_bytes(b"not npz")
        r, recs = _run(tmp_path)
        assert r.returncode == 0, r.stderr[-2000:]
        assert recs[-1]["value"] > 0
        assert recs[-1]["graph_cached"] is False
        # The fallback is reported, not swallowed: a structured WARN event
        # in the telemetry JSONL schema names the corrupt file...
        warns = [json.loads(ln.split("# WARN ", 1)[1])
                 for ln in r.stderr.splitlines() if ln.startswith("# WARN ")]
        corrupt = [w for w in warns if w["name"] == "bench_cache_miss"
                   and w["data"]["reason"] == "corrupt"]
        assert corrupt and corrupt[0]["type"] == "event"
        assert "ws_n2000" in corrupt[0]["data"]["path"]
        # ...and the bench_cache_miss_total counter lands in the stage's
        # telemetry artifact.
        tel = json.loads((tmp_path / "BENCH_TELEMETRY.json").read_text())
        samples = tel["metrics"]["bench_cache_miss_total"]["samples"]
        by_reason = {s["labels"]["reason"]: s["value"] for s in samples}
        assert by_reason["corrupt"] == 1

    def test_stale_layout_cache_not_loaded(self, first_run):
        # The cache key folds in a fingerprint of the graph/layout sources:
        # a file under a different fingerprint (layout code since edited)
        # must be ignored, not measured.
        cache, _, _ = first_run
        import shutil

        sys.path.insert(0, REPO)
        import bench

        fp = bench._layout_fingerprint()
        real = next(p for p in os.listdir(cache)
                    if p.startswith("ws_n2000") and fp in p)
        stale_dir = str(cache) + "_stale"
        os.makedirs(stale_dir, exist_ok=True)
        shutil.copy(os.path.join(cache, real),
                    os.path.join(stale_dir, real.replace(fp, "0" * len(fp))))
        r, recs = _run(stale_dir)
        assert r.returncode == 0, r.stderr[-2000:]
        assert recs[-1]["graph_cached"] is False


class TestStageTelemetry:
    @pytest.mark.slow  # its own full bench run (~1 min); the cheap
    # artifact checks ride first_run in the tests below
    def test_stage_artifacts_written_with_nonzero_timings(self, tmp_path):
        # Each measuring stage leaves a per-stage telemetry artifact beside
        # the headline: BENCH_TELEMETRY.json (1M) / _10M.json (scale row),
        # with non-zero graph-build and compile attributions and the full
        # registry snapshot. Own run, own dirs: other tests re-run bench
        # against the shared first_run cache and overwrite its artifacts.
        r, recs = _run(tmp_path)
        assert r.returncode == 0, r.stderr[-2000:]
        for fname, stage in (("BENCH_TELEMETRY.json", "1m"),
                             ("BENCH_TELEMETRY_10M.json", "10m")):
            tel = json.loads((tmp_path / fname).read_text())
            assert tel["schema"] == "bench-telemetry-v1"
            assert tel["stage"] == stage
            st = tel["stages"]
            assert st["graph_build_s"] > 0
            assert st["compile_s"] > 0
            assert st["run_s"] > 0
            assert st["transfer_s"] > 0
            assert st["transfer_bytes"] > 0
            assert st["cache_hit"] is False
            assert "sim_runs_total" in tel["metrics"]
        tel_1m = json.loads((tmp_path / "BENCH_TELEMETRY.json").read_text())
        # headline and artifact must agree on the graph-build attribution
        assert tel_1m["stages"]["graph_build_s"] == pytest.approx(
            recs[-1]["graph_build_s"], abs=0.01)
        # A cold run built the graph, so the per-phase build attribution
        # (sim/graph.py) rides along: dedup + sort at minimum for the WS
        # family, CSR because the spec builds source_csr=True.
        phases = tel_1m["build_phases"]
        assert phases["sort_s"] >= 0 and phases["dedup_s"] >= 0
        assert "source_csr_s" in phases
        assert set(tel_1m["per_method"]) == {
            "pallas", "hybrid", "adaptive-1024", "adaptive-2048", "frontier"}
        # The frontier column carries the per-round occupancy attribution
        # the crossover constant is re-fit from.
        occ = tel_1m["per_method"]["frontier"]["frontier_occupancy_per_round"]
        assert len(occ) == recs[-1]["rounds"]
        assert all(0.0 <= v <= 1.0 for v in occ)

    def test_artifacts_exist_with_nonzero_core_timings(self, first_run):
        # Cheap coverage that rides first_run (later tests may re-run bench
        # over the same dir and overwrite cache_hit, so only the fields
        # invariant across runs are asserted here; the full check is the
        # slow-marked test above).
        cache, _, _ = first_run
        for fname in ("BENCH_TELEMETRY.json", "BENCH_TELEMETRY_10M.json"):
            tel = json.loads((cache / fname).read_text())
            assert tel["schema"] == "bench-telemetry-v1"
            assert tel["stages"]["graph_build_s"] > 0
            assert tel["stages"]["compile_s"] > 0
            assert tel["stages"]["transfer_bytes"] > 0
            # the per-phase build breakdown is always present (empty only
            # on cache-hit runs, which built nothing)
            assert isinstance(tel["build_phases"], dict)
            # The graftaudit static cost model rides beside the measured
            # numbers: the stage's shape-class slice of budgets.json.
            model = tel["ir_cost_model"]
            assert model["shape_class"] == "ws1k"
            assert model["entries"]["or/frontier@ws1k"]["flops"] > 0
            assert "cov/flood-ppermute@ws1k" in model["entries"]

    def test_memory_slice_published_with_device_stats(self, first_run):
        # The graftmem slice (schema-pinned): the static capacity plan
        # from the checked-in membudgets coefficients beside the live
        # `device_memory_stats` snapshot. On the CPU backend the
        # allocator stats are honestly unavailable (per-device stats:
        # None, available: False) — never missing, never a crash.
        cache, _, _ = first_run
        for fname, nodes in (("BENCH_TELEMETRY.json", 1_000_000),
                             ("BENCH_TELEMETRY_10M.json", 10_000_000)):
            tel = json.loads((cache / fname).read_text())
            mem = tel["memory"]
            dms = mem["device_memory_stats"]
            assert isinstance(dms["available"], bool)
            assert dms["devices"], "no per-device rows"
            for row in dms["devices"]:
                assert set(row) == {"id", "platform", "stats"}
                if not dms["available"]:
                    assert row["stats"] is None
            plan = mem["plan"]
            assert "error" not in plan, plan
            assert plan["n_nodes"] == nodes
            assert plan["n_pad"] % 128 == 0
            assert plan["lane_words"] == 313
            assert plan["global_bytes"] > 0

    def test_batched_column_published_with_p99(self, first_run):
        # The batched message-plane column (ROADMAP 2a) lands in the 1M
        # stage artifact: B in-flight floods per compiled program, the
        # completion-rounds p99, and the aggregate-throughput ratio vs
        # sequential single-message runs.
        cache, _, _ = first_run
        tel = json.loads((cache / "BENCH_TELEMETRY.json").read_text())
        col = tel["batched"]
        assert "error" not in col, col
        assert col["B"] == 40
        assert col["completed"] + col["active_lanes_end"] >= 1
        assert col["batch_completion_rounds_p99"] is not None
        assert col["batch_completion_rounds_p99"] >= 1
        assert col["aggregate_speedup_vs_sequential"] > 0
        assert col["best_s"] > 0 and col["messages"] > 0
        assert col["seq_sample_runs"] >= 1

    def test_serving_column_published_with_percentiles(self, first_run):
        # The serving column (ROADMAP 2): seeded open-loop traffic
        # through the admission-controlled service — sustained lanes/s,
        # submit→completion p50/p99 rounds, peak concurrency, shed rate.
        cache, _, _ = first_run
        tel = json.loads((cache / "BENCH_TELEMETRY.json").read_text())
        col = tel["serving"]
        assert "error" not in col, col
        assert col["capacity"] == 64  # 40 requested, rounded to words
        assert col["completed"] >= 1
        assert col["submit_to_completion_rounds_p50"] >= 1
        assert col["submit_to_completion_rounds_p99"] >= \
            col["submit_to_completion_rounds_p50"]
        assert col["sustained_lanes_per_s"] > 0
        assert col["peak_concurrent_lanes"] >= 1
        assert 0.0 <= col["shed_rate"] <= 1.0
        assert col["offered"] == col["submitted"] + col["shed"]

    def test_serving_column_disabled_is_empty_not_missing(self, tmp_path):
        # BENCH_SERVE=0 must publish
        # an EMPTY column, keeping the artifact schema stable.
        r = subprocess.run(
            [sys.executable, BENCH, "--stage", "1m"],
            env=_env(tmp_path, BENCH_SERVE="0"), capture_output=True,
            text=True, timeout=600, cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        tel = json.loads((tmp_path / "BENCH_TELEMETRY.json").read_text())
        assert tel["serving"] == {}

    def test_queries_column_published_per_family(self, first_run):
        # The queries column (ROADMAP 3): the three non-boolean batched
        # query families each publish aggregate speedup vs warm
        # sequential capacity-1 runs, lanes/s, and completion
        # percentiles.
        cache, _, _ = first_run
        # the fixture's snapshot: the live artifact may since have been
        # overwritten by a re-run with the suite's queries-off default
        tel = json.loads(
            (cache / "BENCH_TELEMETRY_first.json").read_text())
        col = tel["queries"]
        assert "error" not in col, col
        for fam, k in (("minplus", 8), ("pushsum", 4), ("dht", 16)):
            f = col[fam]
            assert "error" not in f, (fam, f)
            assert f["K"] == k
            assert f["completed"] + f["active_lanes_end"] >= 1
            assert f["best_s"] > 0
            assert f["lanes_per_s"] > 0
            assert f["completion_rounds_p99"] is not None
            assert f["completion_rounds_p99"] >= \
                f["completion_rounds_p50"] >= 0
            assert f["aggregate_speedup_vs_sequential"] > 0
            assert f["seq_sample_runs"] >= 1
        # the DHT family rides its own chord overlay
        assert col["dht"]["n_nodes"] == 512
        assert col["minplus"]["n_nodes"] == col["pushsum"]["n_nodes"]

    def test_queries_column_disabled_is_empty_not_missing(self, tmp_path):
        # BENCH_QUERIES=0 must
        # publish an EMPTY column, keeping the artifact schema stable.
        # The sibling columns are disabled and the method contest
        # trimmed to one entry: this subprocess only proves the queries
        # key's disabled shape.
        r = subprocess.run(
            [sys.executable, BENCH, "--stage", "1m"],
            env=_env(tmp_path, BENCH_QUERIES="0", BENCH_BATCH="0",
                     BENCH_SERVE="0", BENCH_MULTICHIP="0",
                     BENCH_METHODS="segment"),
            capture_output=True, text=True, timeout=600, cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        tel = json.loads((tmp_path / "BENCH_TELEMETRY.json").read_text())
        assert tel["queries"] == {}

    def test_multichip_column_published_with_ici_bytes(self, first_run):
        # The multichip ring column (the promoted dryrun_multichip): the
        # ring-sharded flood's wall, the single-chip scaling ratio, and
        # the per-round ICI byte estimates of BOTH halo backends — a
        # Pallas-comm program must never read as zero ICI bytes.
        cache, _, _ = first_run
        tel = json.loads((cache / "BENCH_TELEMETRY.json").read_text())
        col = tel["multichip"]
        assert "error" not in col and "skipped" not in col, col
        assert col["n_devices"] >= 2
        assert col["best_s"] > 0 and col["single_chip_best_s"] > 0
        assert col["scaling_ratio"] > 0
        assert col["rounds"] >= 1 and col["coverage"] > 0
        per_round = col["per_round_ici_bytes"]
        assert per_round["ppermute"] > 0
        assert per_round["pallas"] > 0
        # the acceptance bound: pallas within 20% of ppermute
        assert 0.8 <= per_round["pallas"] / per_round["ppermute"] <= 1.2
        assert col["ici_census"]["pallas"]["ring_dma"]["count"] >= 1
        assert col["ici_bytes_total_est"] == \
            per_round[col["comm"]] * col["rounds"]

    def test_multichip_column_disabled_is_empty_not_missing(self, tmp_path):
        r = subprocess.run(
            [sys.executable, BENCH, "--stage", "1m"],
            env=_env(tmp_path, BENCH_MULTICHIP="0"), capture_output=True,
            text=True, timeout=600, cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        tel = json.loads((tmp_path / "BENCH_TELEMETRY.json").read_text())
        assert tel["multichip"] == {}

    def test_batched_column_disabled_is_empty_not_missing(self, tmp_path):
        # BENCH_BATCH=0 must publish
        # an EMPTY column, keeping the artifact schema stable.
        r = subprocess.run(
            [sys.executable, BENCH, "--stage", "1m"],
            env=_env(tmp_path, BENCH_BATCH="0"), capture_output=True,
            text=True, timeout=600, cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        tel = json.loads((tmp_path / "BENCH_TELEMETRY.json").read_text())
        assert tel["batched"] == {}

    def test_headline_format_unchanged_by_telemetry(self, first_run):
        # The driver parses the LAST stdout line; the artifact must not
        # perturb its key set.
        _, _, recs = first_run
        assert {"metric", "value", "unit", "vs_baseline", "method",
                "rounds", "coverage", "messages", "graph_build_s",
                "graph_cached", "n_nodes", "n_edges",
                "scale_10M"} <= set(recs[-1])

    def test_missing_cache_reported_as_structured_miss(self, first_run):
        cache, r, _ = first_run
        warns = [json.loads(ln.split("# WARN ", 1)[1])
                 for ln in r.stderr.splitlines() if ln.startswith("# WARN ")]
        missing = [w for w in warns if w["name"] == "bench_cache_miss"
                   and w["data"]["reason"] == "missing"]
        assert missing, "first run must report its cold cache misses"


class TestNoTpu:
    """Nothing carries on without a TPU: a measuring stage refuses any
    other platform unless JAX_PLATFORMS=cpu was set explicitly, and the
    parent that launches the stages never touches JAX itself."""

    def test_require_tpu_refuses_cpu_without_explicit_env(self,
                                                          monkeypatch):
        import bench

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(RuntimeError, match="no TPU"):
            bench._require_tpu()

    def test_require_tpu_accepts_explicit_cpu(self, monkeypatch):
        import bench

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        bench._require_tpu()

    def test_require_tpu_refuses_platform_lists(self, monkeypatch):
        # Only the exact opt-in counts: a list that merely includes cpu
        # is a run that wanted a chip and fell through to the CPU.
        import bench

        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        with pytest.raises(RuntimeError, match="platform 'cpu'"):
            bench._require_tpu()

    def test_parent_never_imports_jax(self):
        # One process per chip: the parent launches the stage children
        # and must not hold the chip itself.
        code = ("import sys; sys.path.insert(0, {!r}); import bench; "
                "raise SystemExit('jax' in sys.modules)").format(REPO)
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]


class TestHangContainment:
    def test_stage_timeout_is_bounded_error_not_hang(self, tmp_path):
        # A 1s stage budget cannot fit backend init: the child must be
        # killed and the run must still emit a parseable record whose
        # error names the stage.
        r, recs = _run(tmp_path, BENCH_STAGE_TIMEOUT_S=1)
        assert r.returncode == 1
        assert recs, "no JSON emitted on stage timeout"
        last = recs[-1]
        assert last["value"] is None
        assert "stage 1m" in last["error"]

    def test_stage_exception_carried_into_record(self, tmp_path):
        # A stage child dying on an exception must surface the actual
        # cause in the parsed record, not a bare "exited rc=1".
        r, recs = _run(tmp_path, BENCH_N_1M="not-a-number")
        assert r.returncode == 1
        last = recs[-1]
        assert last["value"] is None
        assert "stage 1m" in last["error"]
        assert "ValueError" in last["error"]

    def test_dead_backend_is_nonzero_error_record(self, tmp_path):
        # An unsatisfiable platform: the run exits non-zero with an error
        # record that carries no value, and no stage measured on a
        # stand-in backend.
        r, recs = _run(tmp_path, JAX_PLATFORMS="nonexistent-platform")
        assert r.returncode == 1
        last = recs[-1]
        assert last["value"] is None
        assert "stage 1m" in last["error"]
        assert "platform" not in last and "backend" not in last
        assert "scale_10M" not in last  # the 10M stage never started

    def test_dead_backend_stage_alone_fails(self, tmp_path):
        r = subprocess.run(
            [sys.executable, BENCH, "--stage", "10m"],
            env=_env(tmp_path, JAX_PLATFORMS="nonexistent-platform"),
            capture_output=True, text=True, timeout=600, cwd=REPO)
        assert r.returncode == 1
        last = json.loads(
            [ln for ln in r.stdout.splitlines() if ln.strip()][-1])
        assert set(last) == {"error"}
        assert not (tmp_path / "BENCH_TELEMETRY_10M.json").exists()


class TestFailuresFail:
    """A failing stage, method or column is a failed run: non-zero exit
    and an error record — never a partial or stand-in record."""

    def _main(self, monkeypatch, capsys, results):
        import bench

        calls = []

        def stage(name, timeout_s):
            calls.append(name)
            return results[name]

        monkeypatch.setattr(bench, "_stage_in_child", stage)
        rc = bench.main()
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.splitlines() if ln.strip()]
        return rc, lines, calls

    def test_main_stops_at_failed_1m_stage(self, monkeypatch, capsys):
        rc, lines, calls = self._main(monkeypatch, capsys, {
            "1m": {"error": "stage 1m: boom"}})
        assert rc == 1 and calls == ["1m"]
        assert lines[-1]["value"] is None
        assert lines[-1]["error"] == "stage 1m: boom"

    def test_main_fails_on_failed_10m_stage(self, monkeypatch, capsys):
        rc, lines, calls = self._main(monkeypatch, capsys, {
            "1m": {"value": 0.5}, "10m": {"error": "stage 10m: boom"}})
        assert rc == 1 and calls == ["1m", "10m"]
        assert lines[-1]["value"] == 0.5
        assert lines[-1]["scale_10M"] == {"error": "stage 10m: boom"}

    def test_failing_method_fails_the_stage(self, tmp_path):
        r = subprocess.run(
            [sys.executable, BENCH, "--stage", "1m"],
            env=_env(tmp_path, BENCH_METHODS="adaptive-notanumber",
                     BENCH_BATCH="0", BENCH_SERVE="0",
                     BENCH_MULTICHIP="0"),
            capture_output=True, text=True, timeout=600, cwd=REPO)
        assert r.returncode == 1
        last = json.loads(
            [ln for ln in r.stdout.splitlines() if ln.strip()][-1])
        assert set(last) == {"error"}
        assert not (tmp_path / "BENCH_TELEMETRY.json").exists()

    def test_failing_column_fails_the_stage(self, tmp_path):
        r = subprocess.run(
            [sys.executable, BENCH, "--stage", "1m"],
            env=_env(tmp_path, BENCH_METHODS="segment",
                     BENCH_BATCH_B="not-a-number", BENCH_SERVE="0",
                     BENCH_MULTICHIP="0"),
            capture_output=True, text=True, timeout=600, cwd=REPO)
        assert r.returncode == 1
        last = json.loads(
            [ln for ln in r.stdout.splitlines() if ln.strip()][-1])
        assert "ValueError" in last["error"]

    def test_multichip_skipped_below_two_devices(self, monkeypatch):
        # The ring column runs on this process's own devices; with one
        # it is skipped — no child is started on another platform.
        import bench
        import jax

        one = jax.devices()[:1]
        monkeypatch.setattr(jax, "devices", lambda *a: one)
        monkeypatch.setattr(bench.subprocess, "run", None)
        col = bench.bench_multichip()
        assert set(col) == {"skipped"} and "1 device" in col["skipped"]

    def test_artifact_names_its_device(self, tmp_path, monkeypatch):
        import bench
        import jax

        monkeypatch.setenv("BENCH_TELEMETRY_DIR", str(tmp_path))
        bench._write_stage_telemetry("1m", {}, 0.0)
        doc = json.loads((tmp_path / "BENCH_TELEMETRY.json").read_text())
        assert doc["device"] == {"platform": jax.devices()[0].platform,
                                 "kind": jax.devices()[0].device_kind,
                                 "count": len(jax.devices())}

    def test_artifact_has_no_probe_or_supervised_slices(self, tmp_path,
                                                        monkeypatch):
        import bench

        monkeypatch.setenv("BENCH_TELEMETRY_DIR", str(tmp_path))
        bench._write_stage_telemetry("1m", {}, 0.0)
        doc = json.loads((tmp_path / "BENCH_TELEMETRY.json").read_text())
        assert "probe_log" not in doc and "supervised" not in doc


class TestPrebuild:
    def test_prebuild_populates_cache_for_measuring_runs(self, tmp_path):
        # --stage prebuild builds + caches both graphs without measuring;
        # a later measuring run must find them (graph_cached: true).
        r = subprocess.run([sys.executable, BENCH, "--stage", "prebuild"],
                           env=_env(tmp_path), capture_output=True,
                           text=True, timeout=600, cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        last = json.loads(
            [ln for ln in r.stdout.splitlines() if ln.strip()][-1])
        assert last == {"prebuilt": True}
        names = os.listdir(tmp_path)
        assert any(n.startswith("ws_n2000") for n in names)
        assert any(n.startswith("ws_n3000") for n in names)
        r2, recs = _run(tmp_path)
        assert r2.returncode == 0, r2.stderr[-2000:]
        assert recs[-1]["graph_cached"] is True
        assert recs[-1]["scale_10M"]["graph_cached"] is True
