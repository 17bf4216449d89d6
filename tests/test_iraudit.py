"""graftaudit (p2pnetwork_tpu/analysis/ir/) tests.

Three layers, mirroring test_analysis.py's contract for graftlint:

- **rule fixtures** — for every jaxpr rule, a deliberately-broken
  lowering (an intentionally-f64 variant, a host callback, a busted slot
  budget, a donation-dropped engine step) asserting the rule fires at
  the exact LOWERING NAME, with a clean real-registry twin;
- **machinery** — budgets round-trip, ratchet arithmetic (inflated cost
  fails, HEAD passes), collective-census drift, parity-gate mismatch;
- **the live tree** — the full registry must trace clean, the donation
  audit must verify every engine carry seam, and the checked-in
  budgets.json must match HEAD: the CI gate this suite keeps honest.
"""

import copy
import json

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from p2pnetwork_tpu.analysis.ir import budgets as B  # noqa: E402
from p2pnetwork_tpu.analysis.ir import donation, registry, rules  # noqa: E402
from p2pnetwork_tpu.analysis.ir.registry import Lowering  # noqa: E402

pytestmark = pytest.mark.audit


@pytest.fixture(scope="module")
def traces():
    """One trace of the full registry, shared across the module (the
    costly part is the sharded entry's mesh build)."""
    return [registry.trace_lowering(e) for e in registry.all_lowerings()]


def _entry(name, build, **kw):
    op, rest = name.split("/", 1)
    variant, cls = rest.split("@", 1)
    return Lowering(name=name, op=op, variant=variant, shape_class=cls,
                    build=build, **kw)


def _sig(n=128, dtype=jnp.float32):
    return jnp.zeros(n, dtype=dtype)


def test_package_import_stays_jax_free():
    # The device-free guarantee: `python -m p2pnetwork_tpu.analysis.ir`
    # (and the console script) execute the package __init__ BEFORE
    # main() can pin JAX_PLATFORMS, and jax captures that env var at
    # import time — so importing the package must not import jax.
    import subprocess
    import sys

    code = ("import sys; import p2pnetwork_tpu.analysis.ir; "
            "sys.exit(2 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0


# ------------------------------------------------------------- registry


class TestRegistry:
    def test_full_registry_traces_clean(self, traces):
        assert len(traces) >= 20
        names = [t.entry.name for t in traces]
        assert len(set(names)) == len(names)
        assert [t.entry.name for t in traces if t.error] == []
        for t in traces:
            assert t.out_sig, t.entry.name
            assert t.prims, t.entry.name

    def test_registry_covers_the_lowering_zoo(self, traces):
        variants = {(t.entry.op, t.entry.variant) for t in traces}
        # Every module the audit exists to police appears.
        assert ("or", "segment") in variants
        assert ("or", "blocked") in variants
        assert ("or", "skew") in variants
        assert ("or", "frontier") in variants
        assert ("floodstep", "bitset") in variants
        assert ("cov", "flood-ppermute") in variants

    def test_sharded_collective_census(self, traces):
        t = next(t for t in traces
                 if t.entry.name == "cov/flood-ppermute@ws1k")
        assert t.collectives.get("ppermute", 0) >= 1
        assert t.collectives.get("psum", 0) >= 1
        assert t.ici_bytes_est > 0

    def test_single_chip_lowerings_have_no_collectives(self, traces):
        for t in traces:
            if t.entry.needs_devices == 1:
                assert not t.collectives, t.entry.name


# ----------------------------------------------------------- jaxpr rules


class TestJaxprRules:
    def test_real_registry_has_zero_rule_findings(self, traces):
        assert rules.run_ir_rules(traces) == []

    def test_f64_widen_fires_at_the_lowering_name(self):
        def build():
            def bad(x):
                with jax.enable_x64(True):
                    y = x.astype(jnp.float64) * 2.0
                return y.astype(jnp.float32)
            return bad, (_sig(),)

        t = registry.trace_lowering(_entry("or/f64bad@ws1k", build,
                                           parity=False))
        found = [f for f in rules.run_ir_rules([t])
                 if f.rule == "ir-f64-widen"]
        assert found and all(f.file == "or/f64bad@ws1k" for f in found)
        assert any("convert_element_type" in f.message for f in found)

    def test_f64_clean_twin(self):
        def build():
            return (lambda x: x * 2.0), (_sig(),)

        t = registry.trace_lowering(_entry("or/f32ok@ws1k", build,
                                           parity=False))
        assert [f for f in rules.run_ir_rules([t])
                if f.rule == "ir-f64-widen"] == []

    def test_host_callback_fires(self):
        def build():
            def bad(x):
                return jax.pure_callback(
                    lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return bad, (_sig(),)

        t = registry.trace_lowering(_entry("or/cb@ws1k", build,
                                           parity=False))
        found = rules.run_ir_rules([t])
        assert [f.rule for f in found] == ["ir-host-callback"]
        assert found[0].severity == "P0"
        assert found[0].file == "or/cb@ws1k"

    def test_trace_error_is_a_finding_not_a_crash(self):
        def build():
            raise RuntimeError("entry rotted")

        t = registry.trace_lowering(_entry("or/dead@ws1k", build))
        found = rules.run_ir_rules([t])
        assert [f.rule for f in found] == ["ir-trace-error"]
        assert "entry rotted" in found[0].message

    def test_gather_slot_budget_fires_when_every_branch_blows_it(self):
        # A cond BOTH of whose branches gather the full table — the
        # compaction invariant (some branch within k·span) is broken.
        def build():
            idx = jnp.arange(4096) % 128

            def fat(x):
                return jax.lax.cond(x.sum() > 0,
                                    lambda s: s[idx], lambda s: s[idx] * 2,
                                    x)
            return fat, (_sig(),)

        t = registry.trace_lowering(_entry("or/fatgather@ws1k", build,
                                           parity=False, slot_budget=64))
        found = [f for f in rules.run_ir_rules([t])
                 if f.rule == "ir-gather-slot-budget"]
        assert found and found[0].file == "or/fatgather@ws1k"
        assert "every branch" in found[0].message

    def test_gather_slot_budget_fires_when_the_cond_is_compiled_out(self):
        def build():
            return (lambda x: x * 2), (_sig(),)

        t = registry.trace_lowering(_entry("or/nocond@ws1k", build,
                                           parity=False, slot_budget=64))
        found = [f for f in rules.run_ir_rules([t])
                 if f.rule == "ir-gather-slot-budget"]
        assert found and "compiled out" in found[0].message

    def test_real_frontier_entries_satisfy_their_budget(self, traces):
        budgeted = [t for t in traces if t.entry.slot_budget is not None]
        assert budgeted, "no frontier entries carry a slot budget"
        assert [f for t in budgeted for f in rules.run_ir_rules([t])
                if f.rule == "ir-gather-slot-budget"] == []


# ------------------------------------------------------------ parity gate


class TestParityGate:
    def test_real_registry_is_parity_clean(self, traces):
        assert rules.parity_findings(traces) == []

    def test_signature_mismatch_is_caught(self, traces):
        g = registry.shape_class("ws1k")

        def build():
            # Same op group as the real `or@ws1k` lowerings, wrong dtype.
            return (lambda x: x.astype(jnp.int32)), (
                jnp.zeros(g.n_nodes_padded, dtype=bool),)

        bad = registry.trace_lowering(_entry("or/badsig@ws1k", build))
        found = rules.parity_findings(list(traces) + [bad])
        assert [f.file for f in found] == ["or/badsig@ws1k"]
        assert found[0].rule == "ir-sig-parity"
        assert found[0].severity == "P0"


# --------------------------------------------------------------- donation


class TestDonationAudit:
    def test_engine_carry_donation_verifies_at_head(self):
        assert donation.audit_donation() == []

    def test_dropped_donate_argnums_is_caught(self):
        # The engine's own donate=False escape-hatch twin IS the
        # dropped-donation artifact: same program, no donate_argnames.
        from p2pnetwork_tpu.models.flood import Flood
        from p2pnetwork_tpu.sim import engine

        g = registry.shape_class("ws1k")
        state = donation._flood_resume_state(g)
        dropped = donation.DonationAudit(
            name="engine/run_from-keeping",
            build=lambda: (engine._run_from_keeping,
                           (g, Flood(source=0), state, jax.random.key(0),
                            4), {}, 2))
        found = donation.audit_donation([dropped])
        assert [f.rule for f in found] == ["ir-donation-dropped"]
        assert found[0].severity == "P0"
        assert found[0].file == "engine/run_from-keeping"

    def test_unbuildable_audit_is_a_finding(self):
        def build():
            raise OSError("no such seam")

        found = donation.audit_donation(
            [donation.DonationAudit(name="x/y", build=build)])
        assert [f.rule for f in found] == ["ir-donation-unverifiable"]

    def test_alias_section_parses_nested_braces(self):
        hlo = ("ENTRY %main, input_output_alias={ {0}: (4, {}, may-alias),"
               " {1}: (5, {}, may-alias) }, entry_computation_layout=x")
        assert len(donation._ALIAS_PAIR.findall(
            donation._alias_section(hlo))) == 2


# ------------------------------------------------------------ cost ratchet


class TestCostRatchet:
    @pytest.fixture(scope="class")
    def head_costs(self, traces):
        return B.collect_costs(traces)

    def test_budgets_round_trip(self, head_costs, tmp_path):
        path = str(tmp_path / "budgets.json")
        B.write_budgets(head_costs, path)
        doc = B.load_budgets(path)
        assert doc["schema"] == B.SCHEMA
        assert set(doc["entries"]) == set(head_costs)
        assert B.check_budgets(head_costs, doc) == []

    def test_checked_in_budgets_match_head(self, head_costs):
        # THE ratchet gate: unexplained cost drift vs the committed file
        # fails CI. A legitimate change is blessed via
        # `graftaudit --write-budgets` (commit the budgets.json diff).
        doc = B.load_budgets()
        assert doc, "analysis/ir/budgets.json is missing"
        assert B.check_budgets(head_costs, doc) == []

    def test_inflated_cost_fails_the_ratchet(self, head_costs):
        doc = copy.deepcopy(B.load_budgets())
        name = "or/segment@ws1k"
        doc["entries"][name]["flops"] /= 1.5  # current looks 1.5x budget
        found = [f for f in B.check_budgets(head_costs, doc)
                 if f.file == name]
        assert found and found[0].rule == "ir-cost-ratchet"
        assert "grew 1.50x" in found[0].message

    def test_shrunk_cost_asks_for_a_re_bless(self, head_costs):
        doc = copy.deepcopy(B.load_budgets())
        name = "or/segment@ws1k"
        doc["entries"][name]["bytes"] *= 2.0  # current is half the budget
        found = [f for f in B.check_budgets(head_costs, doc)
                 if f.file == name]
        assert found and found[0].severity == "P2"
        assert "shrank" in found[0].message

    def test_collective_drift_fails(self, head_costs):
        doc = copy.deepcopy(B.load_budgets())
        name = "cov/flood-ppermute@ws1k"
        doc["entries"][name]["collectives"]["psum"] += 1
        found = [f for f in B.check_budgets(head_costs, doc)
                 if f.file == name]
        assert found and "collective census changed" in found[0].message

    def test_missing_and_stale_entries_are_findings(self, head_costs):
        doc = copy.deepcopy(B.load_budgets())
        doc["entries"]["or/ghost@ws1k"] = {"flops": 1.0, "bytes": 1.0}
        del doc["entries"]["or/segment@ws1k"]
        messages = {f.file: f.message
                    for f in B.check_budgets(head_costs, doc)}
        assert "no blessed budget" in messages["or/segment@ws1k"]
        assert "no longer produces" in messages["or/ghost@ws1k"]

    def test_skipped_lowerings_are_not_stale(self, head_costs):
        # A degraded host (jax imported before graftaudit could pin the
        # virtual mesh) skips the sharded entries; their budgets must NOT
        # read as stale — that advice would regenerate a budgets.json
        # missing them and fail the next full CI run.
        name = "cov/flood-ppermute@ws1k"
        costs = {k: v for k, v in head_costs.items() if k != name}
        doc = B.load_budgets()
        with_skip = B.check_budgets(costs, doc, skipped=[name])
        assert [f for f in with_skip if f.file == name] == []
        without = B.check_budgets(costs, doc)
        assert any(f.file == name and "no longer produces" in f.message
                   for f in without)

    def test_blessed_error_record_is_a_finding_not_an_ungate(self,
                                                             head_costs):
        # A budgets.json entry that is itself an error record (hand-edit,
        # or a bless from before the CLI refused them) has no metrics to
        # compare — it must fail the gate, not skip it forever.
        doc = copy.deepcopy(B.load_budgets())
        name = "or/segment@ws1k"
        doc["entries"][name] = {"error": "RuntimeError: transient OOM"}
        found = [f for f in B.check_budgets(head_costs, doc)
                 if f.file == name]
        assert found and "compile-error record" in found[0].message

    def test_compile_failure_is_gated_not_silent(self):
        # Traces fine, then the cost pass's rebuild blows up — standing in
        # for a lowering the CPU backend cannot compile. The contract
        # under test: the failure becomes a ratchet finding, never a
        # silently ungated entry.
        calls = {"n": 0}

        def build():
            calls["n"] += 1
            if calls["n"] > 1:
                raise RuntimeError("backend cannot lower this entry")
            return (lambda x: x * 2), (_sig(),)

        t = registry.trace_lowering(_entry("or/nocompile@ws1k", build,
                                           parity=False))
        costs = B.collect_costs([t])
        found = B.check_budgets(costs, {"entries": {}})
        assert any("failed to AOT-compile" in f.message for f in found)


# -------------------------------------------------------------------- CLI


class TestCLI:
    def test_head_is_clean_with_json_document(self, capsys):
        from p2pnetwork_tpu.analysis.ir.__main__ import main

        assert main(["--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["ok"] is True
        assert doc["findings"] == []
        assert len(doc["lowerings"]) >= 20
        assert doc["skipped"] == []
        assert "cov/flood-ppermute@ws1k" in doc["census"]
        assert doc["costs"]["or/segment@ws1k"]["flops"] > 0

    def test_no_cost_fast_pass(self, capsys):
        from p2pnetwork_tpu.analysis.ir.__main__ import main

        assert main(["--no-cost"]) == 0
        out = capsys.readouterr().out
        assert "clean" in out

    def test_listings_and_bad_invocations(self, capsys):
        from p2pnetwork_tpu.analysis.ir.__main__ import main

        assert main(["--list-rules"]) == 0
        assert "ir-cost-ratchet" in capsys.readouterr().out
        assert main(["--list-lowerings"]) == 0
        assert "or/frontier@ws1k" in capsys.readouterr().out
        assert main(["--rules", "no-such-rule"]) == 2
        assert main(["--no-cost", "--write-budgets"]) == 2

    def test_write_budgets_round_trips_through_the_cli(self, tmp_path,
                                                       capsys):
        from p2pnetwork_tpu.analysis.ir.__main__ import main

        path = str(tmp_path / "b.json")
        assert main(["--write-budgets", "--budgets", path]) == 0
        capsys.readouterr()
        assert main(["--budgets", path]) == 0

    def test_rebless_preserves_a_custom_tolerance(self, tmp_path, capsys):
        # check_budgets honors the STORED tolerance, so a routine
        # re-bless without --tolerance must keep it, not silently reset
        # to the default and tighten the ratchet.
        from p2pnetwork_tpu.analysis.ir.__main__ import main

        path = str(tmp_path / "b.json")
        assert main(["--write-budgets", "--budgets", path,
                     "--tolerance", "0.35"]) == 0
        assert B.load_budgets(path)["tolerance"] == 0.35
        capsys.readouterr()
        assert main(["--write-budgets", "--budgets", path]) == 0
        assert B.load_budgets(path)["tolerance"] == 0.35

    def test_bless_refuses_compile_error_records(self, tmp_path,
                                                 monkeypatch, capsys):
        # Blessing an error record would write a metric-less budget entry
        # and permanently un-gate that lowering — the CLI must refuse.
        from p2pnetwork_tpu.analysis.ir import __main__ as cli

        real = B.collect_costs

        def with_error(traces):
            costs = real(traces)
            costs["or/segment@ws1k"] = {"error": "RuntimeError: boom"}
            return costs

        monkeypatch.setattr(B, "collect_costs", with_error)
        assert cli.main(["--write-budgets",
                         "--budgets", str(tmp_path / "b.json")]) == 2
        err = capsys.readouterr().err
        assert "fail to compile" in err and "or/segment@ws1k" in err
        assert not (tmp_path / "b.json").exists()

    def test_degraded_run_skips_sharded_and_refuses_bless(self, tmp_path,
                                                          monkeypatch,
                                                          capsys):
        # With fewer devices than the sharded entries need, the gate must
        # still pass (skip list, budgets not stale) and --write-budgets
        # must refuse rather than bless a file missing those entries.
        from p2pnetwork_tpu.analysis.ir import __main__ as cli

        monkeypatch.setattr(jax, "devices", lambda *a: [object()])
        assert cli.main(["--no-cost"]) == 0
        out = capsys.readouterr()
        assert "skipped" in out.err and "flood-ppermute" in out.err
        assert cli.main(["--write-budgets",
                         "--budgets", str(tmp_path / "b.json")]) == 2
        assert "refusing --write-budgets on a degraded run" in \
            capsys.readouterr().err
        assert not (tmp_path / "b.json").exists()
