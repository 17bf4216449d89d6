"""graftaudit CLI: ``python -m p2pnetwork_tpu.analysis.ir`` / ``graftaudit``.

Exit codes mirror graftlint: 0 — no non-baselined findings; 1 — findings
to fix; 2 — bad invocation. The audit is device-free by construction:
this module pins ``JAX_PLATFORMS=cpu`` and the 8-way virtual host
platform BEFORE jax initializes, so the full registry — the sharded
ppermute path included — runs in CPU-only CI.

Typical invocations::

    graftaudit                       # the CI gate (rules + parity +
                                     #   donation + cost + memory ratchets)
    graftaudit --json                # machine-readable document
    graftaudit --no-cost             # skip AOT compiles (fast rule pass)
    graftaudit --write-budgets       # bless current costs into budgets.json
    graftaudit --write-membudgets    # bless memory records + refit the
                                     #   capacity model into membudgets.json
    graftaudit --plan                # the W=313 / 1M-node north-star
                                     #   capacity plan (no building)
    graftaudit --plan nodes=200000,lanes=4096,hbm_gb=8
    graftaudit --list-lowerings      # registry table
    graftaudit --list-rules          # rule table
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional


def _pin_cpu_platform() -> None:
    """Device-free guarantee: the audit must not grab a TPU and must see
    the 8-device virtual mesh. Only
    effective before jax's backend initializes — the conftest does the
    same dance for the test suite."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="graftaudit",
        description=("IR-level static audit of the lowering zoo: jaxpr "
                     "rules, signature parity, donation aliasing, and the "
                     "compiled-cost ratchet — all device-free (CPU-only "
                     "abstract tracing + AOT lowering)."))
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable output (one JSON document with "
                        "findings, census, and cost tables)")
    p.add_argument("--budgets", default=None, metavar="PATH",
                   help="budgets file (default: the package's checked-in "
                        "analysis/ir/budgets.json)")
    p.add_argument("--write-budgets", action="store_true",
                   help="bless the current compiled costs into the "
                        "budgets file and exit 0 (commit the diff)")
    p.add_argument("--baseline", default=None, metavar="PATH",
                   help="grandfathered-findings baseline (default: "
                        "analysis/ir/baseline.json; absent = empty)")
    p.add_argument("--write-baseline", action="store_true",
                   help="grandfather every current finding into the "
                        "baseline file and exit 0")
    p.add_argument("--no-cost", action="store_true",
                   help="skip AOT compilation (no cost ratchet, no "
                        "donation audit, no memory ratchet) — the fast "
                        "jaxpr-rule pass")
    p.add_argument("--membudgets", default=None, metavar="PATH",
                   help="memory-budgets file (default: the package's "
                        "checked-in analysis/ir/membudgets.json)")
    p.add_argument("--write-membudgets", action="store_true",
                   help="bless the current memory records (and refit the "
                        "capacity-model coefficients — two extra "
                        "full-registry AOT passes) into the membudgets "
                        "file and exit 0 (commit the diff)")
    p.add_argument("--no-mem", action="store_true",
                   help="skip the memory ratchet (membudgets gate) while "
                        "keeping the cost pass")
    p.add_argument("--plan", nargs="?", const="northstar", default=None,
                   metavar="SPEC",
                   help="print a capacity plan from the checked-in "
                        "coefficients and exit — no building, no "
                        "compiling. SPEC is k=v[,k=v...] over nodes, "
                        "lanes, hbm_gb, headroom, entry; bare --plan is "
                        "the north-star 1M-node / 10k-lane serving shape")
    p.add_argument("--tolerance", type=float, default=None,
                   help="cost-growth tolerance override (fraction; "
                        "default: the value stored in budgets.json)")
    p.add_argument("--rules", default=None, metavar="ID[,ID...]",
                   help="run only these jaxpr rule ids (parity/donation/"
                        "ratchet gates still run unless skipped)")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule table and exit")
    p.add_argument("--list-lowerings", action="store_true",
                   help="print the lowering registry and exit")
    return p


def _parse_plan_spec(spec: str) -> dict:
    """``nodes=200000,lanes=4096,hbm_gb=8`` -> capacity.plan kwargs.
    Bare ``--plan`` (or any omitted key) falls back to the north-star
    serving shape: 1M nodes, 10k lanes (W=313 words), 16 GiB/chip."""
    kw: dict = {"n_nodes": 1_000_000, "lanes": 10_016}
    if spec and spec != "northstar":
        for part in spec.split(","):
            k, sep, v = part.partition("=")
            k = k.strip()
            if not sep or not k:
                raise ValueError(f"bad --plan token {part!r} "
                                 "(want k=v[,k=v...])")
            if k == "nodes":
                kw["n_nodes"] = int(v)  # graftlint: ignore[host-sync-in-loop] -- CLI string parsing, no device values
            elif k == "lanes":
                kw["lanes"] = int(v)  # graftlint: ignore[host-sync-in-loop] -- CLI string parsing
            elif k == "hbm_gb":
                kw["per_chip_hbm_bytes"] = float(v) * 1024**3  # graftlint: ignore[host-sync-in-loop] -- CLI string parsing
            elif k == "headroom":
                kw["headroom"] = float(v)  # graftlint: ignore[host-sync-in-loop] -- CLI string parsing
            elif k == "entry":
                kw["entry"] = v.strip()
            else:
                raise ValueError(
                    f"unknown --plan key {k!r} (known: nodes, lanes, "
                    "hbm_gb, headroom, entry)")
    return kw


def _render_plan(doc: dict) -> None:
    gib = 1024**3
    print(f"capacity plan — {doc['entry']}")
    print(f"  overlay   {doc['n_nodes']:,} nodes (padded {doc['n_pad']:,} "
          f"nodes / {doc['e_pad']:,} edge slots)")
    print(f"  lanes     {doc['lanes']:,} ({doc['lane_words']} u32 words)")
    print(f"  global    {doc['global_bytes'] / gib:.2f} GiB modeled "
          "resident bytes")
    print(f"  chip HBM  {doc['per_chip_hbm_bytes'] / gib:.1f} GiB "
          f"(headroom {doc['headroom']:.0%})")
    for row in doc["per_chip"]:
        mark = "fits" if row["fits"] else "OVER"
        print(f"    shards={row['shards']:<5d} "
              f"{row['per_chip_bytes'] / gib:7.2f} GiB/chip  {mark}")
    rec = doc["recommended_shards"]
    print("  recommend "
          + (f"{rec} shard(s)" if rec else
             "NOTHING in the candidate list fits — raise shards or HBM"))


def _default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


def main(argv: Optional[List[str]] = None) -> int:
    args = _build_parser().parse_args(argv)
    _pin_cpu_platform()

    from p2pnetwork_tpu.analysis import core
    from p2pnetwork_tpu.analysis.ir import budgets as B
    from p2pnetwork_tpu.analysis.ir import capacity as C
    from p2pnetwork_tpu.analysis.ir import memory as M
    from p2pnetwork_tpu.analysis.ir import donation, registry, rules

    if args.plan is not None:
        try:
            doc = C.plan(**_parse_plan_spec(args.plan))
        except ValueError as e:
            print(f"graftaudit: {e}", file=sys.stderr)
            return 2
        if args.as_json:
            print(json.dumps(doc, indent=1))
        else:
            _render_plan(doc)
        return 0

    if args.list_rules:
        table = rules.all_ir_rules()
        width = max(len(r) for r in table)
        for rule in sorted(table.values(), key=lambda r: (r.severity, r.id)):
            print(f"{rule.id:<{width}}  {rule.severity}  {rule.doc}")
        print(f"{'ir-sig-parity':<{width}}  P0  cross-lowering "
              "eval_shape signature parity gate (rules.parity_findings)")
        print(f"{'ir-donation-dropped':<{width}}  P0  compiled "
              "input_output_alias must cover every donated carry leaf "
              "(donation.audit_donation)")
        print(f"{'ir-cost-ratchet':<{width}}  P1  compiled cost vs the "
              "blessed budgets.json (budgets.check_budgets)")
        print(f"{'ir-mem-regression':<{width}}  P1  compiled peak memory "
              "vs the blessed membudgets.json (memory.check_membudgets; "
              "shrink past tolerance is P2)")
        print(f"{'ir-mem-unbudgeted':<{width}}  P1  lowering with no "
              "blessed memory budget (memory.check_membudgets)")
        print(f"{'ir-mem-model-drift':<{width}}  P2  analytic liveness "
              "walk vs memory_analysis() disagree past the model "
              "tolerance (memory.check_membudgets)")
        return 0

    entries = registry.all_lowerings()
    import jax

    n_dev = len(jax.devices())
    runnable = [e for e in entries if e.needs_devices <= n_dev]
    skipped = [e for e in entries if e.needs_devices > n_dev]
    if skipped:
        # Only possible when a host imported jax before this CLI could
        # pin the virtual mesh — CI never hits this, humans should know.
        print(f"graftaudit: {len(skipped)} lowering(s) need "
              f">{n_dev} devices and were skipped: "
              + ", ".join(e.name for e in skipped), file=sys.stderr)

    if args.list_lowerings:
        width = max(len(e.name) for e in entries)
        for e in entries:
            mark = "" if e in runnable else "  [skipped: needs "\
                f"{e.needs_devices} devices]"
            parity = "parity" if e.parity else "      "
            print(f"{e.name:<{width}}  {parity}  {e.doc or e.op}{mark}")
        return 0

    selected = rules.all_ir_rules()
    if args.rules:
        wanted = [r.strip() for r in args.rules.split(",") if r.strip()]
        unknown = [r for r in wanted if r not in selected]
        if unknown:
            print(f"graftaudit: unknown rule(s): {', '.join(unknown)} "
                  "(try --list-rules)", file=sys.stderr)
            return 2
        selected = {r: selected[r] for r in wanted}

    traces = [registry.trace_lowering(e) for e in runnable]
    findings = rules.run_ir_rules(traces, selected)
    findings += rules.parity_findings(traces)

    costs: Dict[str, dict] = {}
    if not args.no_cost:
        findings += donation.audit_donation()
        costs = B.collect_costs(traces)
        if args.write_budgets:
            broken = sorted(n for n, c in costs.items() if "error" in c)
            if broken:
                # Blessing an error record would permanently un-gate that
                # lowering: check_budgets has no metrics to compare
                # against, so later regressions pass silently.
                print("graftaudit: refusing --write-budgets while "
                      "lowering(s) fail to compile: "
                      + ", ".join(broken)
                      + " — fix the entries, then bless", file=sys.stderr)
                return 2
            if skipped:
                # A degraded run must not bless: the written file would
                # drop the sharded entries and fail the next full CI run
                # as "new lowering with no blessed budget".
                print("graftaudit: refusing --write-budgets on a degraded "
                      "run (skipped: "
                      + ", ".join(e.name for e in skipped)
                      + ") — rerun where graftaudit can pin the full "
                      "virtual mesh (no prior jax import)",
                      file=sys.stderr)
                return 2
            # A re-bless keeps the stored tolerance unless explicitly
            # overridden — check_budgets honors the stored value, so the
            # bless path must not silently reset it to the default.
            stored = B.load_budgets(args.budgets).get("tolerance")
            tol = (args.tolerance if args.tolerance is not None
                   else stored if stored is not None
                   else B.DEFAULT_TOLERANCE)
            path = B.write_budgets(costs, args.budgets, tolerance=tol)
            print(f"graftaudit: wrote {len(costs)} budget entr(ies) to "
                  f"{path}")
            return 0
        findings += B.check_budgets(costs, B.load_budgets(args.budgets),
                                    tolerance=args.tolerance,
                                    skipped=[e.name for e in skipped])
    elif args.write_budgets:
        print("graftaudit: --write-budgets needs the compile pass; drop "
              "--no-cost", file=sys.stderr)
        return 2

    mem_records: Dict[str, dict] = {}
    mem_skip: List[str] = []
    if not args.no_cost and not args.no_mem:
        mem_records = M.collect_memory(traces)
        mem_skip = M.mem_skipped(mem_records)
        if mem_skip:
            # The memory_analysis-unavailable degrade list — loud, like
            # the <8-device skip list, never a crash.
            print(f"graftaudit: memory plane degraded — {len(mem_skip)} "
                  "lowering(s) without memory_analysis() support: "
                  + ", ".join(mem_skip), file=sys.stderr)
        if args.write_membudgets:
            broken = sorted(n for n, r in mem_records.items()
                            if "error" in r)
            if broken:
                # Blessing an error record would permanently un-gate the
                # lowering — no bytes to ratchet against.
                print("graftaudit: refusing --write-membudgets while "
                      "lowering(s) fail to compile: " + ", ".join(broken)
                      + " — fix the entries, then bless", file=sys.stderr)
                return 2
            if skipped or mem_skip:
                # A degraded run (missing devices OR a backend that
                # cannot price memory) must not bless: the written file
                # would drop those entries and fail the next full run as
                # "no blessed memory budget".
                degraded = ([e.name for e in skipped] + mem_skip)
                print("graftaudit: refusing --write-membudgets on a "
                      "degraded run (skipped: " + ", ".join(degraded)
                      + ") — rerun where the full registry prices",
                      file=sys.stderr)
                return 2
            stored = M.load_membudgets(args.membudgets).get("tolerance")
            tol = (args.tolerance if args.tolerance is not None
                   else stored if stored is not None
                   else M.DEFAULT_TOLERANCE)
            print("graftaudit: refitting the capacity model (two extra "
                  "full-registry AOT passes — minutes, not seconds)",
                  file=sys.stderr)
            cap = C.fit_capacity_model(mem_records)
            path = M.write_membudgets(mem_records, args.membudgets,
                                      tolerance=tol, capacity_model=cap)
            print(f"graftaudit: wrote {len(mem_records)} memory budget "
                  f"entr(ies) + {len(cap.get('entries', {}))} capacity "
                  f"fit(s) to {path}")
            return 0
        findings += M.check_membudgets(
            mem_records, M.load_membudgets(args.membudgets),
            tolerance=args.tolerance,
            skipped=[e.name for e in skipped])
    elif args.write_membudgets:
        print("graftaudit: --write-membudgets needs the compile pass; "
              "drop --no-cost/--no-mem", file=sys.stderr)
        return 2

    findings = sorted(findings)
    baseline_path = args.baseline or _default_baseline_path()
    if args.write_baseline:
        path = core.write_baseline(findings, {}, baseline_path)
        print(f"graftaudit: wrote {len(findings)} finding(s) to {path}")
        return 0
    baseline = core.load_baseline(baseline_path)
    new, grandfathered = core.apply_baseline(findings, {}, baseline)

    census = {t.entry.name: {"collectives": t.collectives,
                             "ici_bytes_est": t.ici_bytes_est}
              for t in traces if t.collectives}
    if args.as_json:
        doc = {
            "findings": [f.to_json() for f in new],
            "baselined": len(grandfathered),
            "lowerings": [t.entry.name for t in traces],
            "skipped": [e.name for e in skipped],
            "census": census,
            "costs": costs,
            "memory": mem_records,
            "mem_skipped": mem_skip,
            "ok": not new,
        }
        print(json.dumps(doc, indent=1))
        return 1 if new else 0

    for f in new:
        print(f.render())
    if new:
        counts: Dict[str, int] = {}
        for f in new:
            counts[f.severity] = counts.get(f.severity, 0) + 1
        summary = ", ".join(f"{n} {sev}" for sev, n in sorted(counts.items()))
        print(f"graftaudit: {len(new)} finding(s) ({summary}); "
              f"{len(grandfathered)} baselined")
        return 1
    suffix = f" ({len(grandfathered)} baselined)" if grandfathered else ""
    mem_note = ""
    if mem_records:
        priced = len(mem_records) - len(mem_skip)
        mem_note = f", {priced} memory-ratcheted"
        if mem_skip:
            mem_note += f" ({len(mem_skip)} mem-skipped)"
    print(f"graftaudit: clean{suffix} — {len(traces)} lowering(s) audited"
          + ("" if args.no_cost else
             f", {len(costs)} cost-ratcheted{mem_note}, donation verified"))
    return 0


def _cli() -> int:
    try:
        return main()
    except BrokenPipeError:
        return 0


if __name__ == "__main__":
    sys.exit(_cli())
