"""The knee sweep runs end to end on the CPU: one set-up, one window at
each offered rate, a checked line per rate."""

import json

import harness
import sweep
from test_correctness import SEED, make_ctx


def test_sweep_runs_a_window_per_rate_and_checks_it(monkeypatch, capsys,
                                                    tmp_path):
    ctx = make_ctx("ws-1m.serve")
    monkeypatch.setattr(harness, "configure_process", lambda: None)
    monkeypatch.setattr(harness, "require_chips", lambda chips: ctx.device)
    monkeypatch.setattr(harness, "load_cell",
                        lambda spec, name: (ctx.cell, ctx.config, ctx.traffic))
    monkeypatch.setattr(harness, "LAYOUT_CACHE_DIR", str(tmp_path))
    assert sweep.main(["--workload", "ws-1m.serve", "--rates", "20",
                       "--seconds", "0.5", "--seed", str(SEED)]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["rate_per_s"] for x in lines] == [20.0]
    assert lines[0]["correct"] and lines[0]["ticket_p95_ms"] > 0
