"""The harness end to end on the CPU at a tiny size, through run_cell:
weights from the seed, the real scheduler, both loop kinds, the metric
readers, and the check against the plain reference."""
import chipbench_paths  # noqa: F401  (sets the import path)

import io
import json
import os
import time

import pytest

from chipbench import cli
from chipbench_tiny import make_tree


def run(root, bench, workload, seed=11, seconds=1.5, trace=False):
    out, err = io.StringIO(), io.StringIO()
    res = cli.run_cell(root, bench, workload, seed, seconds, trace,
                       t_start=time.perf_counter(), require_tpu=False,
                       out=out, err=err)
    return res, out.getvalue(), err.getvalue()


@pytest.mark.parametrize("workload", ["tiny.tiny-open", "tiny.tiny-closed"])
def test_cell_runs_and_checks_out(tmp_path, monkeypatch, workload):
    root, bench = make_tree(tmp_path, monkeypatch)
    res, out, err = run(root, bench, workload)
    lines = out.strip().splitlines()
    assert json.loads(lines[-1]) == json.loads(json.dumps(res))
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    want = {"tpot_p95_ms", "output_tok_s", "setup_s"}
    assert want <= set(res["metrics"])
    for m in res["metrics"].values():
        assert m["value"] > 0
    side = json.loads(lines[-2][len("run: "):])
    assert side["compiles_in_window"]["compiles"] == 0
    assert err.strip().splitlines()[-1].startswith("check failed_requests")


def test_run_refuses_without_a_tpu(tmp_path, monkeypatch):
    root, bench = make_tree(tmp_path, monkeypatch)
    with pytest.raises(SystemExit):
        cli.run_cell(root, bench, "tiny.tiny-open", 1, 1.0, False,
                     t_start=time.perf_counter())


def test_added_files_are_picked_up(tmp_path, monkeypatch):
    """A new metric file, listed in BENCHMARK.json, is read in the
    cells it names; nothing that was there is edited."""
    extra = {"name": "sched.steps_in_window", "unit": "1",
             "better": "higher", "source": "host_clock", "layer": "scheduler",
             "moves": "output_tok_s", "workloads": ["tiny.tiny-closed"]}
    root, bench = make_tree(tmp_path, monkeypatch, [extra])
    with open(os.path.join(bench, "metrics",
                           "sched.steps_in_window.py"), "w") as f:
        f.write("def read(run):\n    return len(run.steps)\n")
    res, _, _ = run(root, bench, "tiny.tiny-closed", trace=False)
    assert "sched.steps_in_window" not in res["metrics"]   # per-layer
    from chipbench import spec
    cell = spec.load(root, bench, "tiny.tiny-closed")
    assert [m["name"] for m in cell.per_layer] == [
        "sched.lane_occupancy", "sched.steps_in_window"]
    assert cell.metric("sched.steps_in_window").read(
        type("R", (), {"steps": [1, 2, 3]})()) == 3
    other = spec.load(root, bench, "tiny.tiny-open")
    assert [m["name"] for m in other.per_layer] == ["sched.lane_occupancy"]


def test_tpot_counts_only_the_window():
    """A request warm-started before the window counts from its first
    harvest inside it; one with a single harvest inside gives nothing."""
    from chipbench import work
    from chipbench.client import Rec
    from chipbench.loops import Req

    def rec(win_first, win_first_n, last, in_window):
        return Rec(Req(0, None, 0), None, None, 0.0, first=-30.0,
                   last=last, seen=100, in_window=in_window,
                   win_first=win_first, win_first_n=win_first_n)

    recs = [rec(1.0, 16, 2.6, 48),     # 32 tokens in 1.6 s after 1.0
            rec(5.0, 16, 5.0, 16),     # one harvest in the window
            rec(None, 0, None, 0)]     # none
    run = work.Run(cell={}, model={}, serve={}, traffic={}, window=(0, 10),
                   recs=recs, steps=[], peak_bytes=0, setup_s=0.0, peak={})
    assert work.tpot_samples(run) == [pytest.approx(0.05)]
