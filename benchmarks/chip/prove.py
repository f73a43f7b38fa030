#!/usr/bin/env python3
"""Readings behind a cell's correctness limit, made on the chip at the
cell's own size and load: for each seed, a whole run (set-up, a
window, the check) of the program, then the lower-precision control
over the same prompts and served tokens: the reference computed with
every matrix product's inputs in float8 e4m3 (the nearest precision
below the configuration's bf16). The control's reading is the gap of
the token the control puts first. All seeds run in one process, so
the programs compile once.

    python3 benchmarks/chip/prove.py --workload trimkv4b.reasoning \\
        --seeds 101,102,103 --seconds 20 [--control 1]

Prints one JSON line per seed (the program's checks, and the control's
checks against the same limits), then a summary: the lower reading
(the largest program gap) and the upper one (the smallest control
gap).
Not part of the benchmark's runs.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import cli, correct  # noqa: E402


def control_gap(cell, w, picked):
    """Widest gap, over the sample, of the token the control ranks
    first (0 where the control agrees with the reference everywhere):
    the control put in the program's place, judged as the program's
    served tokens are."""
    ref = cell.reference()
    serve = cell.serve
    seqs = [(r.req.prompt, r.rs.ids) for r in picked]
    ctl = ref.position_logits(w, cell.config["model"], seqs,
                              budget=serve["budget"],
                              chunk=serve["prefill_chunk"], mode="fp8")
    gaps = correct.served_gaps(cell, w, seqs,
                               probes=[[c["argmax"] for c in ctl]])[0]
    return max(float(g.max()) for g in gaps)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=1)
    args = ap.parse_args()
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        row = {"seed": seed}

        def after(cell, run, w, seed, picked):
            if args.control and picked:
                t = time.perf_counter()
                gap = control_gap(cell, w, picked)
                n_tok = sum(len(r.rs.ids) for r in picked)
                ok, checks = correct.judge(cell.cell["correct"], gap,
                                           n_tok, 0)
                row.update(control_gap=gap, control_correct=ok,
                           control_checks=checks,
                           control_s=time.perf_counter() - t)

        t0 = time.perf_counter()
        out = io.StringIO()
        res = cli.run_cell(ROOT, HERE, args.workload, seed, args.seconds,
                           False, t_start=t0, out=out, after=after)
        row.update(correct=res["correct"], checks=res["checks"],
                   metrics={k: v["value"] for k, v in
                            res["metrics"].items()},
                   run_s=time.perf_counter() - t0,
                   side=out.getvalue().splitlines()[0])
        rows.append(row)
        print(json.dumps(row), flush=True)
    lower = max(r["checks"]["max_logit_gap"]["value"] or 0 for r in rows)
    ctl = [r["control_gap"] for r in rows if "control_gap" in r]
    print(json.dumps({
        "lower": lower, "upper": min(ctl) if ctl else None,
        "seeds": len(rows),
        "all_correct": all(r["correct"] for r in rows),
        "control_never_correct": not any(r.get("control_correct")
                                         for r in rows)}), flush=True)


if __name__ == "__main__":
    main()
