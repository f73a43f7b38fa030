"""Table 14 (mesh-sharded serving): tokens/sec + compile time vs device
count on the SPMD serving path, parity asserted at every point.

Each row spawns repro.launch.shard_serve in a SUBPROCESS with
--devices N (it asks for N virtual CPU devices, the only way to get N
real addressable devices on CPU; that must happen before jax
initializes, so it cannot run in-process). The driver serves a request
wave through a
mesh-sharded Scheduler on an N x 1 lane-parallel mesh and asserts every
stream token-identical to the single-device one-shot oracle BEFORE
reporting a number — a row in this table is a correctness certificate
first, a throughput sample second.

What the numbers mean on CPU: all N virtual devices share the same
cores, so tokens/sec does NOT scale with N here (expect it roughly flat
to mildly declining — the column exists to carry the shape of the
measurement to real accelerators, where lane groups own distinct
chips). The columns that are meaningful on CPU:

  * parity_ok — the tentpole claim, asserted per point;
  * compile_sec — SPMD partitioning cost vs device count (GSPMD does
    more work as the mesh grows);
  * the compile-depth section — segment compile time vs num_layers with
    cfg.unroll_layers on/off: the transformer scans over PATTERN
    REPEATS, so scan compile time stays near-flat in depth while the
    unrolled build pays per layer. The residual unrolled cost at
    unroll_layers=False is the pattern-unit body + the tail layers
    (docs/serving.md §Compile-time scaling) — NOT one body per layer.

Emits BENCH_shard.json (uploaded by CI next to the other BENCH_*.json).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from benchmarks.common import print_table, write_bench_json

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEVICES = (1, 2, 4, 8)
DEPTHS = (2, 4, 8)


def _shard_serve(args, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.shard_serve", *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=REPO)
    if p.returncode != 0:
        raise RuntimeError(f"shard_serve {' '.join(args)} failed:\n"
                           + p.stdout[-2000:] + p.stderr[-2000:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def run(quick: bool = False, smoke: bool = False):
    devices = (1, 2) if smoke else DEVICES
    rows = []
    for n in devices:
        out = _shard_serve(["--devices", str(n), "--bench"])
        assert out["ok"] and out["parity_ok"], out
        rows.append({k: out[k] for k in
                     ("devices", "mesh", "n_lanes", "n_requests",
                      "new_tokens", "compile_sec", "decode_sec",
                      "tok_per_sec", "parity_ok")})

    depth_rows = []
    if not smoke:
        out = _shard_serve(["--devices", "1", "--compile-depth"])
        assert out["ok"], out
        depth_rows = out["rows"]
        scan = {r["num_layers"]: r["segment_compile_sec"]
                for r in depth_rows if not r["unroll_layers"]}
        unrolled = {r["num_layers"]: r["segment_compile_sec"]
                    for r in depth_rows if r["unroll_layers"]}
        # the structural claim: going deep costs the UNROLLED build
        # proportionally more than the scanned build
        lo, hi = min(DEPTHS), max(DEPTHS)
        assert (unrolled[hi] / unrolled[lo]
                > scan[hi] / scan[lo]), (scan, unrolled)

    payload = {
        "bench": "shard",
        "workload": {"mesh": "Nx1 lane-parallel", "policy": "trimkv",
                     "note": ("virtual CPU devices share cores: "
                              "tok_per_sec is a shape, parity_ok and "
                              "compile_sec are the measurements")},
        "rows": rows,
        "compile_depth_rows": depth_rows,
        "parity_all": all(r["parity_ok"] for r in rows),
    }
    write_bench_json("BENCH_shard.json", payload)
    print_table(
        "table14_shard (sharded serving vs device count)",
        ("devices", "n_lanes", "new_tokens", "compile_sec",
         "tok_per_sec", "parity_ok"),
        [(r["devices"], r["n_lanes"], r["new_tokens"],
          r["compile_sec"], r["tok_per_sec"], r["parity_ok"])
         for r in rows])
    if depth_rows:
        print_table(
            "segment compile time vs depth (scan vs unrolled)",
            ("num_layers", "unroll_layers", "compile_sec"),
            [(r["num_layers"], r["unroll_layers"],
              r["segment_compile_sec"]) for r in depth_rows])
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="2 device counts, no depth sweep (CI)")
    args = ap.parse_args()
    run(quick=args.quick, smoke=args.smoke)
