"""One run of one cell: set-up, the measured window, the check against
the plain reference, and the result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The run refuses to measure anywhere but a TPU with as many chips as the
cell asks for. Set-up (process start to the window's opening) makes
the weights on the device from the seed, builds the engine and the
scheduler, serves one request per mixed-segment length the window can
meet (so nothing compiles inside it) and the generator's warm
requests. After the window it reads the peak memory, frees the
program's state, runs the reference over a sample of the finished
requests and prints, last on stdout, one JSON object.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

# request ids of the warm-up shapes start here, above any traffic's
WARM_RID = 10 ** 8


def _device_info(require_tpu: bool, chips: int):
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise SystemExit(f"benchmark: no TPU found (JAX sees {info}); the "
                         f"benchmark measures only on the chip")
    if info["count"] < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, "
                         f"JAX sees {info['count']}")
    return devs, info


def _compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: $JAX_COMPILATION_CACHE_DIR
    where it is set (JAX reads it itself), else a fixed directory in the
    checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def build_program(model: dict, serve: dict, w: dict, ref):
    """The system under test: build_engine over the benchmark's weights
    `w`, re-nested by the configuration's reference `ref`, and an
    n-lane Scheduler, with the serving settings `serve` (a Cell's
    `serve`). Returns (scheduler, Request class)."""
    import jax
    from repro.configs import ModelConfig
    from repro.models import transformer as T
    from repro.serve import Request, Scheduler, build_engine
    from chipbench import weights

    cfg = ModelConfig(**{**model, "attn_pattern":
                         tuple(model["attn_pattern"])})
    params, gates = ref.to_program(w, model)
    key = jax.random.PRNGKey(0)
    weights.check_layout(
        params, gates, jax.eval_shape(lambda: T.init_params(key, cfg)),
        jax.eval_shape(lambda: T.init_gate_params(key, cfg)))
    eng = build_engine(
        cfg, params, gates, attn_impl=serve["attn_impl"],
        policy=serve["policy"], budget=serve["budget"],
        prefill_chunk=serve["prefill_chunk"],
        decode_segment=serve["decode_segment"],
        max_queue=serve["max_queue"])
    sched = Scheduler(eng, n_lanes=serve["lanes"],
                      interleaved=serve["interleaved"])
    return sched, Request


def _lateness(recs, window):
    late = [r.sent - r.due for r in recs
            if r.due is not None and r.due <= window[1]]
    if not late:
        return {"p95_s": 0.0, "max_s": 0.0, "n": 0}
    return {"p95_s": float(np.percentile(late, 95)),
            "max_s": float(max(late)), "n": len(late)}


def set_up(cell, seed: int, seconds: float, trace: bool):
    """Weights from the seed in the reference's layout, the program
    over them, the traffic's generator, and a client that has served
    every shape the window can meet and the generator's warm requests.
    Returns (reference, weights, scheduler, generator, client)."""
    from chipbench import client, loops, weights

    model, serve = cell.config["model"], cell.serve
    ref = cell.reference()
    w = weights.make_weights(ref.canonical_shapes(model), seed)
    sched, request_cls = build_program(model, serve, w, ref)
    loop = loops.make(cell.traffic, seed=seed, seconds=seconds,
                      vocab=model["vocab_size"])
    drv = client.Client(sched, request_cls, spans=trace)
    drv.warm_shapes(loop.max_prompt, serve["prefill_chunk"],
                    model["vocab_size"], first_rid=WARM_RID)
    drv.warm_start(loop.warm())
    return ref, w, sched, loop, drv


def run_cell(root: str, bench: str, workload: str, seed: int,
             seconds: float, trace: bool, *, t_start: float,
             require_tpu: bool = True, out=sys.stdout, err=sys.stderr,
             after=None):
    """Everything one run does; returns the result object it printed.
    `after(cell, run, w, seed, picked)`, when given, runs once the check
    is done (prove.py reads the lower-precision control there); the
    benchmark's own runs give none."""
    from chipbench import client, correct, peaks, spec, work
    from chipbench import trace as tracing

    cell = spec.load(root, bench, workload)
    import repro.serve  # noqa: F401  (fail at once where the program is absent)
    devs, dev_info = _device_info(require_tpu, cell.workload["chips"])
    # off the chip (tests) the v5e's peaks stand in: nothing measured
    # there is a device number
    chip_peaks = peaks.peaks(dev_info["kind"] if require_tpu
                             else "TPU v5 lite")
    _compile_cache(root)
    clock = client.CompileClock()
    ref, w, sched, loop, drv = set_up(cell, seed, seconds, trace)
    stats0 = sched.stats()
    comp0 = clock.snapshot()

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
        else None
    import jax
    if trace:
        with tracing.capture(trace_dir):
            setup_s = time.perf_counter() - t_start
            with jax.profiler.TraceAnnotation("bench.window"):
                drv.run_window(loop, seconds)
    else:
        setup_s = time.perf_counter() - t_start
        drv.run_window(loop, seconds)
    comp1 = clock.snapshot()
    stats1 = sched.stats()
    mem = [d.memory_stats() or {} for d in devs[:cell.workload["chips"]]]
    peak_bytes = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    recs = list(drv.recs.values())
    recs = [r for r in recs if r.req.rid < WARM_RID]
    stats = {k: v - stats0.get(k, 0) for k, v in stats1.items()
             if isinstance(v, (int, float))}
    run = work.Run(cell=cell.workload, model=cell.config["model"],
                   serve=cell.serve, traffic=cell.traffic,
                   window=drv.window, recs=recs, steps=drv.steps,
                   peak_bytes=peak_bytes, setup_s=setup_s, peak=chip_peaks,
                   stats=stats, reference=ref)
    del drv, sched
    gc.collect()

    if trace:
        run.trace = tracing.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)

    side = {
        "generator_lateness": _lateness(recs, run.window),
        "compiles_in_window": {k: comp1[k] - comp0[k] for k in comp1},
        "scheduler_stats_in_window": stats,
        "window_s": run.seconds,
    }
    print("run: " + json.dumps(side), file=out, flush=True)

    due = work.due_in_window(run)
    failed = sum(1 for r in due if r.rs.terminal and not r.rs.done)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.metric(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    t_check = time.perf_counter()
    ok, checks, picked = correct.check(cell, run, w, seed, failed=failed)
    print(f"check: {len(picked)} requests against the reference in "
          f"{time.perf_counter() - t_check:.1f} s", file=err, flush=True)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=err, flush=True)

    device = {**dev_info, "memory_peak_bytes": peak_bytes}
    result = {"correct": ok, "attempted": len(due), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s()
        result["breakdown"] = {"device_ops": run.trace.top_ops(),
                               "idle_gaps": run.trace.idle_gaps()}
    result["checks"] = checks
    if after is not None:
        after(cell, run, w, seed, picked)
    print(json.dumps(result), file=out, flush=True)
    return result


def main(t_start: float, argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    root = os.path.dirname(os.path.dirname(bench))
    sys.path.insert(0, os.path.join(root, "src"))
    run_cell(root, bench, args.workload, args.seed, args.seconds,
             bool(args.trace), t_start=t_start)
    return 0
