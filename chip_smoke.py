#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU at the full width of
trimkv-paper-4b (36 layers, d_model 2560, 32/8 heads of 128, d_ff 9728,
vocab 151936, bf16), with random weights drawn from --seed.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips (v5e 2x2)

One chip, in order:
  1. the device (platform, kind, count), the JAX version and where the
     persistent compilation cache lives;
  2. the model at published widths: parameter bytes, device bytes in use;
  3. agreement: one 1024-token prompt at budget 2048 (nothing is
     evicted), prefill_chunk 512, the Pallas kernels against the XLA
     path with the same weights and the same fed tokens, on the logits
     of the first generated position and of 8 greedy steps;
  4. serving: 16 Poisson requests (prompts 1024-4096 tokens, max_new
     64-128) through build_engine + an 8-lane Scheduler evicting down to
     budget 512, once with phased and once with interleaved admission.
     The compiled decode-segment program must hold the Pallas kernels
     (tpu_custom_call), not interpret mode or the XLA path.

--four-chips runs the sharded path and what it is compared with, and
nothing else: a one-chip reference (prefill logits and greedy streams of
8 requests), then the same requests through build_engine(mesh=...) on a
1x4 head-parallel and a 4x1 lane-parallel mesh over the real devices,
and the HLO audit of the 4x1 hot loop (no resharding collectives). The
sharded engine attends through the XLA path: GSPMD cannot partition a
Pallas kernel.

Everything runs in this process, which holds the chips; it starts no
other. The times printed are a smoke run, not a benchmark. A failed
check raises, so the exit code is non-zero and no verdict is printed;
otherwise the last line of stdout is the JSON verdict.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from repro.configs import get_config                         # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_serving_mesh              # noqa: E402
from repro.launch.serve import poisson_requests              # noqa: E402
from repro.launch.shard_serve import run_check_hlo           # noqa: E402
from repro.models import transformer as T                    # noqa: E402
from repro.serve import Scheduler, Status, build_engine      # noqa: E402

ARCH = "trimkv-paper-4b"
LANES = 8
CHUNK = 512
SERVE_KW = dict(attn_impl="pallas", policy="trimkv", budget=512,
                prefill_chunk=CHUNK, decode_segment=16)
# agreement: a prompt shorter than the budget, so no slot is evicted
AGREE_PROMPT, AGREE_BUDGET, AGREE_STEPS = 1024, 2048, 8
# logits tolerance: max |a - b| over the vocabulary, as a fraction of
# the reference's max |logit| at that position. Weights and activations
# are bf16 and the paths round and accumulate in different orders: a
# 36-layer bf16 model differed by 2.2% between the two attention paths
# on the CPU, while a wrong mask or head mapping moves logits by O(1)
LOGIT_TOL = 0.05
# serving traffic
N_REQUESTS, PROMPT_LEN, MAX_NEW = 16, (1024, 4096), (64, 128)
# four chips: 8 equal-length requests, one admission round. GSPMD
# cannot partition a Mosaic kernel (the TPU compiler asks for a
# shard_map around it), so the mesh-sharded engine serves with the XLA
# attention path, and so does its one-chip reference
SHARD_PROMPT, SHARD_NEW = 1024, 16
SHARD_KW = {**SERVE_KW, "attn_impl": "xla"}

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeCheckFailed(Exception):
    pass


def require(ok, what):
    if not ok:
        raise SmokeCheckFailed(what)


class CompileClock:
    """Seconds JAX spent compiling — or reading a compiled program back
    from the persistent cache — and that cache's hits and misses."""

    def __init__(self):
        self.sec, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, duration_secs, **_):
        if event == COMPILE_EVENT:
            self.sec += duration_secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def bytes_in_use(device, key="bytes_in_use"):
    return device.memory_stats()[key]


def init_weights(cfg, seed):
    """Random weights from `seed`, generated on the device (one program
    per tree, so no float32 copy of a whole stack lingers)."""
    kp, kg = jax.random.split(jax.random.PRNGKey(seed))
    params = jax.jit(lambda k: T.init_params(k, cfg))(kp)
    gates = jax.jit(lambda k: T.init_gate_params(k, cfg))(kg)
    return params, gates


def logits_fn(cfg):
    """[B, d] last hidden state -> [B, vocab] f32 logits."""
    return jax.jit(lambda p, h: T.compute_logits(p, cfg, h)
                   [..., :cfg.vocab_size])


def compare_logits(label, got, ref):
    """got/ref: [rows, vocab]. Prints the worst row's difference beside
    its tolerance; fails past it."""
    require(np.isfinite(got).all() and np.isfinite(ref).all(),
            f"{label}: non-finite logits")
    diff = np.abs(got - ref).max(axis=1)
    tol = LOGIT_TOL * np.abs(ref).max(axis=1)
    worst = int(np.argmax(diff / tol))
    rel_l2 = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    same_argmax = int((got.argmax(1) == ref.argmax(1)).sum())
    print(f"{label}: max |logit diff| {diff[worst]:.6g} at row {worst} "
          f"(tolerance {tol[worst]:.6g} = {LOGIT_TOL} x max |logit|); "
          f"all rows max diff {diff.max():.6g}; relative L2 "
          f"{rel_l2:.6g}; argmax agrees on {same_argmax}/{len(diff)} rows",
          flush=True)
    require(bool((diff <= tol).all()),
            f"{label}: logits differ beyond the tolerance")


def agreement(cfg, params, gates, seed, *, prompt_len=AGREE_PROMPT,
              budget=AGREE_BUDGET, steps=AGREE_STEPS, chunk=CHUNK):
    """The Pallas serving kernels against the XLA path: prefill, then
    `steps` decode steps fed the same tokens (the Pallas path's greedy
    picks), comparing logits at every position."""
    prompt = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, size=(1, prompt_len)).astype(np.int32)
    to_logits = logits_fn(cfg)
    rows, fed = {}, None
    for impl in ("pallas", "xla"):
        eng = build_engine(cfg, params, gates, attn_impl=impl,
                           policy="trimkv", budget=budget,
                           prefill_chunk=chunk)
        state, h_last = eng.prefill(prompt, chunked=True)
        out = [to_logits(params, h_last)]
        toks = []
        for j in range(steps):
            tok = (fed[j] if fed is not None else
                   jnp.argmax(out[-1], axis=-1).astype(jnp.int32))
            toks.append(tok)
            state, logits = eng._decode(state, tok)
            out.append(logits[..., :cfg.vocab_size])
        rows[impl] = np.stack([np.asarray(x[0], np.float32) for x in out])
        fed = fed or toks
    compare_logits(f"agreement pallas vs xla ({prompt_len}-token prompt, "
                   f"first position + {steps} greedy steps)",
                   rows["pallas"], rows["xla"])


def segment_program_text(sched):
    """Compiled text of the decode-segment program exactly as the
    scheduler dispatches it (same operands, so the compile is shared)."""
    seg = sched.eng.lane_closures(sched.greedy, sched.n_lanes)["segment"]
    n = sched.serve.decode_segment
    return seg.lower(sched.state, sched.tok, sched.keys,
                     jnp.asarray(sched.active),
                     jnp.asarray(sched.n_emitted),
                     jnp.asarray(sched.max_new), jnp.asarray(sched.eos),
                     n, np.int32(n)).compile().as_text()


def run_requests(eng, reqs, *, lanes=LANES, interleaved=False):
    """Serve `reqs` to the end; every request must be DONE with exactly
    its max_new in-vocabulary tokens and no lane quarantined or failed.
    Returns ({rid: tokens}, stats, wall seconds)."""
    sched = Scheduler(eng, n_lanes=lanes, interleaved=interleaved)
    t0 = time.perf_counter()
    res = sched.run(reqs)
    wall = time.perf_counter() - t0
    st = sched.stats()
    vocab = eng.cfg.vocab_size
    for r in reqs:
        rs = res[r.rid]
        require(rs.status is Status.DONE,
                f"request {r.rid} ended {rs.status}")
        ids = rs.ids
        require(len(ids) == r.max_new,
                f"request {r.rid}: {len(ids)} tokens, max_new {r.max_new}")
        require(bool(((ids >= 0) & (ids < vocab)).all()),
                f"request {r.rid}: token out of the vocabulary")
    require(st["n_quarantined"] == 0 and st["n_failed"] == 0,
            f"quarantined {st['n_quarantined']}, failed {st['n_failed']}")
    return {r.rid: res[r.rid].ids for r in reqs}, st, wall


def serving(eng, reqs, clock, device, *, lanes=LANES):
    """Phased, then interleaved admission, on one engine, after checking
    that the compiled segment program holds the Pallas kernels."""
    c0 = clock.sec
    txt = segment_program_text(Scheduler(eng, n_lanes=lanes))
    require("tpu_custom_call" in txt,
            "the compiled segment program holds no Pallas kernel")
    print(f"segment program: tpu_custom_call present "
          f"({txt.count('tpu_custom_call')} occurrences, "
          f"compile {clock.sec - c0:.3f}s)", flush=True)
    streams = {}
    for mode in ("phased", "interleaved"):
        c0 = clock.sec
        streams[mode], st, wall = run_requests(
            eng, reqs, lanes=lanes, interleaved=(mode == "interleaved"))
        n_tok = sum(len(s) for s in streams[mode].values())
        print(f"serving [{mode}] smoke run, not a benchmark: "
              f"{len(reqs)} requests DONE on {lanes} lanes, {n_tok} tokens, "
              f"wall {wall:.3f}s (compile {clock.sec - c0:.3f}s inside), "
              f"segments {st['n_segments']}, prefill rounds "
              f"{st['n_prefill_rounds']}, quarantined "
              f"{st['n_quarantined']}, failed {st['n_failed']}, "
              f"peak_bytes_in_use {bytes_in_use(device, 'peak_bytes_in_use')}",
              flush=True)
    same = sum(int((streams["phased"][r.rid] ==
                    streams["interleaved"][r.rid]).sum()) for r in reqs)
    print(f"phased vs interleaved: {same}/{sum(r.max_new for r in reqs)} "
          f"tokens equal position by position", flush=True)


def serving_requests(cfg, seed, n=N_REQUESTS, prompt=PROMPT_LEN,
                     new=MAX_NEW):
    return poisson_requests(n, 8.0, vocab=cfg.vocab_size,
                            prompt_lo=prompt[0], prompt_hi=prompt[1],
                            new_lo=new[0], new_hi=new[1], seed=seed)


def one_chip(cfg, seed, clock):
    dev = jax.devices()[0]
    params, gates = init_weights(cfg, seed)
    jax.block_until_ready((params, gates))
    print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads} x "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{cfg.dtype}; parameter bytes {nbytes(params)}, gate bytes "
          f"{nbytes(gates)}, bytes_in_use {bytes_in_use(dev)}", flush=True)
    c0 = clock.sec
    agreement(cfg, params, gates, seed)
    print(f"agreement compile {clock.sec - c0:.3f}s", flush=True)
    gc.collect()
    eng = build_engine(cfg, params, gates, **SERVE_KW)
    serving(eng, serving_requests(cfg, seed), clock, dev)


def four_chips(cfg, seed, clock, *, prompt_len=SHARD_PROMPT,
               max_new=SHARD_NEW, lanes=LANES):
    devs = jax.devices()
    require(len(devs) == 4, f"--four-chips needs 4 devices, found "
                            f"{len(devs)}")
    reqs = serving_requests(cfg, seed, n=lanes,
                            prompt=(prompt_len, prompt_len),
                            new=(max_new, max_new))
    prompts = np.stack([r.prompt for r in reqs])
    to_logits = logits_fn(cfg)

    def prefill_logits(eng):
        _, h_last = eng.prefill(prompts, chunked=True)
        return np.asarray(to_logits(eng.params, h_last), np.float32)

    # one-chip reference, kept on the host: a replicated copy of the
    # weights and the one-chip copy do not fit device 0 together
    params, gates = init_weights(cfg, seed)
    weight_bytes = nbytes(params) + nbytes(gates)
    eng = build_engine(cfg, params, gates, **SHARD_KW)
    ref_logits = prefill_logits(eng)
    ref_streams, _, _ = run_requests(eng, reqs, lanes=lanes)
    params, gates = jax.device_get((params, gates))
    del eng
    gc.collect()
    print(f"one-chip reference: {len(reqs)} requests of {prompt_len} "
          f"prompt tokens, {sum(map(len, ref_streams.values()))} tokens; "
          f"device bytes in use after freeing it "
          f"{[bytes_in_use(d) for d in devs]}", flush=True)

    for shape in ((1, 4), (4, 1)):
        label = f"{shape[0]}x{shape[1]}"
        c0 = clock.sec
        eng = build_engine(cfg, params, gates,
                           mesh=make_serving_mesh(*shape), **SHARD_KW)
        jax.block_until_ready(eng.params)
        placed = [bytes_in_use(d) for d in devs]
        print(f"{label}: bytes in use per device after placing "
              f"{weight_bytes} weight bytes: {placed}", flush=True)
        if shape == (1, 4):
            require(max(placed) < 0.5 * weight_bytes,
                    f"{label}: a device holds over half the weights")
        compare_logits(f"{label} prefill logits vs one chip",
                       prefill_logits(eng), ref_logits)
        streams, _, wall = run_requests(eng, reqs, lanes=lanes)
        same = sum(int((streams[r.rid] == ref_streams[r.rid]).sum())
                   for r in reqs)
        print(f"{label}: greedy tokens equal to the one-chip reference "
              f"{same}/{lanes * max_new}; wall {wall:.3f}s, compile "
              f"{clock.sec - c0:.3f}s (smoke run)", flush=True)
        if shape == (4, 1):
            audit = run_check_hlo(eng, lanes)
            print(f"{label} HLO audit of {audit['programs']}: resharding "
                  f"collectives {audit['resharding_collectives']}",
                  flush=True)
        del eng
        gc.collect()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on four chips")
    args = ap.parse_args()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees {device}); this "
                 f"script runs only on the chip")
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"device: platform {device['platform']}, kind {device['kind']}, "
          f"count {device['count']}; jax {jax.__version__}; compile cache "
          f"{cache_dir}", flush=True)
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(cfg, args.seed, clock)
    else:
        one_chip(cfg, args.seed, clock)
    print(f"total wall {time.perf_counter() - t0:.3f}s, compile "
          f"{clock.sec:.3f}s, persistent cache hits {clock.hits}, "
          f"misses {clock.misses}", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
