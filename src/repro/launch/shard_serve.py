"""Sharded-serving validation driver: SPMD serving on virtual CPU
devices (docs/serving.md §Sharded serving).

Run as its own process: main() pins the CPU backend and asks for
--devices virtual devices before jax initializes, so the caller's
process keeps its own backend. tests/test_shard_serve and
benchmarks/table14_shard both spawn it and parse the JSON it prints.
Importing this module sets nothing: the chip smoke test reuses
run_check_hlo on real devices.

Modes (one JSON document on stdout either way):
  default        parity matrix: for each requested mesh (e.g. 8x1
                 lane-parallel and 1x8 head-parallel), serve the same
                 request set through a mesh-sharded Scheduler for
                 several eviction policies x {phased, interleaved}, plus
                 a park/revive (swap-out + resume) case, a prefix-cache
                 hit case and a speculative-decoding case — and assert
                 every request's stream is TOKEN-IDENTICAL to a
                 single-device one-shot Engine.generate oracle, with the
                 exact dispatch-count formula intact.
  --bench        one throughput point for table14_shard: tokens/sec +
                 compile time on a (devices x 1) lane-parallel mesh,
                 parity asserted against the same oracle.
  --check-hlo    lower the segment + admit closures on the lane-parallel
                 mesh and assert the optimized HLO contains NO
                 cross-shard resharding collectives (all-gather /
                 all-to-all / collective-permute) — the shard-local
                 admission contract, checked on the compiled artifact
                 rather than trusted from the source.
  --compile-depth  compile time vs depth with cfg.unroll_layers on/off
                 (single device): the scan-over-layers residual
                 measurement referenced by docs/serving.md.
"""
import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.mesh import make_serving_mesh
from repro.models import transformer as T
from repro.serve import Request, Scheduler, Status, build_engine

# head counts chosen to DIVIDE the 1x8 head-parallel mesh (8 MHA heads)
# while the 8x1 mesh shards the lane axis instead — the two prod-mesh
# directions, exercised by the same config
VOCAB = 64


def smoke_cfg(num_layers: int = 2, unroll: bool = False):
    return dataclasses.replace(
        get_smoke_config("trimkv-paper-4b"), num_layers=num_layers,
        d_model=64, d_ff=128, num_heads=8, num_kv_heads=8,
        vocab_size=VOCAB, gate_bias_init=3.0, unroll_layers=unroll)


def make_requests(lens, max_new, seed0=0):
    rng = np.random.RandomState(7)
    return [Request(rid=i,
                    prompt=rng.randint(0, VOCAB, size=L).astype(np.int32),
                    max_new=m, seed=seed0 + i)
            for i, (L, m) in enumerate(zip(lens, max_new))]


class Oracle:
    """Single-device one-shot streams, memoized per policy (one engine
    each — its compilations are reused across every case and mesh)."""

    def __init__(self, cfg, params, gates, serve_kw):
        self.cfg, self.params, self.gates = cfg, params, gates
        self.serve_kw = serve_kw
        self._engines = {}
        self._streams = {}

    def stream(self, policy: str, req: Request) -> np.ndarray:
        key = (policy, req.rid, req.prompt.tobytes(), req.max_new,
               req.seed)
        if key not in self._streams:
            if policy not in self._engines:
                self._engines[policy] = build_engine(
                    self.cfg, self.params, self.gates, policy=policy,
                    **self.serve_kw)
            eng = self._engines[policy]
            self._streams[key] = eng.generate(
                req.prompt[None], req.max_new, chunked=True,
                greedy=True, seed=req.seed)["ids"][0]
        return self._streams[key]


def _formula(stats) -> int:
    return (stats["n_prefill_rounds"] + stats["n_segments"]
            + stats["n_resets"] + stats["n_swaps"] + stats["n_resumes"]
            + stats.get("n_prefix_installs", 0)
            + stats.get("n_prefix_extracts", 0)
            + stats.get("n_faults_injected", 0))


def _check(res, reqs, oracle, policy, label):
    for r in reqs:
        want = np.asarray(oracle.stream(policy, r))
        got = np.asarray(res[r.rid].ids)
        if res[r.rid].status is not Status.DONE:
            raise AssertionError(
                f"{label}: rid={r.rid} ended {res[r.rid].status}")
        if got.shape != want.shape or not np.array_equal(got, want):
            raise AssertionError(
                f"{label}: rid={r.rid} sharded stream {got.tolist()} "
                f"!= one-shot {want.tolist()}")


def run_parity(mesh_shape, policies, oracle, cfg, params, gates,
               serve_kw, n_lanes):
    mesh = make_serving_mesh(*mesh_shape)
    cases = []
    engines = {}
    reqs_spec = ([5, 11, 19, 8, 14, 23], [6, 3, 8, 5, 7, 4])
    for policy in policies:
        eng = engines[policy] = build_engine(
            cfg, params, gates, mesh=mesh, policy=policy, **serve_kw)
        for interleaved in (False, True):
            t0 = time.time()
            reqs = make_requests(*reqs_spec)
            sched = Scheduler(eng, n_lanes=n_lanes,
                              interleaved=interleaved)
            d0 = eng.dispatch_count
            res = sched.run(reqs)
            st = sched.stats()
            assert eng.dispatch_count - d0 == _formula(st), (
                policy, interleaved, eng.dispatch_count - d0,
                _formula(st))
            label = (f"{mesh_shape[0]}x{mesh_shape[1]}/{policy}/"
                     f"{'interleaved' if interleaved else 'phased'}")
            _check(res, reqs, oracle, policy, label)
            cases.append({"case": label, "n_requests": len(reqs),
                          "ok": True, "sec": round(time.time() - t0, 2)})

    # swap-out + resume: park a decoding lane mid-flight, revive it, and
    # the final stream must still match the uninterrupted oracle
    policy = policies[0]
    eng = engines[policy]
    reqs = make_requests([5, 11, 19, 8, 14], [10, 12, 9, 11, 10])
    sched = Scheduler(eng, n_lanes=n_lanes)
    d0 = eng.dispatch_count
    for r in reqs:
        sched.submit(r)
    parked = None
    for _ in range(6):
        sched.step()
        for lane, rs in enumerate(sched.lane_req):
            if (rs is not None and sched.lane_prefill[lane] is None
                    and len(rs.tokens) < rs.request.max_new - 2):
                sched.park(rs.rid)
                parked = rs.rid
                break
        if parked is not None:
            break
    assert parked is not None, "no decodable lane to park"
    sched.step()
    sched.revive(parked)
    res = sched.run()
    st = sched.stats()
    assert st["n_swaps"] >= 1 and st["n_resumes"] >= 1, st
    assert eng.dispatch_count - d0 == _formula(st)
    label = f"{mesh_shape[0]}x{mesh_shape[1]}/{policy}/park-revive"
    _check(res, reqs, oracle, policy, label)
    cases.append({"case": label, "n_requests": len(reqs), "ok": True,
                  "n_swaps": st["n_swaps"], "n_resumes": st["n_resumes"]})

    # prefix-cache hits: two waves sharing a 16-token prefix on one
    # scheduler — wave 2 must HIT the slab wave 1 captured, and every
    # stream still equals its one-shot oracle
    rng = np.random.RandomState(11)
    base = rng.randint(0, VOCAB, size=16).astype(np.int32)
    def with_prefix(rid, extra, max_new, seed):
        return Request(
            rid=rid, max_new=max_new, seed=seed,
            prompt=np.concatenate(
                [base, rng.randint(0, VOCAB, size=extra)]
            ).astype(np.int32))
    eng = build_engine(cfg, params, gates, mesh=mesh, policy=policy,
                       prefix_cache_bytes=1 << 22, prefix_min_tokens=8,
                       **serve_kw)
    sched = Scheduler(eng, n_lanes=n_lanes)
    d0 = eng.dispatch_count
    wave1 = [with_prefix(i, e, m, 20 + i)
             for i, (e, m) in enumerate([(5, 6), (9, 4), (13, 5)])]
    res = dict(sched.run(wave1))
    wave2 = [with_prefix(10 + i, e, m, 30 + i)
             for i, (e, m) in enumerate([(3, 5), (7, 6), (11, 4)])]
    res.update(sched.run(wave2))
    st = sched.stats()
    assert st["n_prefix_hits"] >= 1, st
    assert eng.dispatch_count - d0 == _formula(st)
    label = f"{mesh_shape[0]}x{mesh_shape[1]}/{policy}/prefix"
    _check(res, wave1 + wave2, oracle, policy, label)
    cases.append({"case": label, "n_requests": 6, "ok": True,
                  "n_prefix_hits": st["n_prefix_hits"]})

    # speculative decoding: draft/verify lanes under sharding — the
    # exact-replay rollback must stay bit-identical across shards
    eng = build_engine(cfg, params, gates, mesh=mesh, policy=policy,
                       spec_k=2, **serve_kw)
    reqs = make_requests([5, 11, 19, 8], [8, 6, 9, 7], seed0=50)
    sched = Scheduler(eng, n_lanes=n_lanes)
    res = sched.run(reqs)
    st = sched.stats()
    assert st["n_spec_rounds"] > 0, st
    label = f"{mesh_shape[0]}x{mesh_shape[1]}/{policy}/spec"
    _check(res, reqs, oracle, policy, label)
    cases.append({"case": label, "n_requests": len(reqs), "ok": True,
                  "n_spec_tokens": st["n_spec_tokens"]})
    return cases


def run_bench(devices, oracle, cfg, params, gates, serve_kw, n_lanes):
    """One table14_shard point: lane-parallel (devices x 1) mesh,
    compile time (scheduler build + first step) and steady-state
    decode throughput over a drain, parity asserted."""
    mesh = make_serving_mesh(devices, 1) if devices > 1 else None
    policy = "trimkv"
    eng = build_engine(cfg, params, gates, mesh=mesh, policy=policy,
                       **serve_kw)
    reqs = make_requests([5, 11, 19, 8, 14, 23, 9, 17] * 2,
                         [12, 10, 14, 11, 13, 10, 12, 15] * 2)
    t0 = time.time()
    sched = Scheduler(eng, n_lanes=n_lanes)
    for r in reqs:
        sched.submit(r)
    sched.step()
    t_compile = time.time() - t0
    t1 = time.time()
    res = sched.run()
    decode_sec = time.time() - t1
    _check(res, reqs, oracle, policy, f"bench/{devices}dev")
    n_tok = sum(len(res[r.rid].ids) for r in reqs)
    return {"devices": devices, "mesh": [devices, 1],
            "n_lanes": n_lanes, "n_requests": len(reqs),
            "new_tokens": n_tok,
            "compile_sec": round(t_compile, 3),
            "decode_sec": round(decode_sec, 3),
            "tok_per_sec": round(n_tok / max(decode_sec, 1e-9), 1),
            "parity_ok": True}


_RESHARD_COLLECTIVES = ("all-gather", "all-to-all", "collective-permute")


def run_check_hlo(eng, n_lanes):
    """Compile the hot-loop closures of `eng` (an Engine on a
    lane-parallel mesh) and assert the OPTIMIZED HLO has no cross-shard
    resharding collective — lane-aligned operands + mask-select
    installs keep every program shard-local on the lane axis (scalar
    all-reduce, e.g. a global any() on health flags, is tolerated: it
    moves O(1) bytes)."""
    cl = eng.lane_closures(True, n_lanes)
    state = eng.fresh_state(n_lanes)
    tok = jnp.zeros((n_lanes,), jnp.int32)
    keys = jnp.zeros((n_lanes, 2), jnp.uint32)
    bmask = jnp.zeros((n_lanes,), bool)
    i32 = jnp.zeros((n_lanes,), jnp.int32)
    C, n = eng.serve.prefill_chunk, eng.serve.decode_segment
    chunks = jnp.zeros((2, n_lanes, C), jnp.int32)
    nv = jnp.zeros((2, n_lanes), jnp.int32)
    report = {}
    progs = {
        "segment": (cl["segment"],
                    (state, tok, keys, bmask, i32, i32, i32, n,
                     np.int32(n))),
        "admit": (cl["admit"],
                  (state, tok, keys, chunks, nv, keys, bmask)),
        "resume": (cl["resume"],
                   (state, tok, keys, state, tok, keys, bmask)),
        "extract": (cl["extract"], (state, tok, keys)),
        "reset": (cl["reset"], (state, bmask)),
    }
    for name, (fn, args) in progs.items():
        txt = fn.lower(*args).compile().as_text()
        found = {c: txt.count(c) for c in _RESHARD_COLLECTIVES
                 if c in txt}
        report[name] = found
        assert not found, (
            f"{name} HLO contains cross-shard resharding: {found}")
    return {"mesh": list(eng.mesh.shape.values()),
            "programs": list(progs),
            "resharding_collectives": report, "ok": True}


def run_compile_depth(depths, serve_kw, n_lanes):
    """Compile time vs depth, cfg.unroll_layers on/off (single device):
    the transformer already scans over pattern repeats, so compile time
    with the scan should grow sub-linearly in depth while the unrolled
    build pays per layer — the residual cost documented in
    docs/serving.md (unrolled pattern-unit body + tail)."""
    rows = []
    for unroll in (False, True):
        for depth in depths:
            cfg = smoke_cfg(num_layers=depth, unroll=unroll)
            params = T.init_params(jax.random.PRNGKey(0), cfg)
            gates = T.init_gate_params(jax.random.PRNGKey(1), cfg)
            eng = build_engine(cfg, params, gates, policy="trimkv",
                               **serve_kw)
            cl = eng.lane_closures(True)
            state = eng.fresh_state(n_lanes)
            tok = jnp.zeros((n_lanes,), jnp.int32)
            keys = jnp.zeros((n_lanes, 2), jnp.uint32)
            bmask = jnp.zeros((n_lanes,), bool)
            i32 = jnp.zeros((n_lanes,), jnp.int32)
            t0 = time.time()
            cl["segment"].lower(state, tok, keys, bmask, i32, i32, i32,
                                4, np.int32(4)).compile()
            rows.append({"num_layers": depth, "unroll_layers": unroll,
                         "segment_compile_sec":
                             round(time.time() - t0, 3)})
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--meshes", type=str, default="8x1,1x8",
                    help="comma list of DxM mesh shapes for parity")
    ap.add_argument("--policies", type=str,
                    default="trimkv,streaming_llm,h2o")
    ap.add_argument("--bench", action="store_true")
    ap.add_argument("--check-hlo", action="store_true")
    ap.add_argument("--compile-depth", action="store_true")
    ap.add_argument("--n-lanes", type=int, default=8)
    args = ap.parse_args()
    # virtual CPU devices: set before the first backend use, so only
    # this process (spawned by its callers) is affected
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", args.devices)

    serve_kw = dict(budget=16, prefill_chunk=8, decode_segment=4)
    cfg = smoke_cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    gates = T.init_gate_params(jax.random.PRNGKey(1), cfg)
    oracle = Oracle(cfg, params, gates, serve_kw)
    out = {"devices": args.devices, "n_lanes": args.n_lanes}

    if args.compile_depth:
        out["mode"] = "compile-depth"
        out["rows"] = run_compile_depth([2, 4, 8], serve_kw,
                                        args.n_lanes)
    elif args.check_hlo:
        out["mode"] = "check-hlo"
        mesh_shape = tuple(
            int(x) for x in args.meshes.split(",")[0].split("x"))
        eng = build_engine(cfg, params, gates,
                           mesh=make_serving_mesh(*mesh_shape),
                           policy="trimkv", **serve_kw)
        out.update(run_check_hlo(eng, args.n_lanes))
    elif args.bench:
        out["mode"] = "bench"
        out.update(run_bench(args.devices, oracle, cfg, params, gates,
                             serve_kw, args.n_lanes))
    else:
        out["mode"] = "parity"
        policies = args.policies.split(",")
        cases = []
        for spec in args.meshes.split(","):
            d, m = (int(x) for x in spec.split("x"))
            cases += run_parity((d, m), policies, oracle, cfg, params,
                                gates, serve_kw, args.n_lanes)
        out["cases"] = cases
        out["n_cases"] = len(cases)
    out["ok"] = True
    print(json.dumps(out))


if __name__ == "__main__":
    main()
