"""Serving launcher: batched generation under a KV budget.

One-shot batch (the PR-1/2 fused engine):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --smoke \
      --policy trimkv --budget 64 --prompt-len 256 --max-new 32

Continuous batching (--stream): a synthetic Poisson request stream with
RAGGED prompt lengths and per-request decode budgets is served on
--lanes fixed lanes by the lane scheduler (serve.scheduler) — requests
admit into free lanes, decode in fused segments, retire on
EOS/max_new and refill immediately:

  PYTHONPATH=src python -m repro.launch.serve --arch trimkv-paper-4b \
      --smoke --stream --requests 12 --lanes 4 --rate 4.0

Chaos mode (--inject-faults, docs/serving.md §Fault tolerance): a
seeded FaultInjector NaN-poisons lanes, delays dispatches and
burst-submits hostile traffic while the supervision loop quarantines,
replays, times out and sheds — every request still terminates, and the
printed counters show the degradation:

  PYTHONPATH=src python -m repro.launch.serve --arch trimkv-paper-4b \
      --smoke --stream --inject-faults --corrupt-prob 0.3 \
      --burst-prob 0.2 --timeout-ms 30000
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.data.synthetic import make_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serve import FaultInjector, Request, Scheduler, build_engine
from repro.serve.request import latency_percentiles


def _zipf_probs(n: int, alpha: float) -> np.ndarray:
    """Bounded Zipf pmf over ranks 1..n: p(k) proportional to
    k^-alpha — the classic shared-prefix popularity skew (a few hot
    system prompts, a long tail of rare ones)."""
    p = np.arange(1, n + 1, dtype=np.float64) ** -float(alpha)
    return p / p.sum()


def poisson_requests(n, rate, *, vocab, prompt_lo, prompt_hi, new_lo,
                     new_hi, seed=0, eos_id=-1, priority_frac=0.0,
                     high_deadline_ms=None, low_deadline_ms=None,
                     mem_key=None, mem_shape=None, timeout_ms=None,
                     prefix_pools=0, prefix_len=0, zipf_alpha=1.1):
    """Synthetic Poisson trace: exponential inter-arrival gaps at
    `rate` req/s, ragged prompt lengths and per-request max_new drawn
    uniformly, one RNG seed per request. A `priority_frac` fraction of
    requests is drawn as the HIGH class (priority 1, deadline
    high_deadline_ms — the latency-sensitive traffic the priority/edf
    admission policies protect); the rest is priority 0 with
    low_deadline_ms (None = no deadline). For cross-memory families
    pass mem_key/mem_shape (Engine.mem_key / Engine.mem_shape): each
    request then carries its own random memory of RAGGED length (half
    to full slab) — the per-lane cross-memory path under load.

    Shared-prefix pools (prefix_pools > 0, docs/serving.md §Prefix
    cache): `prefix_pools` fixed system prompts of `prefix_len` tokens
    (default prompt_hi) are sampled per request with Zipf(zipf_alpha)
    popularity and CONCATENATED before its ragged user turn — the
    workload class where prefix KV reuse pays: every repeat of a pool
    can skip its prefill on a warm cache."""
    rng = np.random.RandomState(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    pools, pool_p = None, None
    if prefix_pools > 0:
        # pools come from their own RNG stream so the SAME pool token
        # content is reproduced independent of n/rate/class draws
        prng = np.random.RandomState(seed + 104729)
        plen = prefix_len if prefix_len > 0 else prompt_hi
        pools = [prng.randint(0, vocab, size=plen).astype(np.int32)
                 for _ in range(prefix_pools)]
        pool_p = _zipf_probs(prefix_pools, zipf_alpha)
    reqs = []
    for i in range(n):
        L = int(rng.randint(prompt_lo, prompt_hi + 1))
        high = bool(rng.rand() < priority_frac)
        extra = None
        if mem_key is not None:
            S, feat = mem_shape
            S_i = int(rng.randint(max(S // 2, 1), S + 1))
            extra = {mem_key: rng.randn(S_i, feat).astype(np.float32) * 0.1}
        prompt = rng.randint(0, vocab, size=L).astype(np.int32)
        if pools is not None:
            pid = int(rng.choice(len(pools), p=pool_p))
            prompt = np.concatenate([pools[pid], prompt])
        reqs.append(Request(
            rid=i, prompt=prompt,
            max_new=int(rng.randint(new_lo, new_hi + 1)), seed=i,
            eos_id=eos_id, arrival=float(arrivals[i]),
            priority=1 if high else 0,
            deadline_ms=high_deadline_ms if high else low_deadline_ms,
            timeout_ms=timeout_ms, extra_inputs=extra))
    return reqs


def _pct(vals):
    p = latency_percentiles(vals)
    if p is None:
        return "n/a"
    return f"p50 {p['p50'] * 1e3:.1f}ms p95 {p['p95'] * 1e3:.1f}ms"


def _run_stream(cfg, params, gates, args):
    eng = build_engine(cfg, params, gates, budget=args.budget,
                       policy=args.policy, attn_impl=args.attn_impl,
                       prefill_chunk=args.prefill_chunk,
                       decode_segment=args.decode_segment,
                       sched_policy=args.sched_policy,
                       prefill_budget=args.prefill_budget,
                       interleaved=args.interleaved,
                       shed_policy=args.shed_policy,
                       checkpoint_every=args.checkpoint_every,
                       snapshot_dir=args.snapshot_dir,
                       snapshot_host_bytes=args.snapshot_host_bytes,
                       prefix_cache_bytes=args.prefix_cache_bytes,
                       prefix_ttl_sec=args.prefix_ttl_sec,
                       prefix_min_tokens=args.prefix_min_tokens,
                       spec_k=args.spec_k)
    reqs = poisson_requests(
        args.requests, args.rate, vocab=cfg.vocab_size,
        prompt_lo=max(args.prompt_len // 4, 4), prompt_hi=args.prompt_len,
        new_lo=max(args.max_new // 4, 1), new_hi=args.max_new,
        seed=args.seed, priority_frac=args.priority_frac,
        high_deadline_ms=args.deadline_ms,
        mem_key=eng.mem_key, mem_shape=eng.mem_shape,
        timeout_ms=args.timeout_ms, prefix_pools=args.prefix_pools,
        prefix_len=args.prefix_len, zipf_alpha=args.zipf_alpha)

    def make_injector():
        if not args.inject_faults:
            return None
        return FaultInjector(seed=args.fault_seed,
                             corrupt_prob=args.corrupt_prob,
                             delay_prob=args.delay_prob,
                             delay_sec=args.delay_sec,
                             burst_prob=args.burst_prob,
                             snap_corrupt_prob=args.snap_corrupt_prob,
                             io_error_prob=args.io_error_prob)

    # warm-up drain on a throwaway scheduler: compiles every admission/
    # segment shape (closures are cached on the engine), so the printed
    # latencies measure serving, not XLA compilation. Fault injection
    # rides the warm-up too (same seed) so the scrub/resume closures
    # compile before the measured run.
    Scheduler(eng, n_lanes=args.lanes,
              injector=make_injector()).run(reqs)
    sched = Scheduler(eng, n_lanes=args.lanes, injector=make_injector())
    eng.dispatch_count = 0           # count the measured run only
    results = sched.run(reqs, respect_arrivals=True)
    lats = [results[r.rid].latency_sec for r in reqs
            if results[r.rid].latency_sec is not None]
    total_tok = sum(len(results[r.rid].tokens) for r in reqs)
    wall = max(rs.finish_sec or 0.0 for rs in results.values())
    st = sched.stats()
    print(f"stream: {args.requests} requests over {args.lanes} lanes "
          f"(policy={args.policy} budget={args.budget} "
          f"segment={args.decode_segment} sched={args.sched_policy} "
          f"{'interleaved' if sched.interleaved else 'phased'})")
    print(f"  dispatches={eng.dispatch_count} "
          f"(prefill rounds={sched.n_prefill_rounds}, "
          f"segments={sched.n_segments}, resets={sched.n_resets}, "
          f"preempted={sched.n_preempted}) — O(segments), never O(tokens)")
    # supervision counters (docs/serving.md §Fault tolerance): swaps/
    # resumes are the snapshot preemption path; the rest only move
    # under faults or overload — degradation is observable, not silent
    print(f"  supervision: swaps={st['n_swaps']} "
          f"resumes={st['n_resumes']} retries={st['n_retries']} "
          f"quarantined={st['n_quarantined']} shed={st['n_shed']} "
          f"timeouts={st['n_timeouts']} failed={st['n_failed']} "
          f"faults_injected={st['n_faults_injected']}")
    # snapshot store tiers (docs/serving.md §Snapshot store): hit/spill
    # traffic plus the degradation counters — detected corruption, IO
    # errors and capacity drops must be visible, never silent
    print(f"  store: puts={st['store_puts']} "
          f"ram_hits={st['store_ram_hits']} "
          f"disk_hits={st['store_disk_hits']} "
          f"spills={st['store_spills']} "
          f"evictions={st['store_evictions']} "
          f"dropped={st['store_dropped']} "
          f"corrupt_detected={st['store_corrupt_detected']} "
          f"write_errors={st['store_write_errors']} "
          f"io_errors={st['store_io_errors']} "
          f"snapshot_lost={st['n_snapshot_lost']} "
          f"recovered_sessions={st['n_recovered_sessions']}")
    if eng.prefix_cache is not None and sched._pc is not None:
        # prefix cache (docs/serving.md §Prefix cache): the trie lives
        # on the engine, so the warm-up drain above pre-populates it —
        # the measured counters below show WARM-cache behavior
        probes = st["n_prefix_hits"] + st["n_prefix_misses"]
        rate = st["n_prefix_hits"] / max(probes, 1)
        print(f"  prefix: hits={st['n_prefix_hits']} "
              f"misses={st['n_prefix_misses']} "
              f"hit_rate={rate:.2f} "
              f"reused_tokens={st['n_prefix_reused_tokens']} "
              f"installs={st['n_prefix_installs']} "
              f"extracts={st['n_prefix_extracts']} "
              f"inserts={st['prefix_inserts']} "
              f"evictions={st['prefix_evictions']} "
              f"entries={st['prefix_entries']} "
              f"bytes={st['prefix_bytes']}")
    if sched.spec_k > 0:
        # speculative decoding (docs/serving.md §Speculative decoding):
        # mean acceptance length is committed tokens per live verify
        # round — > 1 means speculation is paying for its drafts
        acc = st["n_spec_tokens"] / max(st["n_spec_rounds"], 1)
        print(f"  speculative: spec_k={sched.spec_k} "
              f"verify_rounds={st['n_verify_rounds']} "
              f"spec_rounds={st['n_spec_rounds']} "
              f"spec_tokens={st['n_spec_tokens']} "
              f"mean_acceptance={acc:.2f}")
    if args.inject_faults:
        from repro.serve.request import TERMINAL_STATUSES
        n_terminal = sum(rs.status in TERMINAL_STATUSES
                         for rs in results.values())
        print(f"  chaos: {len(results)} submitted (bursts included), "
              f"{n_terminal} terminal — liveness "
              f"{'OK' if n_terminal == len(results) else 'VIOLATED'}")
    print(f"  {total_tok} tokens in {wall:.2f}s "
          f"= {total_tok / max(wall, 1e-9):.1f} tok/s; latency "
          f"mean {np.mean(lats):.2f}s p95 {np.percentile(lats, 95):.2f}s")
    # per-priority-class SLO stats: TTFT (submit -> first token) and
    # TPOT (per-token after the first) tails — the numbers priority/edf
    # admission exists to protect for the high class
    for prio in sorted({r.priority for r in reqs}, reverse=True):
        states = [results[r.rid] for r in reqs if r.priority == prio]
        missed = [rs for rs in states if rs.missed_deadline]
        print(f"  priority {prio} ({len(states)} reqs): "
              f"ttft {_pct([rs.ttft_sec for rs in states])}, "
              f"tpot {_pct([rs.tpot_sec for rs in states])}, "
              f"deadline misses {len(missed)}")
    for r in reqs[: min(4, len(reqs))]:
        rs = results[r.rid]
        lat = (f"{rs.latency_sec:.2f}s" if rs.latency_sec is not None
               else rs.status.value)
        print(f"  req {r.rid}: prompt {r.prompt_len} -> "
              f"{len(rs.tokens)} tokens, latency {lat}, "
              f"ids {rs.ids[:8]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="trimkv-paper-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--policy", default="trimkv")
    ap.add_argument("--budget", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--chunked", action="store_true")
    ap.add_argument("--prefill-chunk", type=int, default=2048,
                    help="chunk width for --chunked prefill (the fused "
                         "scan pads the tail chunk to this width)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--attn-impl", choices=("xla", "pallas"),
                    default="xla",
                    help="serving attention: XLA einsum path or the "
                         "Pallas flash kernels (interpret mode off-TPU)")
    ap.add_argument("--eager", action="store_true",
                    help="per-token Python decode loop instead of the "
                         "fused lax.scan program")
    # --- continuous batching (--stream) ---
    ap.add_argument("--stream", action="store_true",
                    help="serve a synthetic Poisson request stream with "
                         "ragged prompts through the lane scheduler "
                         "instead of one lock-step batch")
    ap.add_argument("--requests", type=int, default=12,
                    help="--stream: number of requests in the trace")
    ap.add_argument("--lanes", type=int, default=4,
                    help="--stream: fixed scheduler lanes (B)")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="--stream: Poisson arrival rate (req/s)")
    ap.add_argument("--decode-segment", type=int, default=16,
                    help="--stream: fused decode steps per scheduler "
                         "segment")
    # --- SLO-aware scheduling (PR 4, docs/serving.md §Scheduling) ---
    ap.add_argument("--sched-policy", choices=("fifo", "priority", "edf"),
                    default="fifo",
                    help="--stream: admission order over the waiting "
                         "queue (priority/edf may also preempt)")
    ap.add_argument("--interleaved", action="store_true",
                    help="--stream: thread admission prefill chunks "
                         "INSIDE decode segments (T.mixed_step_loop) "
                         "instead of phased whole-prompt admission")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="--stream: max prompt tokens prefilled per "
                         "interleaved segment (0 = unlimited)")
    ap.add_argument("--priority-frac", type=float, default=0.25,
                    help="--stream: fraction of requests in the high "
                         "priority class (priority 1 + deadline)")
    ap.add_argument("--deadline-ms", type=float, default=500.0,
                    help="--stream: latency SLO for the high class")
    # --- fault tolerance (PR 6, docs/serving.md §Fault tolerance) ---
    ap.add_argument("--inject-faults", action="store_true",
                    help="--stream: attach a seeded FaultInjector "
                         "(NaN poison / delays / traffic bursts) and "
                         "report the liveness verdict")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="--inject-faults: injector RNG seed")
    ap.add_argument("--corrupt-prob", type=float, default=0.25,
                    help="--inject-faults: per-step probability of "
                         "NaN-poisoning one decoding lane's KV cache")
    ap.add_argument("--delay-prob", type=float, default=0.0,
                    help="--inject-faults: per-step probability of a "
                         "host-side dispatch delay")
    ap.add_argument("--delay-sec", type=float, default=0.05,
                    help="--inject-faults: length of an injected delay")
    ap.add_argument("--burst-prob", type=float, default=0.1,
                    help="--inject-faults: per-step probability of "
                         "burst-submitting hostile traffic")
    ap.add_argument("--timeout-ms", type=float, default=None,
                    help="--stream: per-request wall-clock timeout "
                         "(cancelled with TIMED_OUT beyond it)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="--stream: snapshot decoding lanes every N "
                         "segments (0 = off) so fault replay resumes "
                         "from the last checkpoint")
    ap.add_argument("--shed-policy", choices=("reject", "evict"),
                    default="reject",
                    help="--stream: overload response when max_queue "
                         "requests wait (reject newcomer, or evict the "
                         "worst queued request if outranked)")
    # --- tiered snapshot store (PR 7, docs/serving.md §Snapshot store) -
    ap.add_argument("--snapshot-dir", default=None,
                    help="--stream: disk tier for lane snapshots "
                         "(np.memmap slab files + JSON manifest; parks "
                         "and checkpoints write through, and a restart "
                         "over the same dir recovers parked sessions)")
    ap.add_argument("--snapshot-host-bytes", type=int, default=0,
                    help="--stream: host-RAM budget of the snapshot "
                         "LRU pool in bytes (0 = unlimited); over "
                         "budget, cold snapshots spill to "
                         "--snapshot-dir or are dropped with a counter")
    ap.add_argument("--snap-corrupt-prob", type=float, default=0.0,
                    help="--inject-faults: per-step probability of "
                         "flipping one bit in a stored snapshot slab "
                         "(RAM or at-rest disk file) — finite silent "
                         "corruption only the checksum can catch")
    ap.add_argument("--io-error-prob", type=float, default=0.0,
                    help="--inject-faults: per-step probability of "
                         "arming a snapshot-store disk fault (write "
                         "failure or silent truncation)")
    # --- prefix KV cache (PR 8, docs/serving.md §Prefix cache) ---
    ap.add_argument("--prefix-cache-bytes", type=int, default=0,
                    help="--stream: byte budget of the radix-trie "
                         "prefix KV cache (0 = off); admission reuses "
                         "the longest cached chunk-aligned prompt "
                         "prefix and prefills only the novel suffix")
    ap.add_argument("--prefix-ttl-sec", type=float, default=0.0,
                    help="--stream: expire unpinned prefix-cache "
                         "entries untouched this long (0 = no TTL)")
    ap.add_argument("--prefix-min-tokens", type=int, default=0,
                    help="--stream: do not capture shared prefixes "
                         "shorter than this many tokens")
    ap.add_argument("--prefix-pools", type=int, default=0,
                    help="--stream: number of shared system prompts "
                         "(Zipf-sampled, concatenated before each "
                         "ragged user turn; 0 = fully random prompts)")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="--prefix-pools: tokens per shared system "
                         "prompt (0 = --prompt-len)")
    ap.add_argument("--zipf-alpha", type=float, default=1.1,
                    help="--prefix-pools: Zipf popularity exponent of "
                         "the pool draw (higher = hotter head)")
    # --- speculative decoding (PR 9, docs/serving.md §Speculative
    # decoding) ---
    ap.add_argument("--spec-k", type=int, default=0,
                    help="--stream: drafted tokens per verify round "
                         "(0 = off). Greedy-only n-gram self-drafting "
                         "from each lane's token history; all spec_k+1 "
                         "positions verify in one chunk-shaped "
                         "dispatch, outputs stay token-identical")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    key = jax.random.PRNGKey(args.seed)
    kp, kg = jax.random.split(key)
    params = T.init_params(kp, cfg)
    gates = T.init_gate_params(kg, cfg)
    if args.stream:
        _run_stream(cfg, params, gates, args)
        return
    eng = build_engine(cfg, params, gates, budget=args.budget,
                       policy=args.policy, attn_impl=args.attn_impl,
                       prefill_chunk=args.prefill_chunk,
                       fused=not args.eager)
    tokens, _, _ = make_batch("copy", args.seed, args.batch,
                              args.prompt_len, cfg.vocab_size)
    extra = {}
    if cfg.family == "vlm":
        extra["vision_embeds"] = jax.numpy.zeros(
            (args.batch, cfg.num_image_tokens, cfg.vision_dim))
    if cfg.family == "encdec":
        extra["source_embeds"] = jax.numpy.zeros(
            (args.batch, cfg.source_len, cfg.d_model))
    out = eng.generate(tokens, args.max_new,
                       extra_inputs=extra or None, chunked=args.chunked)
    print(f"policy={args.policy} budget={args.budget} "
          f"decode {out['tok_per_sec']:.1f} tok/s "
          f"({out['decode_sec']:.2f}s for {args.max_new} steps)")
    print("first row ids:", out["ids"][0][:16])


if __name__ == "__main__":
    main()
