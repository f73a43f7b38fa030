"""Lane-based continuous batching over the fused serving loops, with
SLO-aware admission (PR 4) and fault-tolerant supervision (PR 6).

The `Scheduler` owns B fixed LANES (the batch dim of one shared decode
state). Each lane holds at most one in-flight request; the scheduler

  1. ADMITS queued requests into free lanes in `sched_policy` order
     (fifo | priority | edf). Phased mode packs their ragged prompts
     into ONE padded chunk grid and prefills them with a single
     T.prefill_chunk_loop dispatch before decoding resumes; INTERLEAVED
     mode (ServeConfig.interleaved / Scheduler(interleaved=True))
     instead threads one prompt chunk per admitting lane into every
     step of the next decode segments (T.mixed_step_loop), bounded by
     `prefill_budget` tokens per segment — so a long prompt never
     stalls in-flight decodes and admission costs ZERO extra
     dispatches. Requests holding a LaneSnapshot (swapped-out preemption
     victims, parked sessions, fault replays) are RESUMED instead:
     one dispatch scatters their host snapshots back into lanes,
     bit-identical to never having left the device;
  2. runs bounded fused DECODE SEGMENTS (T.decode_segment_loop, or
     T.mixed_step_loop while any lane is still prefilling:
     serve_cfg.decode_segment steps under one lax.scan, per-lane active
     masks / clocks / RNG chains / max_new / eos). Remainder segments
     (the pure-decode half of a drain-split) are rounded up to
     POWER-OF-TWO buckets with the tail masked (traced n_real), so
     cold-start compiles scale with log2(decode_segment) buckets, not
     with every distinct remainder length;
  3. RETIRES lanes whose request emitted its eos_id or max_new-th token
     at the segment boundary (T.reset_lanes — in the slot-dense layout
     a lane reset is pos := -1, no paged block tables) and immediately
     refills them from the queue. Under priority/edf it may also
     PREEMPT the worst running lane (lowest priority / latest deadline)
     when a strictly better-ranked request waits with no free lane:
     with serve_cfg.swap_preempt (default) a decoding victim is
     SWAPPED OUT — T.extract_lanes gathers its retained slab (O(M),
     eviction already compressed the lane) into a host LaneSnapshot,
     and re-admission resumes it with its emitted tokens intact;
     mid-prefill victims (and swap_preempt=False) restart from scratch
     (recompute-style), so either way the final output stays
     token-identical to an uninterrupted run;
  4. SUPERVISES every dispatch: the segment programs carry an
     in-program per-lane health flag (`ok` — non-finite logits on any
     step the lane was live), and a flagged lane is QUARANTINED at the
     segment boundary: its emissions are discarded, its state scrubbed
     (T.scrub_lanes: reset + K/V payload zeroed, so NaN bytes cannot
     leak through the masked p@v product), and its request replayed
     from its last snapshot (or from scratch) up to
     serve_cfg.max_retries times before a terminal FAILED. Per-request
     wall-clock timeouts (Request.timeout_ms) cancel stuck requests
     (TIMED_OUT), and queue overload is shed per serve_cfg.shed_policy
     instead of growing without bound. Every submitted request reaches
     EXACTLY ONE terminal status (DONE | FAILED | TIMED_OUT |
     REJECTED) — the liveness oracle tests/test_faults.py asserts
     under seeded fault injection (serve.faults.FaultInjector).

Dispatch accounting: every device program this scheduler launches bumps
the owning Engine's `dispatch_count`, and the total is
n_prefill_rounds + n_segments + n_resets + n_swaps + n_resumes
+ n_prefix_installs + n_prefix_extracts (+ n_faults_injected under
fault injection) — O(prefill rounds + segments + preemptions +
prefix-cache traffic), NEVER O(tokens) or O(requests); interleaved
mode keeps n_prefill_rounds at 0 because admission rides inside the
segments (tests/test_scheduler.py asserts the exact formula under churn
and mixed traffic).

Prefix KV cache (PR 8, serve.prefix_cache, docs/serving.md §Prefix
cache): when serve_cfg.prefix_cache_bytes > 0 (self-attention families
only — cross-memory slabs cannot ride a cached prefix), admission walks
the engine's radix trie for the longest cached chunk-aligned prefix of
each fresh prompt. A HIT scatters the cached retained slab into the
free lane and prefills only the novel suffix (phased: the slab rides
into the admission dispatch as the lane's initial sub-state, zero extra
dispatches; interleaved: one n_prefix_installs dispatch per admission
round with hits, then the suffix chunks stream through the mixed
segments as usual). CAPTURE is traffic-aware: the trie's observe()
window picks the deepest chunk boundary the prompt shares with recent
traffic, and the post-prefill slab at that boundary is inserted (phased:
snapshotted INSIDE the admission scan via the capture_chunk carry;
interleaved: the schedule stops at the boundary and one batched
n_prefix_extracts dispatch gathers it). Hits pin their entry until the
request leaves its lane, so LRU/TTL eviction can never tear a slab out
from under a live lane.

Cross-memory families (vlm / encdec, PR 5): each request carries its
own vision/encoder memory in `Request.extra_inputs` (ragged lengths).
Admission packs an admission round's memories into ONE padded
[B, S, feat] slab + per-lane mem_len and installs it with the prompt
prefill (phased: inside the same admission dispatch; interleaved:
inside the segment program — still zero dedicated dispatches), and
lane retirement invalidates it (T.reset_lanes: mem_len := 0), so a
recycled lane can never attend a previous occupant's memory. The
memory slab + mem_len ride in every LaneSnapshot, so swapped-out cross
requests resume without re-encoding.

Correctness contract: each request's output is token-identical to a
one-shot `Engine.generate(prompt[None], max_new, chunked=True,
seed=seed)` (truncated at its eos; cross families with the request's
own unpadded memory), for every eviction policy, both attention
impls, both admission modes, any admission order and under preemption
— lanes are frozen bit-identically while inactive, each lane's RNG
chain is seeded from its request alone, snapshots gather/scatter exact
bytes, and both the ragged phased prefill and the per-lane interleaved
chunk schedule replay the exact chunk sequence one-shot chunked
prefill runs.

`continuous=False` degrades the SAME machinery to static batching
(admission waits until every lane is free, finished lanes idle until
the whole wave drains) — the baseline the serving benchmark
(benchmarks/table7_serving.py) compares goodput against.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.serve.engine import Engine
from repro.serve.request import (LaneSnapshot, Request, RequestState,
                                 Status)
from repro.serve.store import SnapshotStore, state_spec

SCHED_POLICIES = ("fifo", "priority", "edf")
SHED_POLICIES = ("reject", "evict")


def _span(name: str):
    """Decorator: the method's calls become `name` spans in a
    jax.profiler trace (docs/serving.md §Tracing); nothing is recorded
    while no profiler runs."""
    return functools.partial(jax.profiler.annotate_function, name=name)


def _chunk_prompt(prompt: np.ndarray, C: int):
    """One prompt -> its padded chunk sequence, EXACTLY as one-shot
    chunked prefill chunks it: full C-token chunks, then the
    zero-padded tail. Returns (chunks [n_chunks, C] int32,
    n_valid [n_chunks] int32). Both admission paths chunk through
    here, so the interleaved per-lane schedule and the phased ragged
    grid replay the same chunk sequence by construction."""
    n_chunks = -(-prompt.size // C)
    grid = np.zeros((n_chunks * C,), np.int32)
    grid[: prompt.size] = prompt
    n_valid = np.clip(prompt.size - np.arange(n_chunks) * C,
                      0, C).astype(np.int32)
    return grid.reshape(n_chunks, C), n_valid


def _prng_keys(seeds) -> np.ndarray:
    """[k,2] uint32 threefry keys, one per request seed — the same
    layout jax.random.PRNGKey produces ([seed >> 32, seed & 0xffffffff];
    asserted in tests), built host-side so admission costs no extra
    device dispatches. Each lane's chain therefore reproduces a B=1
    Engine.generate(seed=seed) stream exactly."""
    arr = np.empty((len(seeds), 2), np.uint32)
    for i, s in enumerate(seeds):
        arr[i, 0] = (int(s) >> 32) & 0xFFFFFFFF
        arr[i, 1] = int(s) & 0xFFFFFFFF
    return arr


def _snap_row(sub, i: int) -> dict:
    """Slice row i out of a host-side batch-k sub-state, KEEPING a
    k=1 lane dim so snapshots re-stack with plain concatenate."""
    row = {"t": sub["t"][i:i + 1]}
    if sub["layers"] is not None:
        row["layers"] = jax.tree.map(lambda a: a[:, i:i + 1],
                                     sub["layers"])
    else:
        row["layers"] = None
    row["tail"] = jax.tree.map(lambda a: a[i:i + 1], sub["tail"])
    return row


def _stack_rows(rows: List[dict], n: int) -> dict:
    """Stack k single-lane snapshot states into an n-row sub-state
    (pad rows repeat row 0; the install mask drops them, so their
    bytes never land — see Engine's resume closure)."""
    rows = rows + [rows[0]] * (n - len(rows))
    sub = {"t": np.concatenate([r["t"] for r in rows])}
    if rows[0]["layers"] is not None:
        sub["layers"] = jax.tree.map(
            lambda *xs: np.concatenate(xs, axis=1),
            *[r["layers"] for r in rows])
    else:
        sub["layers"] = None
    sub["tail"] = jax.tree.map(lambda *xs: np.concatenate(xs, axis=0),
                               *[r["tail"] for r in rows])
    return sub


def _stack_lane_rows(rows: Dict[int, dict], n: int) -> dict:
    """Stack single-lane snapshot states LANE-ALIGNED into an n-row
    sub-state: row `lane` holds rows[lane]; lanes without an entry
    repeat an arbitrary real row as filler (the [B]-mask where-select
    install drops them — lane-aligned rows + mask install is what keeps
    admission/resume shard-local under a mesh; see Engine's
    lane_closures)."""
    filler = next(iter(rows.values()))
    return _stack_rows([rows.get(l, filler) for l in range(n)], n)


@dataclasses.dataclass
class _LanePrefill:
    """Host-side progress of one interleaved admission prefill: the
    request's prompt chunked exactly as one-shot chunked prefill chunks
    it ([n_chunks, C] full chunks then the padded tail), fed one chunk
    per segment step until done. On a prefix-cache hit the grid holds
    only the NOVEL SUFFIX chunks (the cached slab was installed before
    the first segment). While capture_key is set, chunks at/after
    capture_at stay OFF the schedule until the boundary slab has been
    extracted into the trie (then capture_key clears and the suffix
    resumes) — so the captured state is exactly the prefix state."""
    chunks: np.ndarray                 # [n_chunks, C] int32
    n_valid: np.ndarray                # [n_chunks] int32 (C ... tail)
    next_chunk: int = 0
    capture_at: int = 0                # grid-relative capture boundary
    capture_key: Optional[np.ndarray] = None   # prompt[:cap_tokens]

    @property
    def n_chunks(self) -> int:
        return int(self.chunks.shape[0])

    @property
    def done(self) -> bool:
        return self.next_chunk >= self.n_chunks


class Scheduler:
    def __init__(self, engine: Engine, n_lanes: int, *, greedy: bool = True,
                 continuous: bool = True,
                 interleaved: Optional[bool] = None,
                 injector=None):
        self.eng = engine
        self.cfg, self.serve = engine.cfg, engine.serve
        self.policy = engine.policy
        self.n_lanes = n_lanes
        self.continuous = continuous
        self.interleaved = (self.serve.interleaved if interleaved is None
                            else interleaved)
        self.sched_policy = self.serve.sched_policy
        if self.sched_policy not in SCHED_POLICIES:
            raise ValueError(f"unknown sched_policy "
                             f"{self.sched_policy!r}; "
                             f"expected one of {SCHED_POLICIES}")
        if self.serve.shed_policy not in SHED_POLICIES:
            raise ValueError(f"unknown shed_policy "
                             f"{self.serve.shed_policy!r}; "
                             f"expected one of {SHED_POLICIES}")
        self.greedy = greedy or self.serve.temperature == 0.0
        # chaos adversary (serve.faults.FaultInjector) — None in
        # production; when set, step() gives it first crack at the
        # scheduler (poison / delay / burst), and the supervision
        # machinery below is what keeps every request terminating
        self.injector = injector
        # cross-memory families (vlm/encdec): per-request encoder/vision
        # memory is a first-class per-lane resource — admission packs
        # ragged memories into one padded [B, S, feat] slab with
        # per-lane mem_len, the closures install it alongside the
        # prompt prefill, and reset_lanes invalidates it (mem_len := 0)
        self.mem_key = engine.mem_key
        self.mem_shape = engine.mem_shape
        # jitted closures live on the Engine (cached per greedy flag) so
        # successive schedulers — e.g. benchmark warm-up then measured
        # run — share one set of compilations
        closures = engine.lane_closures(self.greedy, n_lanes)
        self._admit_fn = closures["admit"]
        self._segment = closures["segment"]
        self._mixed = closures["mixed"]
        self._mixed_nomem = closures["mixed_nomem"]
        self._reset = closures["reset"]
        self._extract = closures["extract"]
        self._resume = closures["resume"]
        self._scrub = closures["scrub"]
        self._admit_prefix_fn = closures["admit_prefix"]
        self._admit_capture_fn = closures["admit_capture"]
        self._prefix_install = closures["prefix_install"]
        self._spec_segment_fn = closures["spec_segment"]
        self._spec_mixed = closures["spec_mixed"]
        self._spec_mixed_nomem = closures["spec_mixed_nomem"]
        # speculative decoding (PR 9, docs/serving.md §Speculative
        # decoding): GREEDY-ONLY — the engine builds the spec closures
        # only for the greedy flag, so under temperature sampling
        # spec_k silently degrades to 0 (the classic per-token path)
        self.spec_k = (self.serve.spec_k
                       if self._spec_segment_fn is not None else 0)
        # prefix KV cache: the trie lives on the ENGINE (shared across
        # schedulers, like the compilation cache); cross-memory families
        # bypass it — a cached slab cannot carry the encoder/vision
        # memory its suffix would cross-attend into
        self._pc = (engine.prefix_cache if self.mem_key is None
                    else None)

        # device lane state
        self.state = engine.fresh_state(n_lanes)
        self.tok = jnp.zeros((n_lanes,), jnp.int32)
        self.keys = jnp.zeros((n_lanes, 2), jnp.uint32)
        # per-lane drafter history (speculative decoding): the tokens
        # the model consumed BEFORE the lane's carry token (prompt +
        # emitted), -1 padded left, most recent last — seeded host-side
        # at admission/resume (_seed_hist), then carried through the
        # spec segment dispatches
        self.hist = np.full((n_lanes, T.SPEC_HISTORY), -1, np.int32)
        # host lane bookkeeping (tiny [B] arrays, re-uploaded per call)
        self.active = np.zeros(n_lanes, bool)
        self.n_emitted = np.zeros(n_lanes, np.int32)
        self.max_new = np.ones(n_lanes, np.int32)
        self.eos = np.full(n_lanes, -1, np.int32)
        self.lane_req: List[Optional[RequestState]] = [None] * n_lanes
        # interleaved admission: per-lane prompt chunk progress (None =
        # lane is free or already decoding)
        self.lane_prefill: List[Optional[_LanePrefill]] = [None] * n_lanes
        self.queue: List[RequestState] = []
        self._submit_seq = 0
        self.results: Dict[int, RequestState] = {}
        # dispatch accounting (engine.dispatch_count gets every launch):
        # total launches == n_prefill_rounds + n_segments + n_resets
        # + n_swaps + n_resumes + n_prefix_installs + n_prefix_extracts
        # (+ n_faults_injected when an injector poisons lanes) —
        # O(prefills + segments + preemptions + prefix traffic),
        # asserted by tests/test_scheduler.py and tests/test_faults.py;
        # interleaved admission keeps n_prefill_rounds at 0
        self.n_prefill_rounds = 0
        self.n_segments = 0
        self.n_resets = 0
        self.n_preempted = 0
        # fault-tolerance counters (surfaced by stats() and the stream
        # launcher so degradation is observable, not silent)
        self.n_swaps = 0          # extract dispatches (swap-out,
        #                           checkpoint, park)
        self.n_resumes = 0        # resume dispatches (snapshot scatter)
        self.n_shed = 0           # requests refused/evicted on overload
        self.n_quarantined = 0    # lanes scrubbed after non-finite
        #                           outputs
        self.n_timeouts = 0       # requests cancelled by timeout_ms
        self.n_failed = 0         # terminal FAILED after max_retries
        self.n_faults_injected = 0  # injector poison dispatches
        self.n_snapshot_lost = 0  # snapshots that failed checksum/IO at
        #                           resume and fell back to
        #                           recompute-from-prompt (bounded replay)
        # prefix-cache counters: admission-time trie traffic (hits /
        # misses / prompt tokens NOT re-prefilled because a cached slab
        # covered them) and the two interleaved-only dispatch kinds —
        # slab installs (hits) and boundary extracts (captures); the
        # phased admission dispatch absorbs both at zero extra cost
        self.n_prefix_hits = 0
        self.n_prefix_misses = 0
        self.n_prefix_reused_tokens = 0
        self.n_prefix_installs = 0
        self.n_prefix_extracts = 0
        # interleaved segments whose prefill drained mid-segment and
        # were split into a mixed part + a pure-decode remainder (each
        # half is its own dispatch and counts in n_segments)
        self.n_segment_splits = 0
        # device work of the segment dispatches (docs/serving.md
        # §Tracing): scan steps the pure-decode programs ran (the
        # power-of-two bucket, masked tail included), mixed steps, and
        # the prompt rows their chunk sub-step ran (C per (step, lane)
        # holding a chunk; drain x lanes x C where lanes are sharded and
        # the whole grid runs) against the rows that held prompt tokens
        self.n_decode_steps = 0
        self.n_mixed_steps = 0
        self.n_chunk_rows = 0
        self.n_chunk_rows_valid = 0
        # speculative-decode counters: verify rounds dispatched
        # (logical, drain-split aware — when spec is on,
        # n_verify_rounds == decode_segment * (n_segments -
        # n_segment_splits) exactly, asserted under churn/faults by
        # tests/test_speculative.py and tests/test_faults.py) and the
        # acceptance totals (n_spec_tokens / n_spec_rounds = fleet mean
        # acceptance length)
        self.n_verify_rounds = 0
        self.n_spec_tokens = 0
        self.n_spec_rounds = 0
        # distinct STATIC scan lengths the pure-decode closure was
        # dispatched with — power-of-two buckets (plus decode_segment
        # itself), so its size is O(log2 decode_segment), asserted in
        # tests/test_faults.py
        self.decode_bucket_lengths = set()
        # same for the phased admission grid's chunk axis: suffix-only
        # prefill diversifies grid lengths, so _pack_prompts rounds
        # n_chunks up to power-of-two buckets (all-zero-valid tail
        # chunks freeze every row) — O(log2 max_prompt_chunks)
        # admission-closure shapes instead of one per suffix length
        self.prefill_bucket_lengths = set()
        # global decode-step clock: total scan steps run so far, the
        # basis of the deterministic RequestState.first_emit_step
        self._steps_done = 0
        self._t0 = time.monotonic()
        # tiered snapshot store (PR 7, serve.store): owns every
        # LaneSnapshot — LRU host pool accounted against
        # serve.snapshot_host_bytes, spilling to np.memmap slabs under
        # serve.snapshot_dir; every snapshot checksummed at capture and
        # verified at fetch. The expected single-lane leaf spec
        # (derived WITHOUT allocating, via eval_shape) fences off disk
        # records written under a different model/budget config.
        expected = state_spec(jax.eval_shape(
            lambda: T.init_decode_state(self.cfg, 1, self.serve.budget)))
        self.store = SnapshotStore(
            host_bytes=self.serve.snapshot_host_bytes,
            directory=self.serve.snapshot_dir, expected_spec=expected)
        # crash-restart: adopt the dir's manifest — every durably
        # captured session comes back as a PARKED RequestState whose
        # revive() resumes bit-identically from its on-disk slab
        self.n_recovered_sessions = 0
        self._recover_sessions()

    def _recover_sessions(self) -> None:
        """Replay the snapshot store's manifest (populated when
        serve.snapshot_dir holds a previous process's state): rebuild
        each record's Request + PARKED RequestState with its emitted
        tokens, exactly as if this Scheduler had parked it itself.
        Records without session metadata, or that fail to rebuild, are
        skipped — recovery degrades, never crashes."""
        for record in self.store.recoverable():
            meta = record.get("request")
            rid = record.get("rid")
            if meta is None or rid in self.results:
                continue
            try:
                req = Request.from_meta(meta)
            except (KeyError, TypeError, ValueError):
                continue
            rs = RequestState(request=req, status=Status.PARKED,
                              submit_seq=self._submit_seq,
                              submit_sec=self._now())
            self._submit_seq += 1
            rs.tokens = [int(t) for t in record.get("tokens", [])]
            self.results[rid] = rs
            self.n_recovered_sessions += 1

    # ---------------------------------------------------------- queueing

    def _now(self) -> float:
        return time.monotonic() - self._t0

    def _check_memory(self, request: Request) -> Optional[str]:
        """Cross-memory families: every request must carry its own
        memory (vision embeds / source frames), at most the family's
        slab length. Returns the rejection reason (None = fine) —
        malformed requests become a structured Status.REJECTED at
        submit, never a crash inside a jitted admission program."""
        if self.mem_key is None:
            return None
        S, feat = self.mem_shape
        extra = request.extra_inputs or {}
        mem = extra.get(self.mem_key)
        if mem is None:
            return (f"family {self.cfg.family!r} requires "
                    f"extra_inputs[{self.mem_key!r}]")
        if mem.ndim != 2 or mem.shape[1] != feat:
            return (f"extra_inputs[{self.mem_key!r}] shape "
                    f"{mem.shape} does not match the family slab "
                    f"[{S}, {feat}]")
        if mem.shape[0] > S:
            return (f"extra_inputs[{self.mem_key!r}] length "
                    f"{mem.shape[0]} exceeds the family slab "
                    f"[{S}, {feat}]")
        return None

    def _shed(self, rs: RequestState) -> Optional[str]:
        """Queue overload: serve_cfg.max_queue requests already wait.
        shed_policy "reject" refuses the newcomer; "evict" sheds the
        WORST queued request instead when the newcomer strictly
        outranks it under sched_policy (so an urgent request is never
        locked out by a full queue of stragglers). Returns the
        newcomer's rejection reason, or None if it won a slot."""
        if self.serve.shed_policy == "evict" and self.queue:
            worst = max(self.queue, key=self._order_key)
            if self._order_key(rs) < self._order_key(worst):
                self.queue.remove(worst)
                worst.status = Status.REJECTED
                worst.reason = ("shed under overload for "
                                f"request {rs.rid}")
                worst.finish_sec = self._now()
                self.n_shed += 1
                return None
        self.n_shed += 1
        return f"queue full (max_queue={self.serve.max_queue})"

    def submit(self, request: Request) -> RequestState:
        """Accept a request into the waiting queue. ALWAYS returns its
        RequestState (recorded in `results`) — a malformed request
        (empty prompt, max_new < 1, bad/oversized cross memory) or an
        overloaded queue yields a structured terminal
        Status.REJECTED with `reason` set, never an exception: a bad
        request in a stream cannot crash the serving loop."""
        rs = RequestState(request=request, submit_seq=self._submit_seq,
                          submit_sec=self._now())
        self._submit_seq += 1
        self.results[request.rid] = rs
        reason = request.validation_error() or self._check_memory(request)
        if reason is None and len(self.queue) >= self.serve.max_queue:
            reason = self._shed(rs)
        if reason is not None:
            rs.status, rs.reason = Status.REJECTED, reason
            rs.finish_sec = self._now()
            return rs
        self.queue.append(rs)
        return rs

    def _order_key(self, rs: RequestState):
        """Admission order under sched_policy — smaller = served first.
        fifo: submit order. priority: highest Request.priority, ties
        FIFO. edf: earliest absolute deadline (submit + deadline_ms;
        no deadline = inf, sorts last), ties FIFO."""
        if self.sched_policy == "priority":
            return (-rs.request.priority, rs.submit_seq)
        if self.sched_policy == "edf":
            return (rs.deadline_sec, rs.submit_seq)
        return (rs.submit_seq,)

    def _pop_next(self) -> RequestState:
        rs = min(self.queue, key=self._order_key)
        self.queue.remove(rs)
        return rs

    @property
    def n_running(self) -> int:
        return sum(rs is not None for rs in self.lane_req)

    @property
    def idle(self) -> bool:
        return not self.queue and self.n_running == 0

    # ----------------------------------------------- snapshots (swap-out)

    def _swap_out(self, lanes: List[int], kind: str = "swap") -> None:
        """ONE extract dispatch gathers the lanes' complete movable
        state (retained KV slab, positions/betas/aux, recurrences,
        cross-memory slab + mem_len, clock) plus carried token and RNG
        chain into host LaneSnapshots, handed to the SnapshotStore
        (checksummed at capture; durable kinds "park"/"checkpoint"
        write through to the disk tier). O(M) per lane by construction
        — eviction already compressed each lane to its budget — which
        is what makes preemption-by-swap, parking and checkpointing
        affordable. The extract closure commits the FULL lane state
        (identity program — shard-local under a mesh, and the same
        bytes the old padded index-gather moved) and the host slices
        out the victim lanes' rows."""
        self.eng.dispatch_count += 1
        self.n_swaps += 1
        sub, toks, keys = jax.device_get(
            self._extract(self.state, self.tok, self.keys))
        for lane in lanes:
            rs = self.lane_req[lane]
            snap = LaneSnapshot(
                state=_snap_row(sub, lane), tok=toks[lane],
                key=keys[lane],
                n_emitted=int(self.n_emitted[lane]),
                n_tokens=len(rs.tokens))
            self.store.put(rs.rid, snap,
                           request_meta=rs.request.to_meta(),
                           tokens=rs.tokens, kind=kind)

    def _seed_hist(self, lane: int, rs: RequestState) -> None:
        """Seed the lane's drafter history: every token the model has
        consumed before its current carry (prompt + emitted tokens),
        truncated to the SPEC_HISTORY window, left-padded with -1.
        Called at every lane entry point (fresh admission — phased and
        interleaved — and snapshot resume, AFTER the host token stream
        was rolled back to the snapshot point), so the history is always
        reconstructable host-side and never needs to ride snapshots."""
        toks = list(rs.request.prompt) + list(rs.tokens)
        H = self.hist.shape[1]
        row = np.full((H,), -1, np.int32)
        tail = toks[-H:]
        if tail:
            row[H - len(tail):] = tail
        self.hist[lane] = row

    def _resume_lanes(
            self,
            batch: List[Tuple[RequestState, LaneSnapshot, int]]) -> None:
        """ONE resume dispatch scatters k verified host LaneSnapshots
        (fetched from the store by _take_admissions — RAM or disk tier)
        back into lanes — the restored lanes are bit-identical to never
        having left the device, so the request continues its exact
        token stream (parity oracle in tests/test_faults.py). Host-side
        stream/bookkeeping is rolled back to the snapshot point
        (tokens truncated to snapshot.n_tokens — a no-op on a plain
        swap-out, a real rollback on fault replay). Snapshot rows are
        stacked LANE-ALIGNED and installed by a [B] mask, so the resume
        program stays shard-local under a mesh."""
        rows = {lane: snap.state for _, snap, lane in batch}
        sub = _stack_lane_rows(rows, self.n_lanes)
        sub_tok = np.zeros((self.n_lanes,), np.int32)
        sub_keys = np.zeros((self.n_lanes, 2), np.uint32)
        mask = np.zeros(self.n_lanes, bool)
        for rs, snap, lane in batch:
            sub_tok[lane] = snap.tok
            sub_keys[lane] = snap.key
            mask[lane] = True
        self.eng.dispatch_count += 1
        self.n_resumes += 1
        self.state, self.tok, self.keys = self._resume(
            self.state, self.tok, self.keys,
            jax.tree.map(jnp.asarray, sub), jnp.asarray(sub_tok),
            jnp.asarray(sub_keys), jnp.asarray(mask))
        now = self._now()
        for rs, snap, lane in batch:
            rs.status, rs.lane = Status.RUNNING, lane
            if rs.admit_sec is None:
                rs.admit_sec = now
            del rs.tokens[snap.n_tokens:]
            self.lane_req[lane] = rs
            self.lane_prefill[lane] = None
            self.active[lane] = True
            self.n_emitted[lane] = snap.n_emitted
            self.max_new[lane] = rs.request.max_new
            self.eos[lane] = rs.request.eos_id
            if self.spec_k:
                self._seed_hist(lane, rs)

    def park(self, rid: int) -> RequestState:
        """Swap a RUNNING (decoding) request out on purpose: its lane
        is snapshotted and freed, the request held OFF the queue in
        Status.PARKED until revive(). An idle interactive session stops
        occupying a lane at O(M) cost and resumes bit-identically."""
        rs = self.results[rid]
        if rs.status is not Status.RUNNING or rs.lane < 0:
            raise ValueError(f"request {rid} is not running "
                             f"(status={rs.status.value})")
        lane = rs.lane
        if self.lane_prefill[lane] is not None:
            raise ValueError(f"request {rid} is still prefilling; "
                             f"park applies to decoding lanes")
        self._swap_out([lane], kind="park")
        mask = np.zeros(self.n_lanes, bool)
        mask[lane] = True
        self.eng.dispatch_count += 1
        self.n_resets += 1
        self.state = self._reset(self.state, jnp.asarray(mask))
        rs.status, rs.lane = Status.PARKED, -1
        self._release_prefix(rs.rid)
        self.lane_req[lane] = None
        self.active[lane] = False
        return rs

    def revive(self, rid: int) -> RequestState:
        """Re-enqueue a PARKED request; the next admission round
        resumes it from its snapshot (tokens intact)."""
        rs = self.results[rid]
        if rs.status is not Status.PARKED:
            raise ValueError(f"request {rid} is not parked "
                             f"(status={rs.status.value})")
        rs.status = Status.QUEUED
        self.queue.append(rs)
        return rs

    # -------------------------------------------------------- preemption

    def _outranks(self, cand: RequestState, victim: RequestState) -> bool:
        """Strict SLO dominance — the only condition under which a
        waiting request may evict a running one. Strictness (plus FIFO
        never preempting) rules out preemption cycles: a re-queued
        victim can never bounce back into its preemptor's lane."""
        if self.sched_policy == "priority":
            return cand.request.priority > victim.request.priority
        if self.sched_policy == "edf":
            # deadline risk: an earlier-absolute-deadline request is
            # waiting while a later-deadline one holds the lane
            return cand.deadline_sec < victim.deadline_sec
        return False

    def _maybe_preempt(self) -> None:
        """Evict the worst running lane(s) (lowest priority / latest
        deadline) when a strictly better-ranked request waits with no
        free lane. serve_cfg.swap_preempt (default): decoding victims
        are swapped out — one vectorized extract dispatch snapshots
        them, they keep their emitted tokens, and re-admission RESUMES
        them where they stopped instead of recomputing (the O(M)
        footprint makes this a DMA, not a recompute). Mid-prefill
        victims (interleaved admission) and swap_preempt=False fall
        back to restart-from-scratch (tokens discarded, RNG re-seeded).
        Either way the victim's final output is token-identical to an
        uninterrupted run. All victims share a single vectorized reset
        dispatch."""
        if (not self.serve.preempt or self.sched_policy == "fifo"
                or not self.continuous or not self.queue):
            return
        victims: List[int] = []
        running = {l: rs for l, rs in enumerate(self.lane_req)
                   if rs is not None}
        if len(running) < self.n_lanes:
            return                       # free lanes: plain admission
        # the freed lanes are NOT reserved: _admit re-selects by
        # _order_key, which hands them to these same candidates
        pool = sorted(self.queue, key=self._order_key)
        for cand in pool:
            if not running:
                break
            worst_lane = max(running, key=lambda l:
                             self._order_key(running[l]))
            if not self._outranks(cand, running[worst_lane]):
                break                    # pool is sorted: nobody else can
            victims.append(worst_lane)
            del running[worst_lane]
        if not victims:
            return
        swapped = set()
        if self.serve.swap_preempt:
            swapped = {l for l in victims
                       if self.lane_prefill[l] is None}
            if swapped:
                self._swap_out(sorted(swapped))
        mask = np.zeros(self.n_lanes, bool)
        mask[victims] = True
        self.eng.dispatch_count += 1
        self.n_resets += 1
        self.state = self._reset(self.state, jnp.asarray(mask))
        for lane in victims:
            rs = self.lane_req[lane]
            rs.status, rs.lane = Status.QUEUED, -1
            if lane not in swapped:
                # recompute path: discard progress, restart from scratch
                self.store.drop(rs.rid)
                rs.admit_sec = rs.first_token_sec = None
                rs.first_emit_step = None
                rs.tokens.clear()
            rs.n_preempts += 1
            self.n_preempted += 1
            self._release_prefix(rs.rid)
            self.lane_req[lane] = None
            self.lane_prefill[lane] = None
            self.active[lane] = False
            self.queue.append(rs)        # re-queued; _order_key decides
            #                              when it gets a lane back

    # ---------------------------------------------------------- timeouts

    def _expire_timeouts(self) -> None:
        """Cancel requests whose wall clock exceeded their timeout_ms:
        queued ones leave the queue with no dispatch; running ones free
        their lanes with one vectorized reset. Terminal status
        TIMED_OUT either way — a stuck or starved request can never pin
        a lane (or the queue) forever. PARKED requests are exempt by
        default (serve.park_exempts_timeout=True: parking is an
        explicit caller decision, and an idle parked session may far
        outlive any per-request SLO); with the knob False they expire
        too — zero dispatches, snapshots released from every tier."""
        now = self._now()

        def expired(rs):
            tm = rs.request.timeout_ms
            return tm is not None and (now - rs.submit_sec) * 1e3 > tm

        for rs in [q for q in self.queue if expired(q)]:
            self.queue.remove(rs)
            rs.status, rs.finish_sec = Status.TIMED_OUT, now
            rs.reason = (f"exceeded timeout_ms="
                         f"{rs.request.timeout_ms} while queued")
            self.store.drop(rs.rid)
            self.n_timeouts += 1
        if not self.serve.park_exempts_timeout:
            parked = [rs for rs in self.results.values()
                      if rs.status is Status.PARKED and expired(rs)]
            for rs in parked:
                rs.status, rs.finish_sec = Status.TIMED_OUT, now
                rs.reason = (f"exceeded timeout_ms="
                             f"{rs.request.timeout_ms} while parked")
                self.store.drop(rs.rid)
                self.n_timeouts += 1
        lanes = [l for l, rs in enumerate(self.lane_req)
                 if rs is not None and expired(rs)]
        if not lanes:
            return
        mask = np.zeros(self.n_lanes, bool)
        mask[lanes] = True
        self.eng.dispatch_count += 1
        self.n_resets += 1
        self.state = self._reset(self.state, jnp.asarray(mask))
        for lane in lanes:
            rs = self.lane_req[lane]
            rs.status, rs.finish_sec, rs.lane = Status.TIMED_OUT, now, -1
            rs.reason = (f"exceeded timeout_ms={rs.request.timeout_ms} "
                         f"while running")
            self.store.drop(rs.rid)
            self._release_prefix(rs.rid)
            self.n_timeouts += 1
            self.lane_req[lane] = None
            self.lane_prefill[lane] = None
            self.active[lane] = False

    # --------------------------------------------------------- admission

    def _pack_prompts(self, slots: List[Tuple[int, RequestState]],
                      skip_chunks: Optional[Dict[int, int]] = None):
        """Pack ragged prompts into one padded chunk grid:
        chunks [n_chunks, B, C] + per-request valid matrix
        [n_chunks, B] (full chunks, then each request's tail, then
        zeros — zero-chunks freeze that row, see prefill_chunk_loop).
        The batch dim is the full n_lanes and the rows are
        LANE-ALIGNED: `slots` maps each admitting request to its
        assigned lane and its chunks land at row == lane (all other
        lanes ride as all-zero-valid frozen rows), so the admission
        closure installs by [B] mask with no index scatter — the
        shard-local admission contract (docs/serving.md §Sharded
        serving). Per-LANE `skip_chunks` drops each request's
        already-cached prefix chunks (a prefix-cache hit prefills only
        its novel suffix; the cached slab's per-lane clock makes
        positions continue where the prefix left off). The chunk axis
        is rounded UP to the next POWER-OF-TWO bucket with
        all-zero-valid tail chunks — the prefill mirror of the decode
        drain-split buckets — so the suffix-length diversity prefix
        reuse creates costs O(log2 max_prompt_chunks)
        admission-closure compiles, never one per distinct length (and
        never one per admission size k, which varies freely under
        churn)."""
        C = self.serve.prefill_chunk
        per = {}
        for lane, rs in slots:
            ch, nv = _chunk_prompt(rs.request.prompt, C)
            d = skip_chunks.get(lane, 0) if skip_chunks else 0
            per[lane] = (ch[d:], nv[d:])
        n_chunks = max(ch.shape[0] for ch, _ in per.values())
        n_chunks = 1 << (n_chunks - 1).bit_length()
        self.prefill_bucket_lengths.add(n_chunks)
        chunks = np.zeros((n_chunks, self.n_lanes, C), np.int32)
        n_valid = np.zeros((n_chunks, self.n_lanes), np.int32)
        for lane, (ch, nv) in per.items():
            chunks[: ch.shape[0], lane] = ch
            n_valid[: nv.shape[0], lane] = nv
        return jnp.asarray(chunks), jnp.asarray(n_valid)

    def _pack_memory(self, slots: Dict[int, RequestState]):
        """Pack per-request cross memory into one padded slab:
        mem [n_lanes, S, feat] f32 + mem_len [n_lanes] int32 (rows not
        in `slots` — free lanes / admission-pad rows — stay all-zero
        with mem_len 0, which masks them out of every cross-attention
        read). slots maps row index -> RequestState."""
        S, feat = self.mem_shape
        mem = np.zeros((self.n_lanes, S, feat), np.float32)
        mem_len = np.zeros((self.n_lanes,), np.int32)
        for row, rs in slots.items():
            m = rs.request.extra_inputs[self.mem_key]
            mem[row, : m.shape[0]] = m
            mem_len[row] = m.shape[0]
        return jnp.asarray(mem), jnp.asarray(mem_len)

    # ------------------------------------------------------ prefix cache

    def _probe_prefix(self, batch: List[RequestState]):
        """Per fresh admission batch: walk the trie for each prompt's
        longest cached chunk-aligned prefix and decide what to capture.
        Returns (hits, caps), both keyed by batch row:
        hits[i] = PrefixEntry whose slab seeds row i (pinned for the
        rid until the request leaves its lane); caps[i] = (cap_rel,
        key) — snapshot row i after its cap_rel-th GRID chunk (grid =
        suffix when row i also hit) and insert it under key.

        Hit rule: lookup is LIMITED to the last chunk boundary STRICTLY
        below the prompt, so at least one suffix chunk always remains —
        the first output token still falls out of the live prefill.
        Capture rule (traffic-aware): the boundary is the deepest chunk
        multiple of the prompt's longest common prefix with the trie's
        recent-prompt window (observe()), clamped to the same strict
        limit — capturing each prompt's OWN deepest boundary would fill
        the budget with suffixes nobody else can hit. Gated on
        serve.prefix_min_tokens, on being strictly deeper than the hit
        (chained hits deepen entries), and deduped against both the
        trie and keys already chosen this round."""
        C = self.serve.prefill_chunk
        hits: Dict[int, object] = {}
        caps: Dict[int, Tuple[int, np.ndarray]] = {}
        chosen = set()
        for i, rs in enumerate(batch):
            prompt = np.asarray(rs.request.prompt, np.int32)
            n_chunks = -(-prompt.size // C)
            limit = (n_chunks - 1) * C
            entry = (self._pc.lookup(prompt, limit=limit, pin=rs.rid)
                     if limit > 0 else None)
            d1 = 0
            if entry is not None:
                d1 = entry.n_tokens // C
                hits[i] = entry
                self.n_prefix_hits += 1
                self.n_prefix_reused_tokens += entry.n_tokens
            else:
                self.n_prefix_misses += 1
            lcp = self._pc.observe(prompt)
            cap_tokens = min(lcp // C * C, limit)
            if (cap_tokens >= max(self.serve.prefix_min_tokens, C)
                    and cap_tokens // C > d1):
                key = prompt[:cap_tokens]
                kb = key.tobytes()
                if kb not in chosen and not self._pc.contains(key):
                    chosen.add(kb)
                    caps[i] = (cap_tokens // C - d1, key)
        return hits, caps

    def _install_prefix(self, batch: List[Tuple[object, int]]) -> None:
        """Interleaved hit path: ONE install dispatch where-selects the
        k cached prefix slabs (stacked lane-aligned) into their freshly
        assigned lanes before the mixed segments stream each request's
        suffix chunks (phased hits ride inside the admission dispatch
        instead — zero extra cost there). tok/keys are NOT touched: the
        mixed scan writes both at the lane's finish transition."""
        rows = {lane: entry.state for entry, lane in batch}
        sub = jax.tree.map(jnp.asarray,
                           _stack_lane_rows(rows, self.n_lanes))
        mask = np.zeros(self.n_lanes, bool)
        mask[[lane for _, lane in batch]] = True
        self.eng.dispatch_count += 1
        self.n_prefix_installs += 1
        self.state = self._prefix_install(self.state, sub,
                                          jnp.asarray(mask))

    def _capture_lanes(self, lanes: List[int]) -> None:
        """Interleaved capture path: the schedule held these lanes at
        their capture boundary (next_chunk == capture_at), so their
        current state IS the boundary prefix state — ONE batched
        extract dispatch commits the full lane state (identity program,
        as in _swap_out), each boundary lane's row is inserted into the
        trie under its chunk-aligned key, and clearing capture_key
        unblocks the remaining suffix chunks for the next segment's
        schedule."""
        self.eng.dispatch_count += 1
        self.n_prefix_extracts += 1
        sub, _, _ = jax.device_get(
            self._extract(self.state, self.tok, self.keys))
        for lane in lanes:
            pf = self.lane_prefill[lane]
            self._pc.insert(pf.capture_key, _snap_row(sub, lane))
            pf.capture_key = None

    def _release_prefix(self, rid: int) -> None:
        """Unpin rid's prefix-cache entry (idempotent; no-op when the
        cache is off or rid holds no pin) — called on EVERY path that
        clears a lane (retire, preempt, timeout, quarantine, park), so
        a slab becomes evictable the moment no lane was built from
        it."""
        if self._pc is not None:
            self._pc.release(rid)

    # --------------------------------------------------- admission lanes

    def _claim_lanes(self) -> List[int]:
        """Common admission gate: which free lanes can be filled now
        (static batching waits for the full drain)."""
        free = [l for l in range(self.n_lanes) if self.lane_req[l] is None]
        if not self.continuous and len(free) < self.n_lanes:
            return []
        return free

    def _snapshot_lost(self, rs: RequestState) -> bool:
        """A stored snapshot failed verification (checksum mismatch,
        torn disk write, IO error) at resume time — the SILENT
        corruption case NaN detection can't see. Route it through the
        same bounded-replay budget as quarantine: recompute from the
        prompt (deterministic seeds regenerate the identical stream)
        unless the request exhausted max_retries, then terminal FAILED.
        Returns True if the request survives (recompute), False if it
        was failed terminally."""
        self.store.drop(rs.rid)
        self.n_snapshot_lost += 1
        rs.n_retries += 1
        if rs.n_retries > self.serve.max_retries:
            rs.status, rs.finish_sec = Status.FAILED, self._now()
            rs.reason = ("snapshot failed integrity verification and "
                         f"replay budget ({self.serve.max_retries}) "
                         "is exhausted")
            self.n_failed += 1
            return False
        rs.tokens.clear()
        rs.admit_sec = rs.first_token_sec = None
        rs.first_emit_step = None
        return True

    def _take_admissions(self) -> Tuple[
            List[Tuple[RequestState, LaneSnapshot, int]],
            List[Tuple[RequestState, int]]]:
        """Pop up to len(free) queued requests in _order_key order and
        split them into (resume, fresh) lane assignments — requests
        with a stored LaneSnapshot (swap-preempted victims, revived
        parks, fault replays with a checkpoint) resume instead of
        re-prefilling. Every snapshot is FETCHED AND VERIFIED here
        (store.get recomputes the capture checksums; disk copies are
        read + verified); a failed verification demotes the request to
        the fresh (recompute) list via _snapshot_lost, or fails it
        terminally once out of retries — corruption can cost a lane
        slot this round, never a crash."""
        free = self._claim_lanes()
        k = min(len(free), len(self.queue))
        batch = [self._pop_next() for _ in range(k)]
        resume, fresh = [], []
        for rs in batch:
            if self.store.has(rs.rid):
                snap = self.store.get(rs.rid)
                if snap is not None:
                    resume.append((rs, snap))
                    continue
                if not self._snapshot_lost(rs):
                    continue             # terminal FAILED: lane unused
            elif rs.tokens:
                # the store dropped this snapshot for CAPACITY (RAM
                # pressure with no disk tier) — not corruption, so no
                # retry is burned: roll the host stream back to the
                # prompt and recompute (deterministic seeds regenerate
                # the identical tokens)
                rs.tokens.clear()
                rs.admit_sec = rs.first_token_sec = None
                rs.first_emit_step = None
            fresh.append(rs)
        lanes = iter(free)
        return ([(rs, snap, next(lanes)) for rs, snap in resume],
                [(rs, next(lanes)) for rs in fresh])

    @_span("serve.admit")
    def _admit(self) -> int:
        """Phased admission (PR 3): fill free lanes from the queue —
        the whole admission batch (ragged prefill, first tokens, masked
        lane install) is ONE dispatch however many requests it packs, but
        decode lanes sit idle while it runs. Snapshot-holding requests
        are restored by ONE resume dispatch instead (no re-prefill).
        Prefix-cache rounds stay ONE dispatch too: hit rows enter the
        grid as suffix-only chunks seeded by their cached slab (sub0),
        and capture rows are snapshotted inside the admission scan
        (capture_chunk carry) and inserted into the trie from the
        returned snap."""
        resume, fresh = self._take_admissions()
        if resume:
            self._resume_lanes(resume)
        if not fresh:
            return len(resume)
        batch = [rs for rs, _ in fresh]
        lanes = [lane for _, lane in fresh]
        k = len(fresh)
        hits, caps = ({}, {})
        if self._pc is not None:
            hits_b, caps_b = self._probe_prefix(batch)
            # _probe_prefix keys by batch row; every device operand
            # below is LANE-ALIGNED, so remap the keys to lanes
            hits = {lanes[i]: e for i, e in hits_b.items()}
            caps = {lanes[i]: c for i, c in caps_b.items()}
        C = self.serve.prefill_chunk
        skip = {l: e.n_tokens // C for l, e in hits.items()} or None
        chunks, n_valid = self._pack_prompts(list(zip(lanes, batch)),
                                             skip_chunks=skip)
        # [B] admission mask: non-admitting lanes keep their state
        # through the where-select install — no index scatter, so the
        # program stays shard-local under a mesh
        mask = np.zeros(self.n_lanes, bool)
        mask[lanes] = True
        seeds = [0] * self.n_lanes
        for rs, lane in fresh:
            seeds[lane] = rs.request.seed
        self.eng.dispatch_count += 1
        self.n_prefill_rounds += 1
        args = (self.state, self.tok, self.keys, chunks, n_valid,
                jnp.asarray(_prng_keys(seeds)), jnp.asarray(mask))
        if self.mem_key is not None:
            # sub-state row `lane` holds that lane's request; its
            # memory rides the same rows and is installed inside the
            # same single dispatch
            args += self._pack_memory({lane: rs for rs, lane in fresh})
            self.state, self.tok, self.keys = self._admit_fn(*args)
        elif hits or caps:
            capture = np.zeros(self.n_lanes, np.int32)
            for l, (cap_rel, _) in caps.items():
                capture[l] = cap_rel
            if hits:
                # hit lanes start from their cached slab (its per-lane
                # clock already at the prefix boundary); the rest from
                # a fresh host row — one stacked sub0 operand
                rows = [hits[l].state if l in hits
                        else self.eng.fresh_lane_row()
                        for l in range(self.n_lanes)]
                sub0 = jax.tree.map(jnp.asarray,
                                    _stack_rows(rows, self.n_lanes))
                (self.state, self.tok, self.keys,
                 snap) = self._admit_prefix_fn(*args, sub0,
                                               jnp.asarray(capture))
            else:
                (self.state, self.tok, self.keys,
                 snap) = self._admit_capture_fn(*args,
                                                jnp.asarray(capture))
            if caps:
                snap_host = jax.device_get(snap)
                for l, (_, key) in caps.items():
                    self._pc.insert(key, _snap_row(snap_host, l))
        else:
            self.state, self.tok, self.keys = self._admit_fn(*args)
        now = self._now()
        for rs, lane in fresh:
            rs.status, rs.lane, rs.admit_sec = Status.RUNNING, lane, now
            self.lane_req[lane] = rs
            self.active[lane] = True
            self.n_emitted[lane] = 0
            self.max_new[lane] = rs.request.max_new
            self.eos[lane] = rs.request.eos_id
            if self.spec_k:
                self._seed_hist(lane, rs)
        return len(resume) + k

    @_span("serve.admit")
    def _admit_interleaved(self) -> int:
        """Interleaved admission: assign requests to free lanes and
        chunk their prompts host-side; the prefill itself is threaded
        into the coming mixed segments (zero dedicated dispatches).
        The lane was reset at retire time (pos := -1 makes every slot
        invisible and lose every top-M merge), so chunk-prefilling
        straight into it is token-identical to one-shot prefill into a
        fresh state. Snapshot-holding requests are restored by one
        resume dispatch — they have no prompt left to prefill."""
        resume, fresh = self._take_admissions()
        if resume:
            self._resume_lanes(resume)
        hits, caps = ({}, {})
        if self._pc is not None and fresh:
            hits, caps = self._probe_prefix([rs for rs, _ in fresh])
        now = self._now()
        C = self.serve.prefill_chunk
        install: List[Tuple[object, int]] = []
        for i, (rs, lane) in enumerate(fresh):
            ch, nv = _chunk_prompt(rs.request.prompt, C)
            d1 = hits[i].n_tokens // C if i in hits else 0
            pf = _LanePrefill(ch[d1:], nv[d1:])
            if i in caps:
                pf.capture_at, pf.capture_key = caps[i]
            self.lane_prefill[lane] = pf
            if i in hits:
                install.append((hits[i], lane))
            rs.status, rs.lane, rs.admit_sec = Status.RUNNING, lane, now
            self.lane_req[lane] = rs
            self.active[lane] = False    # activates inside the scan at
            #                              its finish step
            self.n_emitted[lane] = 0
            self.max_new[lane] = rs.request.max_new
            self.eos[lane] = rs.request.eos_id
            if self.spec_k:
                # the first carry is the prefill argmax (set inside the
                # mixed scan), so the history at activation is exactly
                # the full prompt tail — no in-scan history write needed
                self._seed_hist(lane, rs)
        if install:
            # one dispatch seeds every hit lane with its cached slab;
            # the mixed segments then stream only the novel suffixes
            self._install_prefix(install)
        return len(resume) + len(fresh)

    # ---------------------------------------------------------- decoding

    @_span("serve.plan")
    def _build_prefill_schedule(self, n_steps: int):
        """Lay this segment's prompt chunks onto the [n_steps, B] grid:
        one chunk per prefilling lane per step, lanes visited in
        sched_policy order, capped at serve.prefill_budget prompt
        tokens per segment (0 = unlimited; the first chunk of a segment
        always proceeds so admission can never starve). Returns device
        operands (chunks, n_valid, finish), the RNG keys for lanes
        finishing within this segment, the per-lane chunk counts to
        commit after the dispatch, the per-lane install mask (lanes
        whose FIRST prompt chunk — global chunk index 0 — rides in this
        segment: their cross memory must be installed before the scan),
        and the DRAIN step: the first step index with no chunk left —
        the segment is split there into mixed + pure-decode dispatches
        so drained steps never pay the chunk sub-step."""
        C = self.serve.prefill_chunk
        B = self.n_lanes
        chunks = np.zeros((n_steps, B, C), np.int32)
        nv = np.zeros((n_steps, B), np.int32)
        finish = np.zeros((n_steps, B), bool)
        new_keys = np.zeros((B, 2), np.uint32)
        install = np.zeros((B,), bool)
        budget = self.serve.prefill_budget
        lanes = [l for l in range(B) if self.lane_prefill[l] is not None]
        lanes.sort(key=lambda l: self._order_key(self.lane_req[l]))
        progress = {l: self.lane_prefill[l].next_chunk for l in lanes}
        spent, drain = 0, 0
        for j in range(n_steps):
            for lane in lanes:
                pf = self.lane_prefill[lane]
                i = progress[lane]
                if i >= pf.n_chunks:
                    continue
                if pf.capture_key is not None and i >= pf.capture_at:
                    # hold at the capture boundary: the slab must be
                    # extracted (end of this segment) before any chunk
                    # past it may mutate the lane. capture_at >= 1 and
                    # captures fire every segment boundary, so a held
                    # lane ALWAYS still has schedulable chunks — the
                    # drain can never collapse to zero because of this
                    continue
                tok_count = int(pf.n_valid[i])
                if budget > 0 and spent > 0 and spent + tok_count > budget:
                    continue
                chunks[j, lane] = pf.chunks[i]
                nv[j, lane] = tok_count
                if i == 0:
                    install[lane] = True
                if i == pf.n_chunks - 1:
                    finish[j, lane] = True
                    new_keys[lane] = _prng_keys(
                        [self.lane_req[lane].request.seed])[0]
                progress[lane] = i + 1
                spent += tok_count
                drain = j + 1
        scheduled = {l: progress[l] - self.lane_prefill[l].next_chunk
                     for l in lanes}
        return chunks, nv, finish, new_keys, scheduled, install, drain

    @_span("serve.mixed")
    def _dispatch_mixed(self, chunks, nv, finish, new_keys, scheduled,
                        install):
        """One mixed prefill/decode dispatch running the prebuilt
        schedule (chunks [d, B, C] — already sliced to the drain
        boundary); commits the host-side chunk progress it carries.
        Returns the per-step (ids, emitted) rows plus the per-lane
        health flags. Cross families route through the
        memory-installing closure only when some lane's FIRST chunk
        rides in this dispatch — otherwise the plain closure skips
        re-running the encoder/vision projection."""
        self.eng.dispatch_count += 1
        self.n_segments += 1
        self.n_mixed_steps += chunks.shape[0]
        self.n_chunk_rows += (int((nv > 0).sum()) * chunks.shape[2]
                              if self.eng.mixed_compact else chunks.size)
        self.n_chunk_rows_valid += int(nv.sum())
        spec = self.spec_k > 0
        args = (self.state, self.tok, self.keys, jnp.asarray(self.active),
                jnp.asarray(self.n_emitted), jnp.asarray(self.max_new),
                jnp.asarray(self.eos))
        if spec:
            args += (jnp.asarray(self.hist),)
        args += (jnp.asarray(chunks), jnp.asarray(nv),
                 jnp.asarray(finish), jnp.asarray(new_keys))
        mixed_fn = self._spec_mixed_nomem if spec else self._mixed_nomem
        if self.mem_key is not None and install.any():
            mem, mem_len = self._pack_memory(
                {l: self.lane_req[l] for l in range(self.n_lanes)
                 if install[l]})
            args += (mem, mem_len, jnp.asarray(install))
            mixed_fn = self._spec_mixed if spec else self._mixed
        if spec:
            self.n_verify_rounds += int(chunks.shape[0])
        outs = mixed_fn(*args)
        for lane, n in scheduled.items():
            pf = self.lane_prefill[lane]
            pf.next_chunk += n
            if pf.done:
                self.lane_prefill[lane] = None       # decoding now
        return self._fetch(outs)

    @_span("serve.decode")
    def _dispatch_decode(self, n_steps: int):
        """One pure-decode dispatch of n_steps steps (a full segment,
        or the drained remainder of a split interleaved segment).
        Remainders are rounded UP to the next power-of-two BUCKET with
        the tail masked bit-identically inside the scan (traced
        n_real), so the closure cold-compiles once per bucket —
        O(log2 decode_segment) shapes — instead of once per distinct
        remainder length."""
        seg = self.serve.decode_segment
        if n_steps >= seg:
            bucket = n_steps             # the full segment: one shape
        else:
            bucket = min(1 << (n_steps - 1).bit_length(), seg)
        self.decode_bucket_lengths.add(bucket)
        self.eng.dispatch_count += 1
        self.n_segments += 1
        self.n_decode_steps += bucket
        if self.spec_k > 0:
            # speculative segment: `bucket` static VERIFY ROUNDS (same
            # pow2 contract, round units), n_steps logical; each round
            # commits 1..spec_k+1 tokens per live lane, so the returned
            # grids carry n_steps * (spec_k + 1) token columns
            self.n_verify_rounds += n_steps
            outs = self._spec_segment_fn(
                self.state, self.tok, self.keys, jnp.asarray(self.active),
                jnp.asarray(self.n_emitted), jnp.asarray(self.max_new),
                jnp.asarray(self.eos), jnp.asarray(self.hist), bucket,
                np.int32(n_steps))
            n_cols = n_steps * (self.spec_k + 1)
        else:
            outs = self._segment(
                self.state, self.tok, self.keys, jnp.asarray(self.active),
                jnp.asarray(self.n_emitted), jnp.asarray(self.max_new),
                jnp.asarray(self.eos), bucket, np.int32(n_steps))
            n_cols = n_steps
        ids, emitted, ok = self._fetch(outs)
        # masked bucket-tail steps emit nothing; slice to logical length
        return ids[:, :n_cols], emitted[:, :n_cols], ok

    def _fetch(self, outs):
        """Adopt one segment dispatch's outputs: the lane state stays
        on the device; the lane bookkeeping, the token grids, the health
        flags and (speculative programs) the drafter history and
        acceptance counters come to the host. The first conversion
        blocks until the program has run: the serve.wait span."""
        (self.state, self.tok, self.keys, active_d, n_emitted_d, ids,
         emitted, ok, *spec) = outs
        with jax.profiler.TraceAnnotation("serve.wait"):
            # np.array (copy): asarray views of device buffers are
            # read-only, and the scheduler writes these
            self.active = np.array(active_d)
            self.n_emitted = np.array(n_emitted_d)
            ids, emitted, ok = (np.asarray(ids), np.asarray(emitted),
                                np.array(ok))
            spec = [np.array(x) for x in spec]
        if spec:
            self.hist, a_tok, a_rnd = spec
            self._account_spec(a_tok, a_rnd)
        return ids, emitted, ok

    def _account_spec(self, a_tok: np.ndarray, a_rnd: np.ndarray):
        """Fold one spec dispatch's per-lane acceptance counters
        (committed tokens / live rounds) into the scheduler totals and
        each lane's RequestState — spec_tokens / spec_rounds is the
        request's mean acceptance length."""
        self.n_spec_tokens += int(a_tok.sum())
        self.n_spec_rounds += int(a_rnd.sum())
        for lane in range(self.n_lanes):
            rs = self.lane_req[lane]
            if rs is not None and a_rnd[lane]:
                rs.spec_rounds += int(a_rnd[lane])
                rs.spec_tokens += int(a_tok[lane])

    def _quarantine(self, bad: List[int]) -> None:
        """Recover lanes whose segment produced non-finite outputs:
        scrub their state (reset + K/V payload zeroed — T.scrub_lanes,
        one vectorized dispatch), discard this segment's suspect
        emissions, and replay each victim from its last snapshot (or
        from scratch) unless it exhausted serve_cfg.max_retries — then
        it is FAILED terminally instead of wedging the loop."""
        mask = np.zeros(self.n_lanes, bool)
        mask[bad] = True
        self.eng.dispatch_count += 1
        self.n_resets += 1
        self.state = self._scrub(self.state, jnp.asarray(mask))
        self.n_quarantined += len(bad)
        now = self._now()
        for lane in bad:
            rs = self.lane_req[lane]
            self.lane_req[lane] = None
            self.lane_prefill[lane] = None
            self.active[lane] = False
            self._release_prefix(rs.rid)
            rs.lane = -1
            rs.n_retries += 1
            if rs.n_retries > self.serve.max_retries:
                rs.status, rs.finish_sec = Status.FAILED, now
                rs.reason = (f"non-finite outputs persisted after "
                             f"{self.serve.max_retries} replays")
                self.store.drop(rs.rid)
                self.n_failed += 1
                continue
            rs.status = Status.QUEUED
            n_tok = self.store.peek_n_tokens(rs.rid)
            if n_tok is not None:
                # replay from the last stored checkpoint: roll the
                # host-side stream back to the snapshot point (the slab
                # itself is verified when admission fetches it)
                del rs.tokens[n_tok:]
            else:
                # no checkpoint: recompute from scratch
                rs.tokens.clear()
                rs.admit_sec = rs.first_token_sec = None
                rs.first_emit_step = None
            self.queue.append(rs)

    def _run_segment(self) -> List[RequestState]:
        """One logical segment (serve.decode_segment steps) over all
        lanes — plain decode, or, while any lane is still prefilling
        (interleaved admission), the mixed prefill/decode program SPLIT
        at the drain boundary: mixed steps only while prompt chunks
        remain, the pure-decode closure (power-of-two bucketed) for the
        rest. The split keeps dispatches O(segments) (each half counts
        in n_segments) and stops drained steps from paying the per-step
        chunk sub-step. Harvest emissions, quarantine lanes whose
        health flag tripped, retire lanes that finished inside the
        segment; TTFT derives from each lane's first-emission STEP
        (interpolated over the segment wall time), not the harvest
        timestamp."""
        n_steps = self.serve.decode_segment
        prefilling = any(pf is not None for pf in self.lane_prefill)
        t_seg0 = self._now()
        if prefilling:
            chunks, nv, finish, new_keys, scheduled, install, drain = \
                self._build_prefill_schedule(n_steps)
            # every scheduled chunk lies before `drain`, so slicing the
            # grids to [:drain] dispatches exactly the built schedule
            ids, emitted, ok = self._dispatch_mixed(
                chunks[:drain], nv[:drain], finish[:drain], new_keys,
                scheduled, install)
            if drain < n_steps:
                self.n_segment_splits += 1
                ids2, emitted2, ok2 = self._dispatch_decode(
                    n_steps - drain)
                ids = np.concatenate([ids, ids2], axis=1)
                emitted = np.concatenate([emitted, emitted2], axis=1)
                ok = ok & ok2
        else:
            ids, emitted, ok = self._dispatch_decode(n_steps)
        return self._retire(ids, emitted, ok, t_seg0)

    @_span("serve.retire")
    def _retire(self, ids, emitted, ok, t_seg0) -> List[RequestState]:
        """The tail of a segment: harvest each lane's emissions,
        quarantine lanes whose health flag tripped, capture prefix
        slabs, reset retired lanes and take periodic checkpoints.
        Returns the requests that finished."""
        bad = [l for l in range(self.n_lanes)
               if not ok[l] and self.lane_req[l] is not None]
        finished, retired_lanes, now = [], [], self._now()
        for lane in range(self.n_lanes):
            rs = self.lane_req[lane]
            if rs is None or lane in bad:
                continue                 # bad lanes: emissions suspect
            new_toks = ids[lane][emitted[lane]]
            if new_toks.size and not rs.tokens:
                # first emission: stamp the within-segment TOKEN COLUMN
                # it happened at, and interpolate its wall time across
                # the segment — decode_segment no longer quantizes TTFT
                # up. Columns are token units: one per step normally,
                # spec_k + 1 per verify round under speculation, so the
                # interpolation denominator is the column count.
                j0 = int(np.argmax(emitted[lane]))
                rs.first_emit_step = self._steps_done + j0
                rs.first_token_sec = t_seg0 + (now - t_seg0) * \
                    (j0 + 1) / ids.shape[1]
            rs.tokens.extend(int(x) for x in new_toks)
            if not self.active[lane] and self.lane_prefill[lane] is None:
                rs.status, rs.finish_sec, rs.lane = Status.DONE, now, -1
                self.lane_req[lane] = None
                self.store.drop(rs.rid)  # release snapshots, every tier
                self._release_prefix(rs.rid)
                finished.append(rs)
                retired_lanes.append(lane)
        # the global emission clock advances in TOKEN COLUMNS (== steps
        # when spec is off), keeping first_emit_step deterministic and
        # monotone across spec and non-spec segments alike
        self._steps_done += ids.shape[1]
        if bad:
            self._quarantine(bad)
        if self._pc is not None:
            ready = [l for l in range(self.n_lanes)
                     if self.lane_prefill[l] is not None
                     and self.lane_prefill[l].capture_key is not None
                     and self.lane_prefill[l].next_chunk
                     >= self.lane_prefill[l].capture_at]
            if ready:
                self._capture_lanes(ready)
        if retired_lanes:
            # one vectorized reset for every lane retired this segment
            mask = np.zeros(self.n_lanes, bool)
            mask[retired_lanes] = True
            self.eng.dispatch_count += 1
            self.n_resets += 1
            self.state = self._reset(self.state, jnp.asarray(mask))
        every = self.serve.checkpoint_every
        if every > 0 and self.n_segments % every == 0:
            decoding = [l for l in range(self.n_lanes)
                        if self.lane_req[l] is not None
                        and self.lane_prefill[l] is None
                        and self.active[l]]
            if decoding:
                # periodic checkpoint: fault replay resumes from here
                # instead of recomputing the whole request (durable
                # kind: written through to the disk tier when
                # serve.snapshot_dir is set — crash-restart material)
                self._swap_out(decoding, kind="checkpoint")
        return finished

    # --------------------------------------------------------- top level

    def step(self) -> List[RequestState]:
        """One scheduling round: let the fault injector act (chaos
        runs), expire timeouts, preempt if an SLO demands it, admit /
        resume into free lanes, then run one fused segment. Returns the
        requests that finished."""
        if self.injector is not None:
            self.injector.on_step(self)
        self._expire_timeouts()
        self._maybe_preempt()
        if self.interleaved:
            self._admit_interleaved()
            if self.active.any() or any(pf is not None
                                        for pf in self.lane_prefill):
                return self._run_segment()
            return []
        self._admit()
        if self.active.any():
            return self._run_segment()
        return []

    def stats(self) -> Dict[str, int]:
        """Supervision / dispatch counters (the stream launcher prints
        these, and the chaos suite asserts on them — degradation must
        be observable, not silent)."""
        out = {
            "n_prefill_rounds": self.n_prefill_rounds,
            "n_segments": self.n_segments,
            "n_segment_splits": self.n_segment_splits,
            "n_decode_steps": self.n_decode_steps,
            "n_mixed_steps": self.n_mixed_steps,
            "n_chunk_rows": self.n_chunk_rows,
            "n_chunk_rows_valid": self.n_chunk_rows_valid,
            "n_resets": self.n_resets,
            "n_preempted": self.n_preempted,
            "n_swaps": self.n_swaps,
            "n_resumes": self.n_resumes,
            "n_shed": self.n_shed,
            "n_quarantined": self.n_quarantined,
            "n_timeouts": self.n_timeouts,
            "n_failed": self.n_failed,
            "n_faults_injected": self.n_faults_injected,
            "n_retries": sum(rs.n_retries for rs in self.results.values()),
            "n_snapshot_lost": self.n_snapshot_lost,
            "n_recovered_sessions": self.n_recovered_sessions,
            "n_verify_rounds": self.n_verify_rounds,
            "n_spec_rounds": self.n_spec_rounds,
            "n_spec_tokens": self.n_spec_tokens,
        }
        # snapshot tier counters (serve.store) — hits/spills/corruption
        # detection/IO degradation, prefixed to keep one flat namespace
        out.update({f"store_{k}": v for k, v in self.store.stats().items()})
        if self._pc is not None:
            # prefix-cache traffic: scheduler-side admission counters
            # plus the trie's own structural counters (prefix_*)
            out.update({
                "n_prefix_hits": self.n_prefix_hits,
                "n_prefix_misses": self.n_prefix_misses,
                "n_prefix_reused_tokens": self.n_prefix_reused_tokens,
                "n_prefix_installs": self.n_prefix_installs,
                "n_prefix_extracts": self.n_prefix_extracts,
            })
            out.update({f"prefix_{k}": v
                        for k, v in self._pc.stats().items()})
        return out

    def run(self, requests: Iterable[Request] = (),
            respect_arrivals: bool = False) -> Dict[int, RequestState]:
        """Drain: serve every given (plus already queued) request to a
        terminal status and return {rid: RequestState}. With
        respect_arrivals, each request is submitted once wall-clock
        reaches its `arrival` offset (fast-forwarding when the engine
        goes idle, so a sparse Poisson trace never sleeps). Requests
        PARKED via park() are left parked — revive() puts them back in
        play."""
        pending = sorted(requests, key=lambda r: r.arrival)
        pending.reverse()                # pop() takes the earliest
        while pending or self.queue or self.n_running:
            # submit due arrivals; when the queue is at max_queue the
            # remaining arrivals WAIT here (backpressure) instead of
            # being shed — they retry once the queue drains, so a drain
            # run never drops traffic it was handed
            now = self._now()
            while pending and (not respect_arrivals or
                               pending[-1].arrival <= now or self.idle):
                if len(self.queue) >= self.serve.max_queue:
                    break
                self.submit(pending.pop())
            self.step()
        # drain the snapshot writer: parked/checkpointed sessions are
        # durably on disk when the drain returns (crash-restart safety)
        self.store.flush()
        return self.results
