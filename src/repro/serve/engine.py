"""Batched serving engine: prefill (single-shot or chunked) + decode with
any eviction policy over the bounded KV cache.

The engine jit-compiles one prefill and one decode closure per
(config, policy, budget) and reuses them across requests. Greedy or
temperature sampling. `teacher_forced_accuracy` scores gold answer spans
under eviction — the measurement used by the paper-table benchmarks.

Decode-path architecture (docs/serving.md):
  * fused (default): the whole generation runs as ONE compiled device
    program — T.decode_loop scans sample -> embed -> layers -> evict ->
    logits with the state donated, so Engine.generate issues O(1)
    dispatches regardless of max_new. `serve_cfg.fused=False` (or
    `generate(..., fused=False)`) falls back to the eager per-token
    Python loop (one dispatch per token) — kept as the parity/benchmark
    reference.
  * attn_impl: "xla" routes decode attention through the grouped einsum
    in core.cache and prefill through chunked_attention; "pallas" routes
    them through the flash kernels (kernels.decode_attention /
    kernels.retention_attention / kernels.chunk_attention), which also
    emit the per-slot probs and in-flight-token mass the eviction
    policies consume.
  * chunked prefill mirrors decode: `serve_cfg.fused` runs the whole
    per-chunk pipeline (chunk attention + top-M eviction merge) under
    one lax.scan (T.prefill_chunk_loop, donated state) — O(1) dispatches
    for any prompt length. The prompt is padded to whole chunks with the
    tail positions masked, so the eager reference loop also compiles a
    single closure shape regardless of T % prefill_chunk.

`dispatch_count` counts host->device program launches issued by this
engine (incremented once per jitted-closure call) — the O(1)-dispatch
claim is asserted on it by tests/test_decode_fused.py. The
continuous-batching scheduler (serve.scheduler) builds on this engine:
its jitted lane closures live here (`lane_closures`, cached per engine
so successive schedulers share compilations) and its launches are
counted on the same `dispatch_count`.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ServeConfig
from repro.core.policies import make_policy
from repro.models import transformer as T
from repro.serve.prefix_cache import PrefixCache
from repro.sharding import rules as shard_rules


def _greedy_token(params, cfg, h_last):
    """Greedy token from the prefill's last hidden state [B,d]."""
    logits = T.compute_logits(params, cfg, h_last)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class _Weighted:
    """A jitted program whose two leading arguments are the weights and
    gates, called with the rest: it supplies the engine's arrays, so the
    weights reach the device program as arguments (counted in its
    argument bytes), never as constants compiled into its HLO."""

    def __init__(self, engine, jitted):
        self.engine, self.jitted = engine, jitted

    def __call__(self, *args):
        return self.jitted(self.engine.params, self.engine.gates, *args)

    def lower(self, *args):
        return self.jitted.lower(self.engine.params, self.engine.gates,
                                 *args)

    def _cache_size(self):
        return self.jitted._cache_size()


class Engine:
    def __init__(self, cfg, params, gate_params, serve_cfg: ServeConfig,
                 mesh=None):
        self.cfg = cfg
        self.mesh = mesh
        # shardings of (params, gates): None on one device
        self._weight_shardings = None
        if mesh is not None:
            # SPMD serving (docs/serving.md §Sharded serving): weights
            # are placed ONCE — tensor-parallel over "model" where the
            # head/FFN counts divide, replicated over the data axes
            # (fsdp=False: decode must not all-gather weights every
            # step). Every program below takes the committed arrays as
            # arguments with these shardings, so the partitioner sees
            # their layout without per-call traffic.
            q_tp, kv_tp = shard_rules.attn_tp_flags(cfg, mesh)
            self._weight_shardings = (
                shard_rules.param_shardings(mesh, params, fsdp=False,
                                            q_tp=q_tp, kv_tp=kv_tp),
                shard_rules.replicated(mesh, gate_params))
            params = jax.device_put(params, self._weight_shardings[0])
            gate_params = jax.device_put(gate_params,
                                         self._weight_shardings[1])
        self.params = params
        self.gates = gate_params
        self.serve = serve_cfg
        self.policy = make_policy(serve_cfg)
        self.dispatch_count = 0
        impl = serve_cfg.attn_impl
        if impl not in ("xla", "pallas"):
            raise ValueError(f"unknown attn_impl {impl!r}; "
                             f"expected 'xla' or 'pallas'")
        if serve_cfg.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got "
                             f"{serve_cfg.spec_k}")
        if (serve_cfg.spec_k > 0 and cfg.family == "moe"
                and cfg.num_experts > 0):
            raise ValueError(
                "spec_k > 0 is unsupported for the MoE family: expert "
                "capacity couples tokens across the verify chunk, so "
                "chunk-shaped scoring cannot be bit-identical per row")

        # every program that reads the weights takes them as its two
        # leading ARGUMENTS (see _Weighted): a closed-over array would be
        # lowered as an HLO literal, baking the weights into each program
        def _prefill(params, gates, tokens, state, extra):
            return T.prefill(params, gates, cfg, tokens, state,
                             self.policy, serve_cfg, extra_inputs=extra)

        def _prefill_chunk(params, gates, tokens, n_valid, state, extra):
            return T.prefill_chunk(params, gates, cfg, tokens, state,
                                   self.policy, serve_cfg, n_valid=n_valid,
                                   extra_inputs=extra)

        def _prefill_chunk_loop(params, gates, chunks, n_valid, state,
                                extra):
            return T.prefill_chunk_loop(params, gates, cfg, chunks,
                                        n_valid, state, self.policy,
                                        serve_cfg, extra_inputs=extra)

        def _decode(params, gates, state, token):
            return T.decode_step(params, gates, cfg, state, token,
                                 self.policy, attn_impl=impl)

        def _decode_loop(params, gates, state, h_last, rng, n_steps,
                         greedy):
            first = _greedy_token(params, cfg, h_last)
            return T.decode_loop(params, gates, cfg, state, first,
                                 n_steps, self.policy, greedy=greedy,
                                 temperature=serve_cfg.temperature,
                                 rng=rng, attn_impl=impl)

        def _tf_loop(params, gates, state, h_last, tokens):
            preds0 = _greedy_token(params, cfg, h_last)
            state, preds = T.teacher_force_loop(params, gates, cfg, state,
                                                tokens, self.policy,
                                                attn_impl=impl)
            return state, jnp.concatenate([preds0[:, None], preds], axis=1)

        self._prefill = self._weighted(_prefill, donate=(1,))
        self._prefill_chunk = self._weighted(_prefill_chunk, donate=(2,))
        self._prefill_chunk_loop = self._weighted(_prefill_chunk_loop,
                                                  donate=(2,))
        self._decode = self._weighted(_decode, donate=(0,))
        self._decode_loop = self._weighted(_decode_loop, static=(3, 4),
                                           donate=(0,))
        self._tf_loop = self._weighted(_tf_loop, donate=(0,))
        self._lane_closures = {}
        # prefix KV cache (docs/serving.md §Prefix cache): owned by the
        # ENGINE, not the scheduler, so successive schedulers built on
        # this engine (warm-up then measured run, multi-phase benches)
        # share one warm trie the way they share one compilation cache
        self.prefix_cache = (
            PrefixCache(serve_cfg.prefix_cache_bytes,
                        ttl_sec=serve_cfg.prefix_ttl_sec)
            if serve_cfg.prefix_cache_bytes > 0 else None)
        self._fresh_row = None

    @property
    def mem_key(self) -> Optional[str]:
        """extra_inputs key carrying the cross-attention memory for
        this family (None for families without one)."""
        return {"vlm": "vision_embeds",
                "encdec": "source_embeds"}.get(self.cfg.family)

    @property
    def mem_shape(self):
        """(S, feat) of one request's full-length memory slab — the
        shared shape the scheduler pads ragged per-request memory to
        (per-lane mem_len marks each request's valid prefix)."""
        cfg = self.cfg
        if cfg.family == "vlm":
            return cfg.num_image_tokens, cfg.vision_dim
        if cfg.family == "encdec":
            return cfg.source_len, cfg.d_model
        return None

    @property
    def mixed_compact(self) -> bool:
        """Whether the mixed programs run their chunk sub-step only on
        the lanes holding a chunk (T._chunk_substep): everywhere except
        where the mesh shards the lane axis, since slicing one lane out
        of a sharded axis would add collectives to the hot loop."""
        return not shard_rules.lanes_sharded(self.mesh)

    def lane_closures(self, greedy: bool, n_lanes: Optional[int] = None):
        """Jitted continuous-batching closures (serve.scheduler), built
        lazily and CACHED PER ENGINE so every Scheduler constructed on
        this engine shares one set of compilations: ragged admission
        prefill(+first token), masked lane install, masked decode
        segment, lane reset. Keyed by (greedy, n_lanes): the segment
        closure bakes the sampling mode in, and under a mesh the lane
        count pins the sharding tables stamped on every closure (a
        single-device engine ignores n_lanes — shapes specialize per
        call as always). For cross-memory families (vlm/encdec) the
        admit/mixed closures take extra operands: the padded per-lane
        memory slab [B, S, feat] and its valid lengths mem_len [B].

        Every per-lane operand is LANE-ALIGNED (row i belongs to lane
        i) and installs are [B]-bool-mask where-selects, so with a mesh
        the lane axis shards over the data axes with NO cross-shard
        scatter or gather anywhere in the serving hot loop
        (docs/serving.md §Sharded serving)."""
        greedy = bool(greedy)
        if self.mesh is not None and n_lanes is None:
            raise ValueError(
                "a mesh-sharded Engine needs the lane count to build "
                "its sharding tables: call lane_closures(greedy, "
                "n_lanes)")
        cache_key = (greedy, n_lanes if self.mesh is not None else None)
        if cache_key in self._lane_closures:
            return self._lane_closures[cache_key]
        cfg = self.cfg
        serve, policy, impl = self.serve, self.policy, self.serve.attn_impl
        mem_key = self.mem_key
        compact = self.mixed_compact

        def _admit_core(params, gates, state, tok, keys, chunks, n_valid,
                        new_keys, lane_mask, extra):
            # the WHOLE admission is one program: fresh sub-state +
            # (cross-memory install +) ragged prefill + first tokens +
            # masked lane install — one dispatch per admission round
            # however many requests and chunks it packs. The grid is
            # lane-aligned (free lanes ride as all-zero-valid frozen
            # rows), so the install is a where-select that stays
            # shard-local on the lane axis
            k = chunks.shape[1]
            sub = T.init_decode_state(cfg, k, serve.budget)
            sub, h_last = T.prefill_chunk_loop(
                params, gates, cfg, chunks, n_valid, sub, policy, serve,
                extra_inputs=extra)
            logits = T.compute_logits(params, cfg, h_last)
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            state = T.install_lanes(state, sub, lane_mask)
            return (state, jnp.where(lane_mask, first, tok),
                    jnp.where(lane_mask[:, None], new_keys, keys))

        def _segment(params, gates, state, tok, keys, active, n_emitted,
                     max_new, eos, n_steps, n_real):
            # n_steps is STATIC (the scan length) but the scheduler
            # rounds it up to power-of-two BUCKETS and passes the
            # logical length as the TRACED n_real (tail steps masked
            # bit-identically), so cold-start compiles scale with
            # log2(decode_segment) buckets, not with every distinct
            # drain-split remainder length
            return T.decode_segment_loop(
                params, gates, cfg, state, tok, keys, active, n_emitted,
                max_new, eos, n_steps, policy, greedy=greedy,
                temperature=serve.temperature, attn_impl=impl,
                n_real=n_real)

        def _mixed_core(params, gates, state, tok, keys, active, n_emitted,
                        max_new, eos, chunks, chunk_valid, finish,
                        new_keys, mem_inputs, mem_install):
            # interleaved prefill/decode segment (SLO scheduling): the
            # admission prefill — cross-memory install included — rides
            # INSIDE the decode segment, one chunk per admitting lane
            # per step — one dispatch covers both, so admission never
            # pauses in-flight decodes
            return T.mixed_step_loop(
                params, gates, cfg, state, tok, keys, active, n_emitted,
                max_new, eos, chunks, chunk_valid, finish, new_keys,
                policy, serve, greedy=greedy,
                temperature=serve.temperature, attn_impl=impl,
                mem_inputs=mem_inputs, mem_install=mem_install,
                compact=compact)

        def _mixed_plain(params, gates, state, tok, keys, active,
                         n_emitted, max_new, eos, chunks, chunk_valid,
                         finish, new_keys):
            # mixed segment WITHOUT memory operands — the only mixed
            # closure for self-attention families, and the no-install
            # fast path for cross families (segments where no lane's
            # first chunk rides: skips re-running the encoder/vision
            # projection over the slab just to where-keep old state)
            return _mixed_core(params, gates, state, tok, keys, active,
                               n_emitted, max_new, eos, chunks,
                               chunk_valid, finish, new_keys, None, None)

        if mem_key is None:
            def _admit(params, gates, state, tok, keys, chunks, n_valid,
                       new_keys, lane_mask):
                return _admit_core(params, gates, state, tok, keys, chunks,
                                   n_valid, new_keys, lane_mask, None)

            _mixed = _mixed_plain
        else:
            def _admit(params, gates, state, tok, keys, chunks, n_valid,
                       new_keys, lane_mask, mem, mem_len):
                return _admit_core(params, gates, state, tok, keys, chunks,
                                   n_valid, new_keys, lane_mask,
                                   {mem_key: mem, "mem_len": mem_len})

            def _mixed(params, gates, state, tok, keys, active, n_emitted,
                       max_new, eos, chunks, chunk_valid, finish, new_keys,
                       mem, mem_len, install):
                return _mixed_core(params, gates, state, tok, keys, active,
                                   n_emitted, max_new, eos, chunks,
                                   chunk_valid, finish, new_keys,
                                   {mem_key: mem, "mem_len": mem_len},
                                   install)

        def _admit_prefix(params, gates, state, tok, keys, chunks, n_valid,
                          new_keys, lane_mask, sub0, capture_chunk):
            # prefix-cache admission (docs/serving.md §Prefix cache):
            # sub0 carries the lanes' INITIAL sub-state — cached slabs
            # at hit lanes' rows (their per-lane t already at the
            # prefix boundary, so chunk positions continue from it),
            # fresh rows elsewhere — and the grid holds only each
            # request's NOVEL SUFFIX chunks. capture_chunk[lane] = j>0
            # snapshots that lane's state right after its j-th suffix
            # chunk (its capture boundary) via the scan's snap carry;
            # the host inserts those rows into the trie. Still ONE
            # dispatch per admission round: hits and captures ride the
            # same program that cold admission uses.
            sub, h_last, snap = T.prefill_chunk_loop(
                params, gates, cfg, chunks, n_valid, sub0, policy, serve,
                capture_chunk=capture_chunk)
            logits = T.compute_logits(params, cfg, h_last)
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            state = T.install_lanes(state, sub, lane_mask)
            return (state, jnp.where(lane_mask, first, tok),
                    jnp.where(lane_mask[:, None], new_keys, keys), snap)

        def _admit_capture(params, gates, state, tok, keys, chunks, n_valid,
                           new_keys, lane_mask, capture_chunk):
            # capture-only variant (no hits this round): fresh
            # sub-state built on device, so the host skips shipping a
            # [n_lanes]-row sub0 it would only fill with zeros
            sub0 = T.init_decode_state(cfg, chunks.shape[1], serve.budget)
            return _admit_prefix(params, gates, state, tok, keys, chunks,
                                 n_valid, new_keys, lane_mask, sub0,
                                 capture_chunk)

        def _prefix_install(state, sub, lane_mask):
            # interleaved-mode prefix hit: where-select the cached
            # slabs (lane-aligned rows) into their lanes BEFORE the
            # mixed segment streams the suffix chunks. tok/keys need no
            # install here — the mixed scan writes both at the lane's
            # finish transition.
            return T.install_lanes(state, sub, lane_mask)

        def _spec_segment(params, gates, state, tok, keys, active,
                          n_emitted, max_new, eos, hist, n_rounds, n_real):
            # speculative decode segment (docs/serving.md §Speculative
            # decoding): n_rounds draft/verify rounds, each committing
            # 1..spec_k+1 tokens per live lane in ONE chunk-shaped
            # dispatch. Same pow2 bucketing contract as _segment, in
            # ROUND units.
            return T.spec_decode_segment_loop(
                params, gates, cfg, state, tok, keys, active, n_emitted,
                max_new, eos, hist, n_rounds, policy,
                spec_k=serve.spec_k, attn_impl=impl, n_real=n_real)

        def _spec_mixed_core(params, gates, state, tok, keys, active,
                             n_emitted, max_new, eos, hist, chunks,
                             chunk_valid, finish, new_keys, mem_inputs,
                             mem_install):
            return T.spec_mixed_step_loop(
                params, gates, cfg, state, tok, keys, active, n_emitted,
                max_new, eos, hist, chunks, chunk_valid, finish,
                new_keys, policy, serve, spec_k=serve.spec_k,
                attn_impl=impl, mem_inputs=mem_inputs,
                mem_install=mem_install, compact=compact)

        def _spec_mixed_plain(params, gates, state, tok, keys, active,
                              n_emitted, max_new, eos, hist, chunks,
                              chunk_valid, finish, new_keys):
            return _spec_mixed_core(params, gates, state, tok, keys, active,
                                    n_emitted, max_new, eos, hist, chunks,
                                    chunk_valid, finish, new_keys,
                                    None, None)

        if mem_key is None:
            _spec_mixed = _spec_mixed_plain
        else:
            def _spec_mixed(params, gates, state, tok, keys, active,
                            n_emitted, max_new, eos, hist, chunks,
                            chunk_valid, finish, new_keys, mem, mem_len,
                            install):
                return _spec_mixed_core(
                    params, gates, state, tok, keys, active, n_emitted,
                    max_new, eos, hist, chunks, chunk_valid, finish,
                    new_keys, {mem_key: mem, "mem_len": mem_len}, install)

        def _extract(state, tok, keys):
            # swap-out / checkpoint: ONE dispatch commits the complete
            # movable state + carried tokens + RNG chains; the host
            # slices out the victim lanes' rows (scheduler._snap_row).
            # Identity on purpose: the old per-victim index gather
            # compiled a cross-lane gather an SPMD partitioner must
            # lower as a cross-shard collective — full-B extract keeps
            # the program shard-local and moves the same bytes (the
            # gather operand was already padded to n_lanes rows). state
            # is NOT donated: the source lanes live on.
            return state, tok, keys

        def _resume(state, tok, keys, sub, sub_tok, sub_keys, lane_mask):
            # swap-in: host LaneSnapshots arrive LANE-ALIGNED (row lane
            # of sub is that lane's snapshot; other rows carry filler
            # the mask drops) — a where-select install, bit-identical
            # to never having left the device, shard-local under a mesh
            state = T.install_lanes(state, sub, lane_mask)
            return (state, jnp.where(lane_mask, sub_tok, tok),
                    jnp.where(lane_mask[:, None], sub_keys, keys))

        # ---- sharding tables (mesh-native serving, docs/serving.md
        # §Sharded serving): with a mesh, EVERY closure is stamped with
        # explicit in_shardings/out_shardings — decode state by the
        # state_spec rules (lane axis over the data axes, heads/slots
        # over "model"), per-lane operands by lane_operand_spec (lane
        # axis only; broadcast to every "model" shard), scalars
        # replicated. Donation is preserved: donated state in/out carry
        # the identical sharding tree, so buffers are reused in place.
        # The weights' own shardings are prepended by _weighted.
        sh = {}
        if self.mesh is not None:
            mesh = self.mesh
            st = shard_rules.state_shardings(mesh, jax.eval_shape(
                lambda: T.init_decode_state(cfg, n_lanes, serve.budget)))

            def lane(nd, axis=0):
                shape = tuple(n_lanes if i == axis else 1
                              for i in range(nd))
                return shard_rules.lane_operand_sharding(mesh, shape,
                                                         axis)

            l1, l2, l3 = lane(1), lane(2), lane(3)
            g2, g3 = lane(2, axis=1), lane(3, axis=1)
            rep = NamedSharding(mesh, P())
            tl = (st, l1, l2)                       # (state, tok, keys)
            seg_out = tl + (l1, l1, l2, l2, l1)
            spec_out = seg_out + (l2, l1, l1)
            mem_tail = (l3, l1) if mem_key is not None else ()
            mixed_tail = (l3, l1, l1) if mem_key is not None else ()
            mixed_in = tl + (l1, l1, l1, l1, g3, g2, g2, l2)
            spec_mixed_in = tl + (l1, l1, l1, l1, l2, g3, g2, g2, l2)
            sh = {
                "admit": (tl + (g3, g2, l2, l1) + mem_tail, tl),
                # static n_steps/n_rounds excluded: in_shardings cover
                # the DYNAMIC args only
                "segment": (tl + (l1, l1, l1, l1, rep), seg_out),
                "mixed": (mixed_in + mixed_tail, seg_out),
                "mixed_nomem": (mixed_in, seg_out),
                "reset": ((st, l1), st),
                "extract": (tl, tl),
                "resume": (tl + (st, l1, l2, l1), tl),
                "scrub": ((st, l1), st),
                "admit_prefix": (tl + (g3, g2, l2, l1, st, l1),
                                 tl + (st,)),
                "admit_capture": (tl + (g3, g2, l2, l1, l1),
                                  tl + (st,)),
                "prefix_install": ((st, st, l1), st),
                "spec_segment": (tl + (l1, l1, l1, l1, l2, rep),
                                 spec_out),
                "spec_mixed": (spec_mixed_in + mixed_tail, spec_out),
                "spec_mixed_nomem": (spec_mixed_in, spec_out),
            }

        def _jit(name, fn, donate=()):
            # weight-free programs (lane reset / extract / install)
            kw = {}
            if donate:
                kw["donate_argnums"] = donate
            if name in sh:
                kw["in_shardings"], kw["out_shardings"] = sh[name]
            return jax.jit(fn, **kw)

        def _wjit(name, fn, donate=(), static=()):
            return self._weighted(fn, donate=donate, static=static,
                                  shardings=sh.get(name))

        mixed_jit = _wjit("mixed", _mixed, donate=(0,))
        # speculative closures exist only where speculation is legal:
        # spec_k > 0 and GREEDY (stochastic verification cannot
        # reproduce the per-lane key chain bit-identically)
        spec_on = serve.spec_k > 0 and greedy
        spec_mixed_jit = (_wjit("spec_mixed", _spec_mixed, donate=(0,))
                          if spec_on else None)
        closures = {
            "admit": _wjit("admit", _admit, donate=(0,)),
            "segment": _wjit("segment", _segment, static=(7,),
                             donate=(0,)),
            "mixed": mixed_jit,
            # same jit object for non-cross families: _mixed IS the
            # plain closure there, so no second compilation cache
            "mixed_nomem": (mixed_jit if mem_key is None else
                            _wjit("mixed_nomem", _mixed_plain,
                                  donate=(0,))),
            "reset": _jit("reset", T.reset_lanes, donate=(0,)),
            "extract": _jit("extract", _extract),
            "resume": _jit("resume", _resume, donate=(0,)),
            # quarantine: reset + zero the poisoned lanes' K/V payload
            "scrub": _jit("scrub", T.scrub_lanes, donate=(0,)),
            # prefix-cache closures — self-attention families only; the
            # scheduler bypasses the cache for cross-memory families
            # (a cached slab would not carry the encoder/vision memory
            # its suffix chunks cross-attend into)
            "admit_prefix": (_wjit("admit_prefix", _admit_prefix,
                                   donate=(0,))
                             if mem_key is None else None),
            "admit_capture": (_wjit("admit_capture", _admit_capture,
                                    donate=(0,))
                              if mem_key is None else None),
            "prefix_install": (_jit("prefix_install", _prefix_install,
                                    donate=(0,))
                               if mem_key is None else None),
            "spec_segment": (_wjit("spec_segment", _spec_segment,
                                   static=(8,), donate=(0,))
                             if spec_on else None),
            "spec_mixed": spec_mixed_jit,
            "spec_mixed_nomem": (
                spec_mixed_jit if (mem_key is None or not spec_on) else
                _wjit("spec_mixed_nomem", _spec_mixed_plain,
                      donate=(0,))),
        }
        self._lane_closures[cache_key] = closures
        return closures

    def _first_token(self, h_last):
        """Greedy token from the prefill's last hidden state [B,d]."""
        return _greedy_token(self.params, self.cfg, h_last)

    def _weighted(self, fn, *, donate=(), static=(), shardings=None):
        """jit `fn(params, gates, *args)` and bind this engine's
        weights: argument indices (donate/static) and `shardings`
        (in, out) name the args AFTER the weights."""
        kw = {}
        if static:
            kw["static_argnums"] = tuple(i + 2 for i in static)
        if donate:
            kw["donate_argnums"] = tuple(i + 2 for i in donate)
        if shardings is not None:
            ins, outs = shardings
            kw["in_shardings"] = tuple(self._weight_shardings) + ins
            kw["out_shardings"] = outs
        return _Weighted(self, jax.jit(fn, **kw))

    # ------------------------------------------------------------ state

    def fresh_state(self, batch: int):
        state = T.init_decode_state(self.cfg, batch, self.serve.budget)
        if self.mesh is not None:
            # commit the lane state to its mesh layout up front so the
            # first donated closure call starts from the same placement
            # it will produce
            state = jax.device_put(
                state, shard_rules.state_shardings(self.mesh, state))
        return state

    def fresh_lane_row(self):
        """Host-side single-lane fresh decode-state row (cached after
        the first call) — the filler the scheduler stacks at non-hit
        rows of a prefix-admission sub0, shape-compatible with the
        single-row slabs PrefixCache stores."""
        if self._fresh_row is None:
            self._fresh_row = jax.device_get(
                T.init_decode_state(self.cfg, 1, self.serve.budget))
        return self._fresh_row

    # ---------------------------------------------------------- prefill

    def prefill(self, tokens, extra_inputs=None, chunked: bool = False,
                fused: Optional[bool] = None):
        """tokens: [B,T] np/jnp. Returns (state, last_hidden).

        Chunked path: the prompt is padded up to a whole number of
        prefill_chunk-sized chunks (tail positions masked), so every
        chunk — remainder included — shares ONE closure shape. With
        fused (default: serve_cfg.fused) the whole per-chunk pipeline
        runs under one lax.scan dispatch (T.prefill_chunk_loop);
        fused=False keeps the eager one-dispatch-per-chunk reference.
        chunked=True ALWAYS runs the per-chunk compression pipeline,
        even for prompts within one chunk — it is the parity oracle for
        the continuous-batching scheduler, whose ragged admission grid
        runs every prompt (short ones included) through the chunk
        path."""
        tokens = jnp.asarray(tokens)
        B, Tn = tokens.shape
        state = self.fresh_state(B)
        extra = extra_inputs or {}
        C = self.serve.prefill_chunk
        if not chunked:
            self.dispatch_count += 1
            return self._prefill(tokens, state, extra)
        fused = self.serve.fused if fused is None else fused
        n_chunks = -(-Tn // C)
        pad = n_chunks * C - Tn
        if pad:
            tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
        n_valid = np.full((n_chunks,), C, np.int32)
        n_valid[-1] = C - pad
        if fused:
            chunks = jnp.moveaxis(tokens.reshape(B, n_chunks, C), 1, 0)
            self.dispatch_count += 1
            return self._prefill_chunk_loop(chunks, jnp.asarray(n_valid),
                                            state, extra)
        h_last = None
        # extra is passed per chunk: install_memory re-writes the same
        # cross-attn memory K/V each call (idempotent), keeping the
        # eager loop bit-identical to the fused scan's one-time install
        for i in range(n_chunks):
            self.dispatch_count += 1
            state, h_last = self._prefill_chunk(
                tokens[:, i * C:(i + 1) * C],
                jnp.asarray(n_valid[i]), state, extra)
        return state, h_last

    # ----------------------------------------------------------- decode

    def generate(self, tokens, max_new: int, extra_inputs=None,
                 chunked: bool = False, greedy: bool = True, seed: int = 0,
                 fused: Optional[bool] = None):
        """Returns dict with generated ids [B, max_new] and timing.
        fused=None defers to serve_cfg.fused; fused=False runs the eager
        per-token reference loop (one dispatch per token)."""
        fused = self.serve.fused if fused is None else fused
        state, h_last = self.prefill(tokens, extra_inputs, chunked,
                                     fused=fused)
        key = jax.random.PRNGKey(seed)
        greedy = greedy or self.serve.temperature == 0.0
        if fused:
            t0 = time.time()
            self.dispatch_count += 1
            state, ids = self._decode_loop(state, h_last, key, max_new,
                                           greedy)
            jax.block_until_ready(ids)
            dt = time.time() - t0
            return {"ids": np.asarray(ids), "decode_sec": dt,
                    "tok_per_sec": ids.size / max(dt, 1e-9)}
        tok = self._first_token(h_last)
        outs = []
        t0 = time.time()
        for i in range(max_new):
            outs.append(tok)
            self.dispatch_count += 1
            state, logits = self._decode(state, tok)
            tok, key = T.sample_token(logits, greedy=greedy,
                                      temperature=self.serve.temperature,
                                      key=key)
        jax.block_until_ready(tok)
        dt = time.time() - t0
        ids = jnp.stack(outs, axis=1)
        return {"ids": np.asarray(ids), "decode_sec": dt,
                "tok_per_sec": ids.size / max(dt, 1e-9)}

    def teacher_forced_accuracy(self, tokens, labels, extra_inputs=None,
                                chunked: bool = False):
        """Feed gold tokens; measure argmax-match on positions where
        labels >= 0 (the benchmark metric: answer-span accuracy under
        eviction). tokens/labels: [B,T]. Runs as one fused scan after
        the prefill (2 dispatches total for the unchunked path)."""
        tokens = jnp.asarray(tokens)
        labels = np.asarray(labels)
        B, Tn = tokens.shape
        first_label = int(np.min(np.where(labels >= 0)[1]))
        prefix_len = max(first_label, 1)
        state, h_last = self.prefill(tokens[:, :prefix_len], extra_inputs,
                                     chunked)
        if prefix_len < Tn:
            self.dispatch_count += 1
            state, preds = self._tf_loop(state, h_last,
                                         tokens[:, prefix_len:])
        else:
            preds = self._first_token(h_last)[:, None]
        # preds[:, i] predicts position prefix_len-1+i; labels[:, t] is
        # supervised by the prediction made at position t
        preds = np.asarray(preds)
        labs = labels[:, prefix_len - 1:]
        sel = labs >= 0
        correct = int((preds[sel] == labs[sel]).sum())
        counted = int(sel.sum())
        return correct / max(counted, 1)


def build_engine(cfg, params, gate_params, mesh=None,
                 **serve_kwargs) -> Engine:
    return Engine(cfg, params, gate_params, ServeConfig(**serve_kwargs),
                  mesh=mesh)
