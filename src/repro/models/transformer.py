"""Generic stacked model covering all six families.

Layers are grouped into repeats of cfg.attn_pattern and the repeats are
driven by lax.scan over stacked params (O(1) HLO size regardless of
depth — essential for 100-layer configs on a single-core compiler).
Remainder layers (num_layers % len(pattern)) run unrolled as "tail".

Public entry points:
  init_params / init_gate_params
  forward_train(...)          -> (hidden, aux)   [train + distillation]
  compute_logits(...)         -> [B,T,Vp] f32 (small-scale only)
  init_decode_state(...)      -> state pytree
  prefill(...)                -> (state, last_hidden)
  decode_step(...)            -> (state, logits [B,Vp])
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import blocks
from repro.models.common import rmsnorm_apply, rmsnorm_init, to_dtype

ZERO = lambda: jnp.zeros((), jnp.float32)


def _unit_and_counts(cfg):
    unit = cfg.attn_pattern
    U = len(unit)
    R = cfg.num_layers // U
    tail = tuple(unit[: cfg.num_layers % U])
    return unit, U, R, tail


# ------------------------------------------------------------------ init


def init_params(key, cfg):
    dtype = to_dtype(cfg.dtype)
    unit, U, R, tail = _unit_and_counts(cfg)
    keys = jax.random.split(key, 8)
    Vp = cfg.padded_vocab
    params = {
        "embed": (jax.random.normal(keys[0], (Vp, cfg.d_model)) * 0.02
                  ).astype(dtype),
        "final_norm": rmsnorm_init(cfg.d_model),
        "unembed": {"w": (jax.random.normal(keys[1],
                                            (cfg.d_model, Vp))
                          / np.sqrt(cfg.d_model)).astype(dtype)},
    }

    def init_unit(k):
        ks = jax.random.split(k, U)
        return tuple(blocks.init_block(ks[i], cfg, unit[i])
                     for i in range(U))

    if R > 0:
        params["layers"] = jax.vmap(init_unit)(jax.random.split(keys[2], R))
    else:
        params["layers"] = None
    tks = jax.random.split(keys[3], max(len(tail), 1))
    params["tail"] = tuple(blocks.init_block(tks[i], cfg, tail[i])
                           for i in range(len(tail)))

    if cfg.family == "vlm":
        params["vis_proj"] = {
            "w": (jax.random.normal(keys[4], (cfg.vision_dim, cfg.d_model))
                  / np.sqrt(cfg.vision_dim)).astype(dtype)}
    if cfg.family == "encdec":
        ekeys = jax.random.split(keys[5], cfg.encoder_layers)

        def init_enc_unit(k):
            return (blocks.init_block(k, cfg, "global"),)

        params["encoder"] = {
            "layers": jax.vmap(init_enc_unit)(ekeys),
            "final_norm": rmsnorm_init(cfg.d_model),
        }
    return params


def init_gate_params(key, cfg):
    """Retention gates mirroring the layer stack (None where the kind has
    no growing KV cache)."""
    unit, U, R, tail = _unit_and_counts(cfg)

    def init_unit(k):
        ks = jax.random.split(k, U)
        return tuple(blocks.init_block_gate(ks[i], cfg, unit[i])
                     for i in range(U))

    gates = {}
    if R > 0:
        gates["layers"] = jax.vmap(init_unit)(jax.random.split(key, R))
    else:
        gates["layers"] = None
    tks = jax.random.split(jax.random.fold_in(key, 1), max(len(tail), 1))
    gates["tail"] = tuple(blocks.init_block_gate(tks[i], cfg, tail[i])
                          for i in range(len(tail)))
    return gates


def num_gate_layers(cfg) -> int:
    return sum(1 for k in cfg.layer_kinds()
               if cfg.trimkv and k in ("global", "local", "cross"))


# --------------------------------------------------------------- helpers


def _take_unit(stacked, i):
    return jax.tree.map(lambda a: a[i], stacked)


def _encoder_forward(enc_params, cfg, source_embeds, mem_len=None):
    """Bidirectional encoder over stub frame embeddings [B,S,d].
    mem_len: optional [B] valid frame counts for a ragged batch padded
    to a shared S — padded frames are masked out of every (non-causal)
    attention read, so each row's valid prefix is bit-identical to
    encoding that row alone at its own length. Padded ROWS of the
    output are garbage; downstream cross-attention masks them by the
    same mem_len."""
    kv_positions = None
    if mem_len is not None:
        S = source_embeds.shape[1]
        iota = jnp.arange(S, dtype=jnp.int32)[None, :]
        kv_positions = jnp.where(
            iota < jnp.asarray(mem_len, jnp.int32)[:, None], iota, -1)

    def body(h, up):
        h, _ = blocks.apply_block_train(up[0], None, cfg, "global", h,
                                        causal=False,
                                        kv_positions=kv_positions)
        return h, None

    h, _ = jax.lax.scan(body, source_embeds, enc_params["layers"],
                        unroll=enc_params["layers"] is not None and
                        cfg.unroll_layers and
                        jax.tree.leaves(enc_params["layers"])[0].shape[0]
                        or 1)
    return rmsnorm_apply(enc_params["final_norm"], h, cfg.norm_eps)


def _memory_from_inputs(params, cfg, extra_inputs):
    """Project stub frontend embeddings into d_model memory tokens.

    Returns (memory [B,S,d], mem_len [B]) — or (None, None) for
    families without cross-attention memory. extra_inputs may carry a
    per-row "mem_len" ([B] int32) marking each row's valid length
    inside a padded [B,S,feat] batch (ragged continuous-batching
    admission); without it every row is fully valid. Rows beyond
    mem_len are masked out of the encoder (so padding never
    contaminates real frames) and out of every cross-attention read."""
    mem_len = extra_inputs.get("mem_len")
    if mem_len is not None:
        mem_len = jnp.asarray(mem_len, jnp.int32)
    if cfg.family == "vlm":
        vis = extra_inputs["vision_embeds"]            # [B,S,vision_dim]
        memory = (vis @ params["vis_proj"]["w"]).astype(to_dtype(cfg.dtype))
    elif cfg.family == "encdec":
        src = extra_inputs["source_embeds"]            # [B,S,d_model]
        memory = _encoder_forward(params["encoder"], cfg,
                                  src.astype(to_dtype(cfg.dtype)),
                                  mem_len=mem_len)
    else:
        return None, None
    if mem_len is None:
        mem_len = jnp.full((memory.shape[0],), memory.shape[1], jnp.int32)
    return memory, mem_len


# ----------------------------------------------------------------- train


def forward_train(params, gate_params, cfg, tokens, *, gated=False,
                  cap_M=None, extra_inputs=None, remat=False):
    """tokens: [B,T] -> (hidden [B,T,d], aux).

    aux = {"cap": summed per-layer capacity losses, "router": summed MoE
    aux, "n_gate_layers": python int}. When `gated`, attention uses the
    retention bias (student); otherwise vanilla attention (teacher).
    `remat` checkpoints each layer-unit of the scan (stores only the
    inter-unit residual stream — required to fit 4k-seq training of the
    large configs in 16 GB HBM; DESIGN.md §5).
    """
    unit, U, R, tail = _unit_and_counts(cfg)
    extra_inputs = extra_inputs or {}
    memory, mem_len = _memory_from_inputs(params, cfg, extra_inputs)
    h = jnp.take(params["embed"], tokens, axis=0)

    def unit_body(h, xs):
        up, ug = xs
        cap, router = ZERO(), ZERO()
        for i, kind in enumerate(unit):
            g = ug[i] if ug is not None else None
            h, aux = blocks.apply_block_train(
                up[i], g, cfg, kind, h, gated=gated, cap_M=cap_M,
                memory=memory, mem_len=mem_len)
            cap = cap + aux["cap"]
            router = router + aux["router"]
        return h, (cap, router)

    cap_total, router_total = ZERO(), ZERO()
    body = jax.checkpoint(unit_body) if remat else unit_body
    if R > 0:
        glayers = (gate_params or {}).get("layers")
        h, (caps, routers) = jax.lax.scan(
            body, h, (params["layers"], glayers),
            unroll=R if cfg.unroll_layers else 1)
        cap_total += jnp.sum(caps)
        router_total += jnp.sum(routers)
    for i, kind in enumerate(tail):
        g = (gate_params or {}).get("tail", (None,) * len(tail))[i]
        h, aux = blocks.apply_block_train(params["tail"][i], g, cfg, kind,
                                          h, gated=gated, cap_M=cap_M,
                                          memory=memory, mem_len=mem_len)
        cap_total += aux["cap"]
        router_total += aux["router"]
    h = rmsnorm_apply(params["final_norm"], h, cfg.norm_eps)
    return h, {"cap": cap_total, "router": router_total,
               "n_gate_layers": num_gate_layers(cfg)}


def compute_logits(params, cfg, hidden):
    """[B,T,d] -> [B,T,Vp] f32 with padded-vocab masking. Only for
    small-scale paths; large-scale losses are chunked (core.losses)."""
    logits = (hidden @ params["unembed"]["w"]).astype(jnp.float32)
    mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
    return jnp.where(mask, logits, -1e30)


# ---------------------------------------------------------------- decode


def init_decode_state(cfg, batch: int, budget: int):
    """Decode state pytree. `t` is a PER-LANE [batch] clock: under
    continuous batching every lane (request slot) runs at its own
    position; the lock-step engine paths simply keep all entries
    equal."""
    dtype = to_dtype(cfg.dtype)
    unit, U, R, tail = _unit_and_counts(cfg)

    def one(kind):
        return blocks.init_block_state(cfg, kind, batch, budget, dtype)

    state = {"t": jnp.zeros((batch,), jnp.int32)}
    if R > 0:
        unit_state = tuple(one(k) for k in unit)
        state["layers"] = jax.tree.map(
            lambda a: jnp.tile(a[None], (R,) + (1,) * a.ndim), unit_state)
    else:
        state["layers"] = None
    state["tail"] = tuple(one(k) for k in tail)
    return state


def prefill(params, gate_params, cfg, tokens, state, policy, serve_cfg, *,
            extra_inputs=None):
    """Single-shot prefill of tokens [B,T] into `state` (assumed fresh).
    Returns (state, last_hidden [B,d])."""
    unit, U, R, tail = _unit_and_counts(cfg)
    extra_inputs = extra_inputs or {}
    memory, mem_len = _memory_from_inputs(params, cfg, extra_inputs)
    h = jnp.take(params["embed"], tokens, axis=0)
    T = tokens.shape[1]
    attn_impl = getattr(serve_cfg, "attn_impl", "xla")

    def unit_body(h, xs):
        up, ug, st = xs
        new_states = []
        for i, kind in enumerate(unit):
            g = ug[i] if ug is not None else None
            h, ns, _ = blocks.apply_block_prefill(
                up[i], g, cfg, kind, h, st[i], policy=policy,
                budget=serve_cfg.budget, memory=memory, mem_len=mem_len,
                obs_window=serve_cfg.obs_window, attn_impl=attn_impl)
            new_states.append(ns)
        return h, tuple(new_states)

    new_state = {"t": jnp.full((tokens.shape[0],), T, jnp.int32)}
    if R > 0:
        glayers = (gate_params or {}).get("layers")
        h, stacked = jax.lax.scan(
            unit_body, h, (params["layers"], glayers, state["layers"]),
            unroll=R if cfg.unroll_layers else 1)
        new_state["layers"] = stacked
    else:
        new_state["layers"] = None
    new_tail = []
    for i, kind in enumerate(tail):
        g = (gate_params or {}).get("tail", (None,) * len(tail))[i]
        h, ns, _ = blocks.apply_block_prefill(
            params["tail"][i], g, cfg, kind, h, state["tail"][i],
            policy=policy, budget=serve_cfg.budget, memory=memory,
            mem_len=mem_len, obs_window=serve_cfg.obs_window,
            attn_impl=attn_impl)
        new_tail.append(ns)
    new_state["tail"] = tuple(new_tail)
    h = rmsnorm_apply(params["final_norm"], h, cfg.norm_eps)
    return new_state, h[:, -1]


def _prefill_chunk_step(params, gate_params, cfg, tokens, state, policy,
                        serve_cfg, n_valid=None):
    """One chunk of the chunked-prefill pipeline: embed -> per-layer
    chunk attention + top-M eviction merge -> final norm. tokens: [B,C];
    n_valid: real-token count — None (= all C), scalar, or [B] for a
    ragged batch where each request marks its own tail (the padded tail
    positions are masked everywhere; rows with n_valid 0 are frozen
    bit-identically — see blocks.apply_block_prefill_chunk).
    Cross-attention memory (xk/xv + per-lane mem_len mask) is read
    from the state — install it once with install_memory before the
    first chunk. Returns (new_state, h_last [B,d] — each row's LAST
    REAL token's hidden; rows with an empty chunk return garbage there,
    callers carry the previous value — see prefill_chunk_loop)."""
    unit, U, R, tail = _unit_and_counts(cfg)
    h = jnp.take(params["embed"], tokens, axis=0)
    t0 = state["t"]
    C = tokens.shape[1]
    attn_impl = getattr(serve_cfg, "attn_impl", "xla")

    def unit_body(h, xs):
        up, ug, st = xs
        new_states = []
        for i, kind in enumerate(unit):
            g = ug[i] if ug is not None else None
            h, ns, _ = blocks.apply_block_prefill_chunk(
                up[i], g, cfg, kind, h, st[i], t0, policy=policy,
                obs_window=serve_cfg.obs_window,
                n_valid=n_valid, attn_impl=attn_impl)
            new_states.append(ns)
        return h, tuple(new_states)

    nv = C if n_valid is None else n_valid
    new_state = {"t": t0 + nv}
    if R > 0:
        glayers = (gate_params or {}).get("layers")
        h, stacked = jax.lax.scan(
            unit_body, h, (params["layers"], glayers, state["layers"]),
            unroll=R if cfg.unroll_layers else 1)
        new_state["layers"] = stacked
    else:
        new_state["layers"] = None
    new_tail = []
    for i, kind in enumerate(tail):
        g = (gate_params or {}).get("tail", (None,) * len(tail))[i]
        h, ns, _ = blocks.apply_block_prefill_chunk(
            params["tail"][i], g, cfg, kind, h, state["tail"][i], t0,
            policy=policy, obs_window=serve_cfg.obs_window,
            n_valid=n_valid, attn_impl=attn_impl)
        new_tail.append(ns)
    new_state["tail"] = tuple(new_tail)
    h = rmsnorm_apply(params["final_norm"], h, cfg.norm_eps)
    if n_valid is None:
        h_last = h[:, -1]
    elif jnp.ndim(n_valid) == 1:
        # ragged: each row reads its own last real token
        ix = jnp.clip(n_valid - 1, 0, C - 1).astype(jnp.int32)
        h_last = jnp.take_along_axis(h, ix[:, None, None], axis=1)[:, 0]
    else:
        h_last = jax.lax.dynamic_index_in_dim(h, nv - 1, axis=1,
                                              keepdims=False)
    return new_state, h_last


def _lane_row(leaf, at):
    """Rows of `leaf` at index prefix `at` (int32 scalars, the lane
    index last) as a batch-1 block: [R, B, ...] at (r, lane) or
    [B, ...] at (lane,) -> [1, ...]."""
    k = len(at)
    row = jax.lax.dynamic_slice(leaf, at + (0,) * (leaf.ndim - k),
                                (1,) * k + leaf.shape[k:])
    return row.reshape((1,) + leaf.shape[k:])


def _put_lane_row(leaf, row, at):
    """Inverse of _lane_row: write the batch-1 block back in place."""
    k = len(at)
    return jax.lax.dynamic_update_slice(
        leaf, row.reshape((1,) * k + leaf.shape[k:]),
        at + (0,) * (leaf.ndim - k))


def _prefill_chunk_lane(params, gate_params, cfg, tokens, state, lane,
                        n_valid, policy, serve_cfg):
    """_prefill_chunk_step for ONE lane of the B-lane state, in place:
    tokens [1,C], n_valid [1] (> 0), lane a traced int32 index. Each
    layer slices the lane's rows of its block state, runs the chunk at
    batch 1 and writes the rows back (dynamic_update_slice on the
    carried stack), so no other lane is read or written and no
    whole-stack copy of the state is made. Returns (state, h_last
    [1,d])."""
    unit, U, R, tail = _unit_and_counts(cfg)
    h = jnp.take(params["embed"], tokens, axis=0)
    C = tokens.shape[1]
    t0 = jax.lax.dynamic_slice_in_dim(state["t"], lane, 1)
    attn_impl = getattr(serve_cfg, "attn_impl", "xla")

    def block(p, g, kind, h, st, at):
        h, ns, _ = blocks.apply_block_prefill_chunk(
            p, g, cfg, kind, h, jax.tree.map(lambda a: _lane_row(a, at), st),
            t0, policy=policy, obs_window=serve_cfg.obs_window,
            n_valid=n_valid, attn_impl=attn_impl)
        return h, jax.tree.map(lambda a, n: _put_lane_row(a, n, at), st, ns)

    def unit_body(carry, xs):
        h, layers = carry
        up, ug, r = xs
        new = []
        for i, kind in enumerate(unit):
            g = ug[i] if ug is not None else None
            h, ns = block(up[i], g, kind, h, layers[i], (r, lane))
            new.append(ns)
        return (h, tuple(new)), None

    new_state = {"t": jax.lax.dynamic_update_slice_in_dim(
        state["t"], t0 + n_valid, lane, 0)}
    if R > 0:
        glayers = (gate_params or {}).get("layers")
        (h, new_state["layers"]), _ = jax.lax.scan(
            unit_body, (h, state["layers"]),
            (params["layers"], glayers, jnp.arange(R, dtype=jnp.int32)),
            unroll=R if cfg.unroll_layers else 1)
    else:
        new_state["layers"] = None
    new_tail = []
    for i, kind in enumerate(tail):
        g = (gate_params or {}).get("tail", (None,) * len(tail))[i]
        h, ns = block(params["tail"][i], g, kind, h, state["tail"][i],
                      (lane,))
        new_tail.append(ns)
    new_state["tail"] = tuple(new_tail)
    h = rmsnorm_apply(params["final_norm"], h, cfg.norm_eps)
    ix = jnp.clip(n_valid - 1, 0, C - 1).astype(jnp.int32)
    return new_state, jnp.take_along_axis(h, ix[:, None, None], axis=1)[:, 0]


def _chunk_substep(params, gate_params, cfg, tokens, state, policy,
                   serve_cfg, n_valid, compact):
    """The chunk (prefill) sub-step of the mixed programs: tokens
    [B,C], n_valid [B] (0 = no chunk for that lane this step). Returns
    (state, h_last [B,d]); h_last rows of lanes without a chunk are
    meaningless.

    compact=False runs _prefill_chunk_step over the whole lane-aligned
    [B,C] grid (zero-valid rows computed, then frozen). compact=True
    runs only the lanes holding a chunk: a fori_loop with a traced trip
    count (the number of such lanes) runs one lane's [1,C] chunk per
    pass (_prefill_chunk_lane), so every shape is fixed whatever the
    admission count, the other lanes are never computed and stay
    bit-identical, and each pass writes its h_last row into the [B,d]
    buffer. A one-lane [1,C] chunk at C in the hundreds already fills
    the matrix unit, so one lane per pass loses little to batching.
    Lane-sharded meshes take compact=False: slicing a lane out of a
    sharded lane axis would put collectives in the hot loop."""
    if not compact:
        return _prefill_chunk_step(params, gate_params, cfg, tokens, state,
                                   policy, serve_cfg, n_valid=n_valid)
    B = tokens.shape[0]
    has = n_valid > 0
    lanes = jnp.nonzero(has, size=B, fill_value=0)[0].astype(jnp.int32)

    def one(i, carry):
        state, h_last = carry
        lane = lanes[i]
        state, h = _prefill_chunk_lane(
            params, gate_params, cfg,
            jax.lax.dynamic_slice_in_dim(tokens, lane, 1), state, lane,
            jax.lax.dynamic_slice_in_dim(n_valid, lane, 1), policy,
            serve_cfg)
        return state, jax.lax.dynamic_update_slice_in_dim(h_last, h, lane,
                                                           0)

    h0 = jnp.zeros((B, cfg.d_model), params["embed"].dtype)
    return jax.lax.fori_loop(0, jnp.sum(has.astype(jnp.int32)), one,
                             (state, h0))


def _finish_first(params, cfg, fin, h_last):
    """The prefill -> decode transition's greedy first token and logit
    health for lanes finishing their prompt (fin [B] bool), from their
    last prompt token's hidden h_last [B,d]. The full-vocab projection
    only runs on steps where some lane finishes."""
    def first_and_health(h):
        lg = compute_logits(params, cfg, h)
        return (jnp.argmax(lg, axis=-1).astype(jnp.int32),
                jnp.all(jnp.isfinite(lg), axis=-1))

    return jax.lax.cond(
        jnp.any(fin), first_and_health,
        lambda h: (jnp.zeros((h.shape[0],), jnp.int32),
                   jnp.ones((h.shape[0],), bool)),
        h_last)


def install_memory(params, cfg, state, memory, mem_len, lanes_mask=None):
    """Write cross-attention memory K/V into every cross layer's state:
    xk/xv from make_memory_kv(memory) and the per-lane valid length
    mem_len ([B] int32 — slots >= mem_len are masked out of every
    cross-attention read). memory: [B,S,d] d_model memory tokens
    (vision projection / encoder output).

    lanes_mask: optional [B] bool — install ONLY the masked lanes,
    leaving every other lane's memory bit-identical (interleaved lane
    admission writes a new request's memory into its reset lane while
    neighbors keep decoding). With lanes_mask=None the whole batch is
    replaced (fresh sub-state admission / one-shot prefill), and S may
    differ from the state's slab width (the state adopts the new
    shape); with a mask the shapes must match.

    Done ONCE up front (not per chunk): the K/V projections of the
    memory are loop-invariant, so the fused chunk scan no longer
    recomputes them every chunk step."""
    unit, U, R, tail = _unit_and_counts(cfg)
    B = memory.shape[0]
    ml = jnp.broadcast_to(jnp.asarray(mem_len, jnp.int32), (B,))

    def upd(block_params, block_state, stacked: bool):
        if stacked:
            mem_kv = jax.vmap(
                lambda pp: blocks.make_memory_kv(pp, cfg, memory))(
                    block_params["xattn"])               # [R,B,S,Hkv,Dh]
            ml_b = jnp.broadcast_to(ml, (mem_kv[0].shape[0], B))
        else:
            mem_kv = blocks.make_memory_kv(block_params["xattn"], cfg,
                                           memory)
            ml_b = ml
        if lanes_mask is None:
            return {"cache": block_state["cache"], "xk": mem_kv[0],
                    "xv": mem_kv[1], "mem_len": ml_b}
        sel = lanes_mask.reshape((1,) * stacked + (B, 1, 1, 1))
        return {"cache": block_state["cache"],
                "xk": jnp.where(sel, mem_kv[0], block_state["xk"]),
                "xv": jnp.where(sel, mem_kv[1], block_state["xv"]),
                "mem_len": jnp.where(
                    lanes_mask.reshape((1,) * stacked + (B,)),
                    ml_b, block_state["mem_len"])}

    out = dict(state)
    if R > 0 and "cross" in unit:
        out["layers"] = tuple(
            upd(params["layers"][i], state["layers"][i], True)
            if kind == "cross" else state["layers"][i]
            for i, kind in enumerate(unit))
    out["tail"] = tuple(
        upd(params["tail"][i], state["tail"][i], False)
        if kind == "cross" else state["tail"][i]
        for i, kind in enumerate(tail))
    return out


def prefill_chunk(params, gate_params, cfg, tokens, state, policy,
                  serve_cfg, *, n_valid=None, extra_inputs=None):
    """Continue prefill with a chunk of tokens [B,C] against existing
    state (chunked-prefill setting, paper Sec B.3). For cross-attn
    families the memory must be in the state before the first chunk:
    pass extra_inputs here (install_memory runs first; idempotent) or
    install it up front. n_valid: number of real tokens (pad+mask tail
    chunks so every chunk shares ONE closure shape regardless of the
    prompt length)."""
    extra_inputs = extra_inputs or {}
    memory, mem_len = _memory_from_inputs(params, cfg, extra_inputs)
    if memory is not None:
        state = install_memory(params, cfg, state, memory, mem_len)
    return _prefill_chunk_step(params, gate_params, cfg, tokens, state,
                               policy, serve_cfg, n_valid=n_valid)


def _where_lanes(mask, new, old):
    """Per-lane select over two same-shape decode states: lanes where
    mask ([B] bool) is True take `new`'s rows, the rest keep `old`'s —
    the state analogue of jnp.where, respecting the layout (t [B],
    layers leaves [R, B, ...], tail leaves [B, ...])."""
    out = {"t": jnp.where(mask, new["t"], old["t"])}
    if new["layers"] is not None:
        out["layers"] = jax.tree.map(
            lambda n, o: jnp.where(
                mask.reshape((1, -1) + (1,) * (n.ndim - 2)), n, o),
            new["layers"], old["layers"])
    else:
        out["layers"] = None
    out["tail"] = jax.tree.map(
        lambda n, o: jnp.where(mask.reshape((-1,) + (1,) * (n.ndim - 1)),
                               n, o),
        new["tail"], old["tail"])
    return out


def prefill_chunk_loop(params, gate_params, cfg, chunks, n_valid, state,
                       policy, serve_cfg, *, extra_inputs=None,
                       capture_chunk=None):
    """Fused chunked prefill: drive the whole chunk pipeline (embed ->
    chunk attention -> eviction merge, per chunk) under ONE jax.lax.scan
    so a long-prompt prefill is a single device program — O(1) host
    dispatches like the fused decode loop, instead of one per chunk.

    chunks: [n_chunks, B, C] (prompt reshaped, tail padded to C);
    n_valid: [n_chunks] int32 real-token counts (== C except the tail),
    OR [n_chunks, B] for a RAGGED batch — mixed-length prompts packed
    into one shared chunk grid, each request marking its own per-chunk
    valid counts (full chunks, then its tail, then zeros once it is
    fully prefilled; zero-chunks freeze that row bit-identically).
    All chunks share one closure shape, so any prompt-length mix
    compiles exactly once per n_chunks. Returns (state, h_last [B,d] of
    each row's last real token — the ragged loop carries every row's
    h_last across its trailing empty chunks). Token-exact vs the eager
    per-chunk loop AND vs per-request unpadded prefill: all run
    _prefill_chunk_step on identical padded inputs.

    Cross-memory families: extra_inputs carries the frontend embeds
    (+ optional per-row "mem_len" for a ragged batch padded to a
    shared S); the memory K/V are installed into the state ONCE before
    the scan (install_memory) — they are loop-invariant, so the scan
    body no longer rebuilds them per chunk.

    capture_chunk: optional [B] int32 — per-lane chunk-boundary
    SNAPSHOT for the prefix cache (serve.prefix_cache): lane l's state
    is captured right after its capture_chunk[l]-th chunk step (0 =
    no capture; the snapshot row stays the entry state). The snapshot
    rides the scan carry (a per-lane _where_lanes select, no extra
    dispatch) and a third return value `snap` (same structure as
    `state`) carries it out — rows with capture_chunk 0 are
    meaningless there."""
    extra_inputs = extra_inputs or {}
    memory, mem_len = _memory_from_inputs(params, cfg, extra_inputs)
    if memory is not None:
        state = install_memory(params, cfg, state, memory, mem_len)
    B = chunks.shape[1]
    dtype = params["embed"].dtype
    ragged = n_valid.ndim == 2
    capture = capture_chunk is not None

    def body(carry, xs):
        if capture:
            state, h_prev, snap = carry
            tokens, nv, j = xs
        else:
            state, h_prev = carry
            tokens, nv = xs
        state, h_last = _prefill_chunk_step(params, gate_params, cfg,
                                            tokens, state, policy,
                                            serve_cfg, n_valid=nv)
        if ragged:
            h_last = jnp.where((nv > 0)[:, None], h_last, h_prev)
        if capture:
            snap = _where_lanes(capture_chunk == j + 1, state, snap)
            return (state, h_last, snap), None
        return (state, h_last), None

    h0 = jnp.zeros((B, cfg.d_model), dtype)
    if capture:
        n_chunks = chunks.shape[0]
        (state, h_last, snap), _ = jax.lax.scan(
            body, (state, h0, state),
            (chunks, n_valid, jnp.arange(n_chunks, dtype=jnp.int32)))
        return state, h_last, snap
    (state, h_last), _ = jax.lax.scan(body, (state, h0),
                                      (chunks, n_valid))
    return state, h_last


def decode_step(params, gate_params, cfg, state, token, policy,
                attn_impl="xla", active=None):
    """token: [B] int32. Returns (new_state, logits [B, Vp] f32).
    state["t"] is the per-lane clock [B] (lock-step paths keep all
    entries equal). active: optional [B] bool — inactive lanes are
    masked to the identity end-to-end: their caches, recurrences and
    clocks come back bit-identical (the continuous-batching scheduler
    freezes retired/empty lanes this way)."""
    unit, U, R, tail = _unit_and_counts(cfg)
    x = jnp.take(params["embed"], token, axis=0)           # [B,d]
    t = state["t"]

    def unit_body(x, xs):
        up, ug, st = xs
        new_states = []
        for i, kind in enumerate(unit):
            g = ug[i] if ug is not None else None
            x, ns, _ = blocks.apply_block_decode(
                up[i], g, cfg, kind, x, st[i], t, policy=policy,
                attn_impl=attn_impl, active=active)
            new_states.append(ns)
        return x, tuple(new_states)

    new_state = {"t": t + 1 if active is None
                 else t + active.astype(jnp.int32)}
    if R > 0:
        glayers = (gate_params or {}).get("layers")
        x, stacked = jax.lax.scan(
            unit_body, x, (params["layers"], glayers, state["layers"]),
            unroll=R if cfg.unroll_layers else 1)
        new_state["layers"] = stacked
    else:
        new_state["layers"] = None
    new_tail = []
    for i, kind in enumerate(tail):
        g = (gate_params or {}).get("tail", (None,) * len(tail))[i]
        x, ns, _ = blocks.apply_block_decode(
            params["tail"][i], g, cfg, kind, x, state["tail"][i], t,
            policy=policy, attn_impl=attn_impl, active=active)
        new_tail.append(ns)
    new_state["tail"] = tuple(new_tail)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    return new_state, compute_logits(params, cfg, x)


def sample_token(logits, *, greedy, temperature, key):
    """logits [B,Vp] f32 -> (token [B] int32, new_key). Greedy argmax or
    temperature sampling; key is split only on the sampling path so a
    seeded eager loop and the fused scan consume identical key streams."""
    if greedy or temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), key
    key, sk = jax.random.split(key)
    tok = jax.random.categorical(sk, logits / temperature).astype(jnp.int32)
    return tok, key


def decode_loop(params, gate_params, cfg, state, first_token, n_steps,
                policy, *, greedy=True, temperature=0.0, rng=None,
                attn_impl="xla"):
    """Fused multi-token decode: the whole sample -> embed -> layers ->
    evict -> logits cycle runs under one jax.lax.scan, so a generation
    is a single device program instead of n_steps host dispatches.

    first_token: [B] int32 — the token produced from the prefill logits
    (it is EMITTED first, then fed through the model, matching the eager
    loop). n_steps must be static (scan length). Returns
    (new_state, ids [B, n_steps] int32).

    Token-for-token identical to the eager per-step loop: greedy argmax,
    or temperature sampling with the PRNG key threaded through the scan
    carry (same split sequence as splitting once per step eagerly).
    """
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def body(carry, _):
        state, tok, key = carry
        state, logits = decode_step(params, gate_params, cfg, state, tok,
                                    policy, attn_impl=attn_impl)
        nxt, key = sample_token(logits, greedy=greedy,
                                temperature=temperature, key=key)
        return (state, nxt, key), tok

    (state, _, _), toks = jax.lax.scan(
        body, (state, first_token, rng), None, length=n_steps)
    return state, jnp.moveaxis(toks, 0, 1)                 # [B, n_steps]


# --------------------------------------------- continuous-batching lanes
#
# The serving scheduler (serve.scheduler) treats the batch dim as B
# fixed LANES: each lane holds one in-flight request at its own
# position, finished lanes are reset (pos := -1 — slot-dense eviction
# needs no paged block tables) and refilled from the queue. The helpers
# below are the transformer-level surface of that model: masked decode
# segments, per-lane RNG sampling, and lane-granular state surgery.


def sample_token_lanes(logits, keys, *, greedy, temperature):
    """Per-lane sampling with INDEPENDENT key chains. keys: [B,2]
    uint32 (one PRNG key per lane, seeded from its request). Each lane
    splits its own key once per step and draws from its own logits row,
    which is bit-identical to the stream a B=1 Engine.generate seeded
    with that lane's key would draw — so scheduler outputs reproduce
    one-shot generation regardless of which lane (or admission order) a
    request landed on. Returns (tokens [B] int32, new_keys [B,2])."""
    if greedy or temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), keys
    split = jax.vmap(jax.random.split)(keys)               # [B,2,2]
    new_keys, sub = split[:, 0], split[:, 1]
    tok = jax.vmap(lambda k, l: jax.random.categorical(k, l / temperature)
                   )(sub, logits)
    return tok.astype(jnp.int32), new_keys


def decode_segment_loop(params, gate_params, cfg, state, tok, keys, active,
                        n_emitted, max_new, eos_id, n_steps, policy, *,
                        greedy=True, temperature=0.0, attn_impl="xla",
                        n_real=None):
    """Masked continuous-batching decode segment: n_steps of the fused
    sample -> embed -> layers -> evict -> logits cycle under ONE
    lax.scan, over B independent lanes that may be mid-request, finished
    or empty. The scheduler calls this once per segment, so dispatches
    stay O(segments) — never O(tokens) — while lanes retire and refill
    between calls.

    Per-lane carries: tok [B] (next token to emit/feed), keys [B,2]
    (independent RNG chains — see sample_token_lanes), active [B] bool,
    n_emitted [B] int32. Per-lane limits: max_new [B] int32, eos_id [B]
    int32 (-1 = never stop early). Each step a lane EMITS its carried
    token, feeds it through the masked decode_step (inactive lanes are
    frozen bit-identically), then samples the next; emitting its
    eos_id or its max_new-th token deactivates it at the step boundary
    (early-exit-safe: the step that emits the final token still updates
    the lane's state, exactly like the one-shot loop it must match).

    n_real: optional traced scalar — run only the first n_real of the
    n_steps scan steps, freezing the padded tail bit-identically (every
    lane masked inactive there, no emissions, no state/RNG updates).
    The scheduler rounds remainder segments up to power-of-two BUCKETS
    and masks the tail, so cold-start compiles scale with
    log2(decode_segment) buckets instead of with every distinct
    remainder length.

    Returns (state, tok, keys, active, n_emitted,
             ids [B, n_steps] int32, emitted [B, n_steps] bool,
             ok [B] bool) — ids[l, j] is valid output for lane l iff
    emitted[l, j]; ok[l] False means lane l produced NON-FINITE logits
    on some step it was active (a poisoned cache / numerical fault):
    its emissions are suspect and the supervision layer (serve.faults)
    quarantines + replays it."""
    if n_real is None:
        n_real = n_steps

    def body(carry, j):
        state, tok, keys, active, n_emitted, ok = carry
        live = active & (j < n_real)
        # each step emits the PRE-step carry token (mirroring
        # decode_loop, which emits first_token before feeding it)
        emit = live
        state, logits = decode_step(params, gate_params, cfg, state, tok,
                                    policy, attn_impl=attn_impl,
                                    active=live)
        # in-program health: a poisoned lane's logits go non-finite;
        # flagging it here costs zero extra dispatches
        ok = ok & (~live | jnp.all(jnp.isfinite(logits), axis=-1))
        nxt, new_keys = sample_token_lanes(logits, keys, greedy=greedy,
                                           temperature=temperature)
        keys = jnp.where(live[:, None], new_keys, keys)
        n_emitted = n_emitted + emit.astype(jnp.int32)
        done = emit & (((eos_id >= 0) & (tok == eos_id)) |
                       (n_emitted >= max_new))
        new_tok = jnp.where(emit, nxt, tok)
        return (state, new_tok, keys, active & ~done, n_emitted, ok), \
            (tok, emit)

    ok0 = jnp.ones(tok.shape[0], bool)
    (state, tok, keys, active, n_emitted, ok), (toks, emits) = \
        jax.lax.scan(body, (state, tok, keys, active, n_emitted, ok0),
                     jnp.arange(n_steps))
    return (state, tok, keys, active, n_emitted,
            jnp.moveaxis(toks, 0, 1), jnp.moveaxis(emits, 0, 1), ok)


def mixed_step_loop(params, gate_params, cfg, state, tok, keys, active,
                    n_emitted, max_new, eos_id, chunks, chunk_valid,
                    finish, new_keys, policy, serve_cfg, *, greedy=True,
                    temperature=0.0, attn_impl="xla", mem_inputs=None,
                    mem_install=None, compact=True):
    """Interleaved prefill/decode segment (the PR-4 SLO hot path): ONE
    lax.scan whose every step advances the active DECODE lanes by one
    token AND feeds at most one prefill chunk per ADMITTING lane — so a
    long prompt entering the server no longer stalls in-flight decodes
    (head-of-line blocking), and admission costs ZERO extra dispatches:
    it rides inside the segment program.

    Per step j the body runs two complementary sub-steps over the same
    B-lane state:

      1. decode_step with `active` as the mask (exactly the
         decode_segment_loop body: emit carried token, feed it, sample
         the next per-lane) — prefilling/empty lanes are frozen
         bit-identically;
      2. the chunk sub-step (_chunk_substep) on chunks[j] with per-lane
         chunk_valid[j] (a lane's next prompt chunk, or 0 = no chunk):
         with compact=True only the lanes holding a chunk are computed,
         one [1,C] lane per pass, and every other lane is left in
         place; with compact=False (lane-sharded meshes) the whole
         [B,C] grid runs and zero-valid rows are frozen
         bit-identically.

    A lane is in at most ONE mode per step (the scheduler guarantees
    active[lane] => chunk_valid[j, lane] == 0), so the combined effect
    per lane equals whichever sub-step owns it, and decode lanes are
    bit-identical to a pure decode_segment_loop.

    The prefill -> decode transition happens INSIDE the scan: at the
    step where a lane consumes its final chunk (finish[j, lane]), the
    body computes logits from that lane's last real token's hidden,
    argmaxes the first token into the lane's carry (matching one-shot
    generate, whose first token is always the greedy prefill argmax),
    installs the lane's per-request RNG key from new_keys, zeroes
    n_emitted and activates the lane — it starts emitting at step j+1
    (or, when it finishes on the segment's last step, in the next
    segment: the carries persist on the scheduler).

    chunks: [n_steps, B, C] int32; chunk_valid: [n_steps, B] int32 (0 =
    no chunk for that lane this step); finish: [n_steps, B] bool (lane
    consumes its LAST chunk this step); new_keys: [B, 2] uint32 (RNG
    key for every lane that finishes prefill within this segment).
    Other operands as decode_segment_loop. Returns the same tuple:
    (state, tok, keys, active, n_emitted, ids [B, n_steps],
    emitted [B, n_steps], ok [B] — False where a lane produced
    non-finite logits while decoding or at its prefill->decode
    transition; see decode_segment_loop).

    Cross-memory families: mem_inputs (the extra_inputs dict, padded
    [B,S,feat] + per-lane "mem_len") and mem_install ([B] bool: lanes
    whose FIRST prompt chunk rides in this segment) install each
    admitting lane's encoder/vision memory into its (reset) lane state
    BEFORE the scan — memory is chunk-invariant, the install is a
    per-lane where (neighbors bit-identical), and it still costs zero
    dedicated dispatches: it rides inside the segment program."""
    if mem_inputs is not None:
        memory, mem_len = _memory_from_inputs(params, cfg, mem_inputs)
        state = install_memory(params, cfg, state, memory, mem_len,
                               lanes_mask=mem_install)

    def body(carry, xs):
        state, tok, keys, active, n_emitted, ok = carry
        ctoks, nv, fin = xs
        # --- decode sub-step (mirrors decode_segment_loop exactly:
        # emit the carried token, feed it, sample the next) ---
        emit = active
        state, logits = decode_step(params, gate_params, cfg, state, tok,
                                    policy, attn_impl=attn_impl,
                                    active=active)
        ok = ok & (~active | jnp.all(jnp.isfinite(logits), axis=-1))
        nxt, new_dec_keys = sample_token_lanes(logits, keys,
                                               greedy=greedy,
                                               temperature=temperature)
        keys = jnp.where(active[:, None], new_dec_keys, keys)
        n_emitted = n_emitted + emit.astype(jnp.int32)
        done = emit & (((eos_id >= 0) & (tok == eos_id)) |
                       (n_emitted >= max_new))
        new_tok = jnp.where(emit, nxt, tok)
        dec_active = active & ~done
        # --- chunk sub-step (lanes without a chunk left bit-identical)
        state, h_last = _chunk_substep(params, gate_params, cfg, ctoks,
                                       state, policy, serve_cfg, nv,
                                       compact)
        # --- transition: finishing lanes take their greedy first token
        # (one-shot parity: Engine.generate argmaxes the prefill
        # logits even under temperature sampling) and their request's
        # RNG key AFTER this step's split, so their first sampled draw
        # consumes split(seed_key) exactly like a fresh decode_loop
        first, fin_ok = _finish_first(params, cfg, fin, h_last)
        ok = ok & (~fin | fin_ok)
        new_tok = jnp.where(fin, first, new_tok)
        keys = jnp.where(fin[:, None], new_keys, keys)
        n_emitted = jnp.where(fin, 0, n_emitted)
        return (state, new_tok, keys, dec_active | fin, n_emitted, ok), \
            (tok, emit)

    ok0 = jnp.ones(tok.shape[0], bool)
    (state, tok, keys, active, n_emitted, ok), (toks, emits) = \
        jax.lax.scan(body, (state, tok, keys, active, n_emitted, ok0),
                     (chunks, chunk_valid, finish))
    return (state, tok, keys, active, n_emitted,
            jnp.moveaxis(toks, 0, 1), jnp.moveaxis(emits, 0, 1), ok)


# ------------------------------------------- speculative decode (PR 9)
#
# Draft/verify speculative decoding inside the fused segments: each
# ROUND drafts spec_k tokens per live lane from its retained token
# history (n-gram self-drafting; pluggable), scores all C = spec_k + 1
# candidate positions in ONE chunk-shaped dispatch
# (blocks.apply_block_verify), accepts the longest agreeing greedy
# prefix and commits exactly those positions' cache transactions
# (blocks.apply_block_verify_commit — bounded rollback; rejected
# positions never touch durable state). Greedy outputs are
# token-identical to the non-speculative path by construction
# (tests/test_speculative.py asserts it across every policy × impl ×
# admission mode). Speculation is GREEDY-ONLY: under temperature
# sampling acceptance would need stochastic verification, which cannot
# be bit-identical to the per-token key chain (the scheduler refuses
# spec_k > 0 off the greedy path).

SPEC_HISTORY = 64  # per-lane token-history window the drafter sees


def ngram_draft(hist, tok, k):
    """Self-draft k tokens from the lane's token history. hist: [B, H]
    int32 — the tokens emitted BEFORE the current carry, left-padded
    with -1, most recent last; tok: [B] the current carry token.
    Finds the most recent earlier occurrence of the bigram
    (hist[-1], tok) and proposes its continuation; lanes with no match
    (or a continuation running off the known history) fall back to
    repeating the carry token — a free win on degenerate greedy cycles.
    Returns drafts [B, k] int32 (always valid vocab ids)."""
    B, H = hist.shape
    ext = jnp.concatenate([hist, tok[:, None]], axis=1)      # [B, H+1]
    last, prev = ext[:, -1], ext[:, -2]
    p = jnp.arange(1, H, dtype=jnp.int32)                    # [H-1]
    match = ((ext[:, 1:H] == last[:, None]) &
             (ext[:, 0:H - 1] == prev[:, None]) &
             (ext[:, 1:H] >= 0) & (ext[:, 0:H - 1] >= 0))
    best = jnp.max(jnp.where(match, p[None], -1), axis=1)    # [B]
    has = best >= 0
    idx = best[:, None] + jnp.arange(1, k + 1, dtype=jnp.int32)[None]
    cont = jnp.take_along_axis(ext, jnp.clip(idx, 0, H), axis=1)
    valid = has[:, None] & (idx <= H) & (cont >= 0)
    return jnp.where(valid, cont, tok[:, None]).astype(jnp.int32)


def _verify_forward(params, gate_params, cfg, state, fed, live, policy,
                    attn_impl="xla"):
    """Phase A of a verify round: score all C candidate positions
    (fed [B, C] int32) through the stack WITHOUT mutating state — each
    block replays the literal decode recipe per position on a scratch
    state (blocks.apply_block_verify), so the logits are bit-identical
    to sequential decode at every correctly-fed position. Returns
    (logits [B, C, Vp] f32, sigs) where sigs mirrors the state layout
    ({layers: stacked, tail: tuple}) holding each block's per-position
    commit signals."""
    unit, U, R, tail = _unit_and_counts(cfg)
    x = jnp.take(params["embed"], fed, axis=0)               # [B,C,d]
    t = state["t"]

    def unit_body(x, xs):
        up, ug, st = xs
        sigs = []
        for i, kind in enumerate(unit):
            g = ug[i] if ug is not None else None
            x, sig = blocks.apply_block_verify(
                up[i], g, cfg, kind, x, st[i], t, policy=policy,
                attn_impl=attn_impl, live=live)
            sigs.append(sig)
        return x, tuple(sigs)

    sigs = {"layers": None}
    if R > 0:
        glayers = (gate_params or {}).get("layers")
        x, stacked = jax.lax.scan(
            unit_body, x, (params["layers"], glayers, state["layers"]),
            unroll=R if cfg.unroll_layers else 1)
        sigs["layers"] = stacked
    tail_sigs = []
    for i, kind in enumerate(tail):
        g = (gate_params or {}).get("tail", (None,) * len(tail))[i]
        x, sig = blocks.apply_block_verify(
            params["tail"][i], g, cfg, kind, x, state["tail"][i], t,
            policy=policy, attn_impl=attn_impl, live=live)
        tail_sigs.append(sig)
    sigs["tail"] = tuple(tail_sigs)

    # final norm + unembed per position at the decode shape [B, d] —
    # chunk-shaped GEMMs are NOT row-bit-identical across batch shapes
    # on every backend, and verify parity is bit-exact by construction
    def lstep(_, x_t):
        h = rmsnorm_apply(params["final_norm"], x_t, cfg.norm_eps)
        return None, compute_logits(params, cfg, h)

    _, lg = jax.lax.scan(lstep, None, jnp.moveaxis(x, 1, 0))
    return jnp.moveaxis(lg, 0, 1), sigs


def _verify_commit(cfg, state, sigs, n_commit, live, policy):
    """Phase B of a verify round: commit each lane's accepted prefix
    (n_commit [B], 0 for non-live lanes) from the round-entry state
    using phase A's signals. Bit-identical to having decode_step'ped
    only the accepted tokens (blocks.apply_block_verify_commit)."""
    unit, U, R, tail = _unit_and_counts(cfg)
    t = state["t"]
    new_state = {"t": t + n_commit}

    def unit_body(carry, xs):
        st, sg = xs
        new = tuple(
            blocks.apply_block_verify_commit(cfg, unit[i], st[i], sg[i],
                                             t, n_commit, live, policy)
            for i in range(U))
        return carry, new

    if R > 0:
        _, stacked = jax.lax.scan(
            unit_body, None, (state["layers"], sigs["layers"]),
            unroll=R if cfg.unroll_layers else 1)
        new_state["layers"] = stacked
    else:
        new_state["layers"] = None
    new_state["tail"] = tuple(
        blocks.apply_block_verify_commit(cfg, tail[i], state["tail"][i],
                                         sigs["tail"][i], t, n_commit,
                                         live, policy)
        for i in range(len(tail)))
    return new_state


def verify_round(params, gate_params, cfg, state, tok, hist, active,
                 live, n_emitted, max_new, eos_id, spec_k, policy, *,
                 attn_impl="xla", draft_fn=None):
    """One draft/verify/commit round over B lanes. Drafts spec_k tokens
    per live lane, scores C = spec_k + 1 positions in one fused
    dispatch, accepts the longest greedy-agreeing prefix (clipped at
    each lane's stop condition) and commits exactly those positions.

    tok [B]: carry token (emitted first, like decode_segment_loop);
    hist [B, SPEC_HISTORY]: tokens BEFORE tok, -1 padded, recent last;
    active/live [B]: lane liveness (live = active & in-real-range);
    draft_fn(hist, tok, k) -> [B, k]: pluggable drafter (defaults to
    ngram_draft; tests inject adversarial drafters, a small draft model
    slots in the same way).

    Returns (state, tok, hist, active, n_emitted, fed [B, C],
    emitted [B, C], ok [B], n_commit [B]) — fed[l, j] is an emitted
    output token iff emitted[l, j]; ok is False where a lane's logits
    went non-finite at a COMMITTED position (rejected positions never
    reach durable state, so only committed ones can poison the lane)."""
    B = tok.shape[0]
    C = spec_k + 1
    drafts = (draft_fn or ngram_draft)(hist, tok, spec_k) \
        if spec_k > 0 else jnp.zeros((B, 0), jnp.int32)
    fed = jnp.concatenate([tok[:, None], drafts], axis=1)    # [B,C]
    logits, sigs = _verify_forward(params, gate_params, cfg, state, fed,
                                   live, policy, attn_impl=attn_impl)
    y = jnp.argmax(logits, axis=-1).astype(jnp.int32)        # [B,C]
    # longest agreeing prefix: position j's feed is trusted iff every
    # draft before it matched the model's own greedy token
    if spec_k > 0:
        acc = jnp.cumprod((drafts == y[:, :-1]).astype(jnp.int32),
                          axis=1)
        n_cand = 1 + jnp.sum(acc, axis=1)                    # [B] 1..C
    else:
        n_cand = jnp.ones((B,), jnp.int32)
    # per-lane stop conditions INSIDE the accepted candidates: emitting
    # eos or the max_new-th token ends the request at that position
    s_idx = jnp.arange(C, dtype=jnp.int32)
    stop = ((((eos_id[:, None] >= 0) & (fed == eos_id[:, None])) |
             (n_emitted[:, None] + s_idx[None] + 1 >= max_new[:, None]))
            & (s_idx[None] < n_cand[:, None]))
    first_stop = jnp.min(jnp.where(stop, s_idx[None], C), axis=1)
    n_commit = jnp.where(live,
                         jnp.minimum(n_cand, first_stop + 1), 0)
    done = live & (first_stop < C)
    # health over committed positions only (position 0 is ALWAYS
    # committed for a live lane, so a poisoned cache cannot hide)
    finite = jnp.all(jnp.isfinite(logits), axis=-1)          # [B,C]
    ok = jnp.all((s_idx[None] >= n_commit[:, None]) | finite, axis=1)
    emitted = live[:, None] & (s_idx[None] < n_commit[:, None])
    state = _verify_commit(cfg, state, sigs, n_commit, live, policy)
    # carry = the model's own prediction after the last committed token
    carry = jnp.take_along_axis(
        y, jnp.maximum(n_commit - 1, 0)[:, None], axis=1)[:, 0]
    new_tok = jnp.where(live, carry, tok)
    # history absorbs the committed tokens (still excluding the carry)
    ext = jnp.concatenate([hist, fed], axis=1)               # [B,H+C]
    H = hist.shape[1]
    shifted = jnp.take_along_axis(
        ext, jnp.arange(H, dtype=jnp.int32)[None] + n_commit[:, None],
        axis=1)
    hist = jnp.where(live[:, None], shifted, hist)
    n_emitted = n_emitted + n_commit
    return (state, new_tok, hist, active & ~done, n_emitted, fed,
            emitted, ok, n_commit)


def spec_decode_segment_loop(params, gate_params, cfg, state, tok, keys,
                             active, n_emitted, max_new, eos_id, hist,
                             n_rounds, policy, *, spec_k,
                             attn_impl="xla", n_real=None,
                             draft_fn=None):
    """Speculative counterpart of decode_segment_loop: n_rounds verify
    rounds under ONE lax.scan, each advancing every live lane by 1 to
    spec_k + 1 tokens. Greedy-only (keys ride through untouched for
    snapshot/layout parity). n_real masks trailing rounds exactly like
    decode_segment_loop's step mask, so the scheduler's pow2 drain
    buckets work unchanged in ROUND units.

    Returns (state, tok, keys, active, n_emitted,
    ids [B, n_rounds*(spec_k+1)], emitted [same], ok [B], hist,
    acc_tok [B] committed tokens, acc_rounds [B] live rounds) — ids
    columns are round-major/position-minor, so masked-select by
    `emitted` yields each lane's tokens in emission order, exactly like
    the non-speculative segment's ids."""
    if n_real is None:
        n_real = n_rounds

    def body(carry, j):
        state, tok, hist, active, n_emitted, ok, a_tok, a_rnd = carry
        live = active & (j < n_real)
        state, tok, hist, active, n_emitted, fed, emitted, r_ok, nc = \
            verify_round(params, gate_params, cfg, state, tok, hist,
                         active, live, n_emitted, max_new, eos_id,
                         spec_k, policy, attn_impl=attn_impl,
                         draft_fn=draft_fn)
        ok = ok & (~live | r_ok)
        a_tok = a_tok + nc
        a_rnd = a_rnd + live.astype(jnp.int32)
        return (state, tok, hist, active, n_emitted, ok, a_tok, a_rnd), \
            (fed, emitted)

    B = tok.shape[0]
    zeros = jnp.zeros((B,), jnp.int32)
    (state, tok, hist, active, n_emitted, ok, a_tok, a_rnd), \
        (feds, emits) = jax.lax.scan(
            body,
            (state, tok, hist, active, n_emitted,
             jnp.ones((B,), bool), zeros, zeros),
            jnp.arange(n_rounds))
    C = spec_k + 1
    ids = jnp.moveaxis(feds, 0, 1).reshape(B, n_rounds * C)
    emitted = jnp.moveaxis(emits, 0, 1).reshape(B, n_rounds * C)
    return (state, tok, keys, active, n_emitted, ids, emitted, ok, hist,
            a_tok, a_rnd)


def spec_mixed_step_loop(params, gate_params, cfg, state, tok, keys,
                         active, n_emitted, max_new, eos_id, hist,
                         chunks, chunk_valid, finish, new_keys, policy,
                         serve_cfg, *, spec_k, attn_impl="xla",
                         mem_inputs=None, mem_install=None,
                         draft_fn=None, compact=True):
    """Speculative counterpart of mixed_step_loop: per scan step the
    decode lanes run one verify_round (1..spec_k+1 tokens each) while
    admitting lanes consume one prefill chunk; a lane finishing its
    prompt takes its greedy first token as carry and seeds its drafter
    history EMPTY-handed — hist rows are seeded host-side at admission
    with the prompt tail, and the first carry token is exactly the
    prefill argmax, so no in-scan history write is needed at the
    transition. Greedy-only; compact as in mixed_step_loop. Returns the
    spec_decode_segment_loop tuple (ids/emitted are
    [B, n_steps*(spec_k+1)])."""
    if mem_inputs is not None:
        memory, mem_len = _memory_from_inputs(params, cfg, mem_inputs)
        state = install_memory(params, cfg, state, memory, mem_len,
                               lanes_mask=mem_install)

    def body(carry, xs):
        state, tok, keys, hist, active, n_emitted, ok, a_tok, \
            a_rnd = carry
        ctoks, nv, fin = xs
        state, tok, hist, dec_active, n_emitted, fed, emitted, r_ok, \
            nc = verify_round(params, gate_params, cfg, state, tok,
                              hist, active, active, n_emitted, max_new,
                              eos_id, spec_k, policy,
                              attn_impl=attn_impl, draft_fn=draft_fn)
        ok = ok & (~active | r_ok)
        a_tok = a_tok + nc
        a_rnd = a_rnd + active.astype(jnp.int32)
        # --- chunk sub-step + transition (mirrors mixed_step_loop)
        state, h_last = _chunk_substep(params, gate_params, cfg, ctoks,
                                       state, policy, serve_cfg, nv,
                                       compact)
        first, fin_ok = _finish_first(params, cfg, fin, h_last)
        ok = ok & (~fin | fin_ok)
        tok = jnp.where(fin, first, tok)
        keys = jnp.where(fin[:, None], new_keys, keys)
        n_emitted = jnp.where(fin, 0, n_emitted)
        return (state, tok, keys, hist, dec_active | fin, n_emitted, ok,
                a_tok, a_rnd), (fed, emitted)

    B = tok.shape[0]
    zeros = jnp.zeros((B,), jnp.int32)
    (state, tok, keys, hist, active, n_emitted, ok, a_tok, a_rnd), \
        (feds, emits) = jax.lax.scan(
            body,
            (state, tok, keys, hist, active, n_emitted,
             jnp.ones((B,), bool), zeros, zeros),
            (chunks, chunk_valid, finish))
    n_steps, C = chunks.shape[0], spec_k + 1
    ids = jnp.moveaxis(feds, 0, 1).reshape(B, n_steps * C)
    emitted = jnp.moveaxis(emits, 0, 1).reshape(B, n_steps * C)
    return (state, tok, keys, active, n_emitted, ids, emitted, ok, hist,
            a_tok, a_rnd)


# reset targets per leaf name — defined in blocks.py next to
# init_block_state (the single place that allocates the leaves): slot
# metadata is invalidated, recurrences and clocks zero; K/V and
# cross-memory BYTES are left in place — invisible to every attention
# read once their metadata is cleared, and fully overwritten by the
# next insert_lanes / install_memory anyway. The cache fills must
# match core.cache.reset_lanes (the per-cache primitive; parity
# asserted in tests/test_scheduler.py).
_LANE_RESET = blocks.LANE_RESET_FILLS


def reset_lanes(state, lane_mask):
    """Retire lanes: clear the masked lanes' cache metadata (pos := -1,
    beta := 1, aux := 0), cross-memory validity (mem_len := 0 — the
    retired lane's encoder/vision K/V bytes become unreadable, so the
    next occupant can never attend a predecessor's memory),
    recurrent/SSM state and clock WITHOUT touching any other lane — in
    the slot-dense layout a lane reset is O(M) metadata writes, no
    paged block tables to walk. lane_mask: [B] bool. Neighbor lanes
    come back bit-identical (asserted by tests/test_scheduler.py)."""
    def reset(axis):
        def f(path, leaf):
            name = next((p.key for p in reversed(path)
                         if isinstance(p, jax.tree_util.DictKey)), None)
            if name not in _LANE_RESET:
                return leaf
            shape = [1] * leaf.ndim
            shape[axis] = lane_mask.shape[0]
            fill = jnp.full_like(leaf, _LANE_RESET[name])
            return jnp.where(lane_mask.reshape(shape), fill, leaf)
        return f

    out = {"t": jnp.where(lane_mask, 0, state["t"])}
    if state["layers"] is not None:
        out["layers"] = jax.tree_util.tree_map_with_path(
            reset(1), state["layers"])
    else:
        out["layers"] = None
    out["tail"] = jax.tree_util.tree_map_with_path(reset(0), state["tail"])
    return out


def install_lanes(state, sub_state, lane_mask):
    """Admit requests, SPMD-shard-local: select the masked lanes' rows
    from a LANE-ALIGNED full-B sub_state (row i of sub_state is lane
    i's fresh/prefilled/resumed state) into the B-lane state.
    lane_mask: [B] bool. This is the mask-select twin of insert_lanes:
    elementwise over the lane axis, so on a lane-sharded mesh every
    shard writes only its own rows — no scatter, no cross-shard
    resharding (the same "select, not scatter" rationale as
    core.cache.cache_insert). The serving closures route ALL lane
    installs (admission, resume, prefix-slab seeding) through here;
    insert_lanes stays as the index-addressed oracle utility."""
    def sel(axis):
        def f(o, n):
            shape = [1] * o.ndim
            shape[axis] = lane_mask.shape[0]
            return jnp.where(lane_mask.reshape(shape), n, o)
        return f

    out = {"t": jnp.where(lane_mask, sub_state["t"], state["t"])}
    if state["layers"] is not None:
        out["layers"] = jax.tree.map(sel(1), state["layers"],
                                     sub_state["layers"])
    else:
        out["layers"] = None
    out["tail"] = jax.tree.map(sel(0), state["tail"], sub_state["tail"])
    return out


def insert_lanes(state, sub_state, lanes):
    """Admit requests: scatter a freshly prefilled sub_state (batch k,
    e.g. from a ragged prefill_chunk_loop over the admitted prompts)
    into lanes `lanes` ([k] int32) of the B-lane state. Every leaf of
    the target lanes is overwritten (cache K/V included), so insert
    after reset_lanes is a complete lane lifecycle. Index-addressed —
    the serving hot path uses the mask-select install_lanes instead
    (shard-local on a lane-sharded mesh); this stays as the oracle
    utility (tests/test_faults.py round-trips through it) and the
    host-side prefix-trie path."""
    lanes = jnp.asarray(lanes, jnp.int32)
    out = {"t": state["t"].at[lanes].set(sub_state["t"])}
    if state["layers"] is not None:
        out["layers"] = jax.tree.map(
            lambda o, n: o.at[:, lanes].set(n), state["layers"],
            sub_state["layers"])
    else:
        out["layers"] = None
    out["tail"] = jax.tree.map(lambda o, n: o.at[lanes].set(n),
                               state["tail"], sub_state["tail"])
    return out


def extract_lanes(state, lanes):
    """Inverse of insert_lanes: gather lanes `lanes` ([k] int32) of the
    B-lane state into a standalone batch-k sub-state. Because eviction
    keeps each lane's live KV inside a bounded M-slot slab (pos -1
    marks the dead slots), the gathered pytree IS the lane's complete
    movable state — O(M x layers) regardless of how many tokens the
    lane has generated — so swap-out/snapshot is an O(M) DMA, not an
    O(T) one. insert_lanes(state, extract_lanes(state, lanes), lanes)
    is a bit-exact no-op (asserted in tests/test_faults.py)."""
    lanes = jnp.asarray(lanes, jnp.int32)
    out = {"t": state["t"][lanes]}
    if state["layers"] is not None:
        out["layers"] = jax.tree.map(lambda a: a[:, lanes],
                                     state["layers"])
    else:
        out["layers"] = None
    out["tail"] = jax.tree.map(lambda a: a[lanes], state["tail"])
    return out


# scrub additionally zeroes the payload bytes that reset_lanes leaves
# in place: a NaN-poisoned lane's K/V (self- and cross-attention) would
# otherwise survive the metadata reset and 0 x NaN = NaN leaks through
# the masked p@v einsum the moment any later read touches the slab.
_LANE_SCRUB = dict(_LANE_RESET,
                   **{n: 0.0 for n in blocks.LANE_PAYLOAD_LEAVES})


def scrub_lanes(state, lane_mask):
    """reset_lanes plus payload zeroing: the quarantine primitive. A
    lane whose dispatch produced non-finite outputs may hold NaN/Inf in
    ANY leaf, including the K/V bytes that an ordinary retire leaves in
    place, so recovery overwrites them with zeros before the lane is
    reused. Neighbor lanes are untouched. lane_mask: [B] bool."""
    def scrub(axis):
        def f(path, leaf):
            name = next((p.key for p in reversed(path)
                         if isinstance(p, jax.tree_util.DictKey)), None)
            if name not in _LANE_SCRUB:
                return leaf
            shape = [1] * leaf.ndim
            shape[axis] = lane_mask.shape[0]
            fill = jnp.full_like(leaf, _LANE_SCRUB[name])
            return jnp.where(lane_mask.reshape(shape), fill, leaf)
        return f

    out = {"t": jnp.where(lane_mask, 0, state["t"])}
    if state["layers"] is not None:
        out["layers"] = jax.tree_util.tree_map_with_path(
            scrub(1), state["layers"])
    else:
        out["layers"] = None
    out["tail"] = jax.tree_util.tree_map_with_path(scrub(0), state["tail"])
    return out


def teacher_force_loop(params, gate_params, cfg, state, tokens, policy,
                       attn_impl="xla"):
    """Fused teacher-forced scoring: feed gold tokens [B,L] through the
    decode cycle under one lax.scan. Returns (new_state, preds [B,L])
    where preds[:, i] is the argmax prediction made AFTER consuming
    tokens[:, i] (i.e. the model's guess for position t0+i+1)."""
    def body(state, tok):
        state, logits = decode_step(params, gate_params, cfg, state, tok,
                                    policy, attn_impl=attn_impl)
        return state, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    state, preds = jax.lax.scan(body, state, jnp.moveaxis(tokens, 0, 1))
    return state, jnp.moveaxis(preds, 0, 1)
