"""Per-kind transformer blocks: init + apply in train / prefill / decode
modes.

Kinds: "global"/"local" (self-attn + FFN), "cross" (self + cross-attn +
FFN; VLM image layers and enc-dec decoder layers), "recurrent" (RG-LRU,
Griffin), "mamba" (Mamba-1 selective SSM).

Block apply returns (x_out, new_state, aux) where aux carries the
retention betas / capacity-loss contribution / MoE router aux loss.
State is None in train mode.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gates as gates_lib
from repro.core.cache import (cache_insert, cache_replay, cache_topm_merge,
                              decode_attend, init_cache, memory_attend,
                              memory_pos)
from repro.core.losses import capacity_loss_chunked
from repro.models.common import (NEG_INF, apply_rope, chunked_attention,
                                 dense_apply, dense_init, mlp_apply,
                                 mlp_init, rmsnorm_apply, rmsnorm_init,
                                 to_dtype)

RG_LRU_C = 8.0  # Griffin's fixed recurrence-sharpness constant


# =================================================================== init


def init_ffn(key, cfg, dtype):
    if cfg.family == "moe" and cfg.num_experts > 0:
        k1, k2, k3, k4 = jax.random.split(key, 4)
        E, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
        s = 1.0 / np.sqrt(d)
        sf = 1.0 / np.sqrt(f)
        return {
            "router": dense_init(k1, d, E, dtype=jnp.float32),
            "gate_w": (jax.random.normal(k2, (E, d, f)) * s).astype(dtype),
            "up_w": (jax.random.normal(k3, (E, d, f)) * s).astype(dtype),
            "down_w": (jax.random.normal(k4, (E, f, d)) * sf).astype(dtype),
        }
    return mlp_init(key, cfg.d_model, cfg.d_ff, dtype=dtype)


def init_attn_proj(key, cfg, dtype):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, cfg.d_model, cfg.q_dim, bias=cfg.qkv_bias,
                         dtype=dtype),
        "wk": dense_init(kk, cfg.d_model, cfg.kv_dim, bias=cfg.qkv_bias,
                         dtype=dtype),
        "wv": dense_init(kv, cfg.d_model, cfg.kv_dim, bias=cfg.qkv_bias,
                         dtype=dtype),
        "wo": dense_init(ko, cfg.q_dim, cfg.d_model, dtype=dtype),
    }


def init_block(key, cfg, kind: str):
    dtype = to_dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    if kind in ("global", "local"):
        return {
            "norm1": rmsnorm_init(cfg.d_model),
            "attn": init_attn_proj(ks[0], cfg, dtype),
            "norm2": rmsnorm_init(cfg.d_model),
            "ffn": init_ffn(ks[1], cfg, dtype),
        }
    if kind == "cross":
        return {
            "norm1": rmsnorm_init(cfg.d_model),
            "attn": init_attn_proj(ks[0], cfg, dtype),
            "normx": rmsnorm_init(cfg.d_model),
            "xattn": init_attn_proj(ks[2], cfg, dtype),
            "xgate": jnp.zeros((), jnp.float32),   # tanh-gated cross path
            "norm2": rmsnorm_init(cfg.d_model),
            "ffn": init_ffn(ks[1], cfg, dtype),
        }
    if kind == "recurrent":
        w = cfg.lru_width
        return {
            "norm1": rmsnorm_init(cfg.d_model),
            "in_x": dense_init(ks[0], cfg.d_model, w, dtype=dtype),
            "in_gate": dense_init(ks[1], cfg.d_model, w, dtype=dtype),
            "conv_w": (jax.random.normal(ks[2], (cfg.conv_width, w))
                       * 0.1).astype(dtype),
            "conv_b": jnp.zeros((w,), dtype),
            "lru_wa": dense_init(ks[3], w, w, dtype=dtype),
            "lru_wx": dense_init(ks[4], w, w, dtype=dtype),
            # lambda init so that a = exp(-8*softplus(lam)) spreads in
            # (0.9, 0.999) as in Griffin
            "lru_lam": jnp.asarray(
                np.log(np.expm1(-np.log(np.random.RandomState(0).uniform(
                    0.9, 0.999, size=(w,))) / RG_LRU_C)), jnp.float32),
            "out": dense_init(ks[5], w, cfg.d_model, dtype=dtype),
            "norm2": rmsnorm_init(cfg.d_model),
            "ffn": init_ffn(ks[6], cfg, dtype),
        }
    if kind == "mamba":
        d, di, n, r = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank
        A = np.tile(np.arange(1, n + 1, dtype=np.float32), (di, 1))
        return {
            "norm": rmsnorm_init(d),
            "in_proj": dense_init(ks[0], d, 2 * di, dtype=dtype),
            "conv_w": (jax.random.normal(ks[1], (cfg.conv_width, di))
                       * 0.1).astype(dtype),
            "conv_b": jnp.zeros((di,), dtype),
            "x_proj": dense_init(ks[2], di, r + 2 * n, dtype=dtype),
            "dt_proj": dense_init(ks[3], r, di, bias=True, dtype=dtype),
            "A_log": jnp.asarray(np.log(A), jnp.float32),
            "D": jnp.ones((di,), jnp.float32),
            "out_proj": dense_init(ks[4], di, d, dtype=dtype),
        }
    raise ValueError(f"unknown layer kind {kind!r}")


def init_block_gate(key, cfg, kind: str):
    """Retention gate for blocks that own a growing self-attn KV cache."""
    if cfg.trimkv and kind in ("global", "local", "cross"):
        return gates_lib.gate_init(key, cfg.d_model, cfg.gate_hidden,
                                   cfg.num_kv_heads, cfg.gate_bias_init)
    return None


def memory_len(cfg) -> int:
    """Length of the static cross-attn memory (vision tokens or encoder
    frames)."""
    if cfg.family == "vlm":
        return cfg.num_image_tokens
    if cfg.family == "encdec":
        return cfg.source_len
    return 0


# ---------------------------------------------------- lane movability
#
# Every leaf init_block_state allocates is BATCH-LEADING (lane axis 0),
# which is what makes a lane's state first-class movable: the serving
# layer gathers lanes out (transformer.extract_lanes -> LaneSnapshot),
# scatters them back (insert_lanes), retires them (reset_lanes) and
# quarantines them (scrub_lanes) with generic per-leaf tree ops. The
# two tables below are the single definition of what those ops write,
# kept HERE next to the state definition so adding a leaf to a block
# state forces the question of how it retires.
#
# LANE_RESET_FILLS: per-leaf-name retire fill. Metadata is invalidated
# (pos := -1 makes a slot invisible everywhere; mem_len := 0 makes the
# cross-memory slab unreadable), recurrences and clocks zero. Matches
# core.cache.reset_lanes (parity asserted in tests/test_scheduler.py).
LANE_RESET_FILLS = {"pos": -1, "beta": 1.0, "aux": 0.0, "h": 0.0,
                    "conv": 0.0, "mem_len": 0}
# LANE_PAYLOAD_LEAVES: bulk K/V bytes an ordinary retire leaves in
# place (invisible once their metadata is cleared, overwritten by the
# next insert anyway) but a QUARANTINE must zero — a NaN payload byte
# survives metadata masking (0 x NaN = NaN in the p@v product).
LANE_PAYLOAD_LEAVES = ("k", "v", "xk", "xv")


def init_block_state(cfg, kind: str, batch: int, budget: int, dtype):
    if kind in ("global", "local", "cross"):
        M = min(budget, cfg.window) if (kind == "local" and cfg.window > 0) \
            else budget
        cache = init_cache(batch, cfg.num_kv_heads, M, cfg.head_dim, dtype)
        if kind != "cross":
            return cache
        S = memory_len(cfg)
        return {
            "cache": cache,
            "xk": jnp.zeros((batch, S, cfg.num_kv_heads, cfg.head_dim),
                            dtype),
            "xv": jnp.zeros((batch, S, cfg.num_kv_heads, cfg.head_dim),
                            dtype),
            # per-lane valid memory length: cross-attention masks slots
            # >= mem_len, so a lane with 0 reads NO memory at all (the
            # state a reset lane is left in — stale xk/xv bytes become
            # unreadable, like pos := -1 for the KV cache)
            "mem_len": jnp.zeros((batch,), jnp.int32),
        }
    if kind == "recurrent":
        w = cfg.lru_width
        return {
            "h": jnp.zeros((batch, w), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, w), dtype),
        }
    if kind == "mamba":
        return {
            "h": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, cfg.d_inner),
                              dtype),
        }
    raise ValueError(kind)


# ================================================================ helpers


def _split_heads(x, n_heads, head_dim):
    return x.reshape(x.shape[:-1] + (n_heads, head_dim))


def _causal_conv_train(x, w, b):
    """x: [B,T,C], w: [W,C] depthwise causal conv."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(W))
    return out + b


def _causal_conv_step(x_t, conv_state, w, b):
    """x_t: [B,C]; conv_state: [B,W-1,C] (previous inputs, oldest first)."""
    full = jnp.concatenate([conv_state, x_t[:, None]], axis=1)  # [B,W,C]
    out = jnp.einsum("bwc,wc->bc", full, w) + b
    return out, full[:, 1:]


def _moe_apply(p, x, cfg):
    """Group-wise GShard-style top-k dispatch (DESIGN.md §5).
    x: [B,T,d] -> (out, aux_loss)."""
    B, T, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    S = B * T
    xf = x.reshape(S, d)
    group = min(2048, S)
    n_groups = S // group if S % group == 0 else 1
    if S % group != 0:
        group = S
    cap = int(np.ceil(group * k / E * cfg.moe_capacity_factor))
    cap = max(cap, k)

    router_logits = (xf.astype(jnp.float32) @ p["router"]["w"])  # [S,E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, k)                  # [S,k]
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

    # load-balance aux (Switch-style): E * mean(frac_routed * mean_prob)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_idx[:, 0], E, dtype=jnp.float32),
                  axis=0)
    aux = E * jnp.sum(me * ce)

    xg = xf.reshape(n_groups, group, d)
    tig = top_idx.reshape(n_groups, group, k)
    tvg = top_vals.reshape(n_groups, group, k)

    wdtype = p["gate_w"].dtype

    @jax.checkpoint
    def one_group(xg_i, ti_i, tv_i):
        # positioning math stays exact (int32 cumsum); the big [g,E,cap]
        # dispatch/combine tensors are built in the WEIGHT dtype (bf16):
        # they hold only 0/1 and routing weights, and f32 doubled the
        # dominant memory term of MoE prefill (§Perf mixtral it. 1).
        counts = jnp.zeros((E,), jnp.int32)
        disp = jnp.zeros((group, E, cap), wdtype)
        comb = jnp.zeros((group, E, cap), wdtype)
        for j in range(k):
            oh = jax.nn.one_hot(ti_i[:, j], E, dtype=jnp.int32)  # [g,E]
            pos = jnp.cumsum(oh, axis=0) - 1 + counts[None, :]
            ok = (pos < cap) & (oh > 0)
            pos_oh = jax.nn.one_hot(jnp.where(ok, pos, cap), cap,
                                    dtype=wdtype)                # [g,E,cap]
            sel = (oh * ok).astype(wdtype)[..., None] * pos_oh
            disp = disp + sel
            comb = comb + sel * tv_i[:, j][:, None, None].astype(wdtype)
            counts = counts + jnp.sum(oh, axis=0)
        xin = jnp.einsum("gec,gd->ecd", disp, xg_i.astype(wdtype))
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, p["gate_w"]))
        u = jnp.einsum("ecd,edf->ecf", xin, p["up_w"])
        eo = jnp.einsum("ecf,efd->ecd", h * u, p["down_w"])
        out = jnp.einsum("gec,ecd->gd", comb, eo,
                         preferred_element_type=jnp.float32)
        return out.astype(x.dtype)

    if n_groups == 1:
        out = one_group(xg[0], tig[0], tvg[0])[None]
    else:
        def body(_, i):
            return None, one_group(xg[i], tig[i], tvg[i])
        _, out = jax.lax.scan(body, None, jnp.arange(n_groups))
    return out.reshape(B, T, d), aux


def _ffn_apply(p, x, cfg):
    if cfg.family == "moe" and cfg.num_experts > 0:
        return _moe_apply(p, x, cfg)
    return mlp_apply(p, x), jnp.zeros((), jnp.float32)


def _rg_lru_scan(a_log, bx, h0):
    """h_t = a_t * h_{t-1} + sqrt(1-a_t^2) * bx_t via associative scan.
    a_log: [B,T,W] (log a, <=0); bx: [B,T,W]; h0: [B,W]."""
    a = jnp.exp(a_log)
    gated = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * bx

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    A, Bc = jax.lax.associative_scan(combine, (a, gated), axis=1)
    return A * h0[:, None] + Bc          # [B,T,W]


# ============================================================== attention


def _qkv(p, cfg, normed, positions):
    q = _split_heads(dense_apply(p["wq"], normed), cfg.num_heads,
                     cfg.head_dim)
    kk = _split_heads(dense_apply(p["wk"], normed), cfg.num_kv_heads,
                      cfg.head_dim)
    v = _split_heads(dense_apply(p["wv"], normed), cfg.num_kv_heads,
                     cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta)
    kk = apply_rope(kk, positions, cfg.rope_theta)
    return q, kk, v


def _attend_full(cfg, q, k, v, *, log_beta=None, causal=True, window=0,
                 q_offset=0, attn_impl="xla", kv_positions=None):
    """Full-sequence attention, context-parallel when configured.

    Context parallelism (§Perf train iteration 2): shard_map over the
    "model" axis, splitting the QUERY-TIME dim; k/v (+ per-key retention
    bias) are replicated within each shard — cheap under GQA (kv_dim <<
    q_dim). Each shard runs the same streaming-block attention on T/cp
    query rows at the right absolute offset. Falls back to the plain
    path when no CP mesh is registered or T doesn't divide.

    attn_impl "pallas" runs the retention flash kernel instead of the
    XLA streaming path — on the plain path AND inside each CP shard:
    the kernel takes the (traced) absolute q_offset, so the shard
    prefill no longer silently falls back to XLA.

    kv_positions: optional [B, Tk] absolute key positions with -1
    marking MASKED keys (the padded tail of a ragged cross-memory
    batch; chunked_attention drops pos<0 keys from every query). Only
    the plain XLA path supports it — callers that pass it (the
    bidirectional encoder) never select pallas or context parallelism.
    """
    kw = dict(log_beta=log_beta, causal=causal, window=window,
              q_block=cfg.attn_q_block, kv_block=cfg.attn_kv_block,
              unroll=cfg.unroll_layers)

    def attend(q_l, k_f, v_f, lb_f, off):
        if attn_impl == "pallas":
            from repro.kernels import ops as kernel_ops
            return kernel_ops.retention_attention(
                q_l, k_f, v_f, lb_f, causal=causal, window=window,
                q_offset=off, impl="pallas")
        return chunked_attention(q_l, k_f, v_f, q_offset=off,
                                 kv_positions=kv_positions,
                                 **{**kw, "log_beta": lb_f})

    if kv_positions is not None:
        if attn_impl == "pallas":
            raise NotImplementedError(
                "kv_positions masking is an XLA-path feature "
                "(encoder / cross-memory attention never runs pallas)")
        return attend(q, k, v, log_beta, q_offset)
    T = q.shape[1]
    mesh = None
    if cfg.context_parallel:
        from repro.sharding import get_cp_mesh
        mesh = get_cp_mesh()
    if mesh is None or "model" not in mesh.shape or \
            T % mesh.shape["model"] != 0:
        return attend(q, k, v, log_beta, q_offset)
    from jax.sharding import PartitionSpec as P
    cp = mesh.shape["model"]
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = dp if q.shape[0] % _mesh_size(mesh, dp) == 0 else None
    T_loc = T // cp

    def local_attn(q_l, k_f, v_f, lb_f):
        off = jax.lax.axis_index("model") * T_loc
        if log_beta is None:
            lb_f = None
        return attend(q_l, k_f, v_f, lb_f, q_offset + off)

    lb = log_beta if log_beta is not None else \
        jnp.zeros((q.shape[0], T, k.shape[2]), jnp.float32)
    return jax.shard_map(
        local_attn, mesh=mesh,
        in_specs=(P(dp, "model", None, None), P(dp), P(dp), P(dp)),
        out_specs=P(dp, "model", None, None),
        check_vma=False)(q, k, v, lb)


def _mesh_size(mesh, axes) -> int:
    size = 1
    for a in (axes or ()):
        size *= mesh.shape[a]
    return size


def self_attn_train(p, g, cfg, x, kind, *, gated, cap_M, q_offset=0,
                    causal=True, kv_positions=None):
    """Training-mode (full-sequence) self-attention; retention-gated when
    `gated` (paper Eq. 3). kv_positions: optional [B, T] key positions
    with -1 masking padded keys (ragged bidirectional encoder batches).
    Returns (out, aux)."""
    B, T, _ = x.shape
    normed = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    positions = q_offset + jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    q, k, v = _qkv(p["attn"], cfg, normed, positions)
    log_beta = None
    aux = {"cap": jnp.zeros((), jnp.float32),
           "beta": None}
    if gated and g is not None:
        log_beta = gates_lib.gate_log_beta(g, normed)     # [B,T,Hkv]
        aux["beta"] = jnp.exp(log_beta)
        if cap_M is not None:
            # log-space path: bounded gradients (see capacity_loss_chunked)
            aux["cap"] = capacity_loss_chunked(aux["beta"], cap_M,
                                               log_beta=log_beta)
    window = cfg.window if kind == "local" else 0
    out = _attend_full(cfg, q, k, v, log_beta=log_beta, causal=causal,
                       window=window, q_offset=q_offset,
                       kv_positions=kv_positions)
    out = dense_apply(p["attn"]["wo"], out.reshape(B, T, cfg.q_dim))
    return out, aux


def cross_attn_apply(p, cfg, x, memory_kv, mem_len=None):
    """x: [B,T,d] or [B,d]; memory_kv = (xk, xv): [B,S,Hkv,Dh].
    mem_len: optional scalar or [B] valid memory length — keys at
    slots >= mem_len are masked out of every query (the padded tail of
    a ragged cross-memory batch; a lane with mem_len 0 attends to
    NOTHING and the output for that row is exactly zero)."""
    single = x.ndim == 2
    if single:
        x = x[:, None]
    B, T, _ = x.shape
    q = _split_heads(dense_apply(p["wq"], x), cfg.num_heads, cfg.head_dim)
    xk, xv = memory_kv
    S = xk.shape[1]
    if mem_len is None:
        kv_pos = jnp.zeros((B, S), jnp.int32)
    else:
        kv_pos = jnp.broadcast_to(memory_pos(mem_len, S)[:, 0], (B, S))
    out = chunked_attention(q, xk, xv, causal=False,
                            kv_positions=kv_pos,
                            q_block=cfg.attn_q_block,
                            kv_block=cfg.attn_kv_block,
                            unroll=cfg.unroll_layers)
    out = dense_apply(p["wo"], out.reshape(B, T, cfg.q_dim))
    return out[:, 0] if single else out


def make_memory_kv(p, cfg, memory):
    """Precompute cross-attn K/V from memory tokens [B,S,d]."""
    xk = _split_heads(dense_apply(p["wk"], memory), cfg.num_kv_heads,
                      cfg.head_dim)
    xv = _split_heads(dense_apply(p["wv"], memory), cfg.num_kv_heads,
                      cfg.head_dim)
    return xk, xv


# ======================================================== block: train


def apply_block_train(p, g, cfg, kind, x, *, gated=False, cap_M=None,
                      memory=None, mem_len=None, causal=True,
                      kv_positions=None):
    aux = {"cap": jnp.zeros((), jnp.float32), "beta": None,
           "router": jnp.zeros((), jnp.float32)}
    if kind in ("global", "local", "cross"):
        attn_out, a_aux = self_attn_train(p, g, cfg, x, kind, gated=gated,
                                          cap_M=cap_M, causal=causal,
                                          kv_positions=kv_positions)
        aux.update({k2: a_aux[k2] for k2 in ("cap", "beta")})
        x = x + attn_out
        if kind == "cross":
            normed = rmsnorm_apply(p["normx"], x, cfg.norm_eps)
            mem_kv = make_memory_kv(p["xattn"], cfg, memory)
            xo = cross_attn_apply(p["xattn"], cfg, normed, mem_kv,
                                  mem_len=mem_len)
            x = x + jnp.tanh(p["xgate"]).astype(x.dtype) * xo
        normed2 = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        ffn_out, router_aux = _ffn_apply(p["ffn"], normed2, cfg)
        aux["router"] = router_aux
        return x + ffn_out, aux
    if kind == "recurrent":
        normed = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        xb = dense_apply(p["in_x"], normed)
        gate = jax.nn.gelu(dense_apply(p["in_gate"], normed))
        xb = _causal_conv_train(xb, p["conv_w"], p["conv_b"])
        r = jax.nn.sigmoid(dense_apply(p["lru_wa"], xb).astype(jnp.float32))
        i = jax.nn.sigmoid(dense_apply(p["lru_wx"], xb).astype(jnp.float32))
        a_log = -RG_LRU_C * jax.nn.softplus(p["lru_lam"]) * r
        bx = i * xb.astype(jnp.float32)
        h0 = jnp.zeros((x.shape[0], cfg.lru_width), jnp.float32)
        h = _rg_lru_scan(a_log, bx, h0).astype(x.dtype)
        x = x + dense_apply(p["out"], h * gate)
        normed2 = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        ffn_out, _ = _ffn_apply(p["ffn"], normed2, cfg)
        return x + ffn_out, aux
    if kind == "mamba":
        out = _mamba_train(p, cfg, x)
        return x + out, aux
    raise ValueError(kind)


def _mamba_train(p, cfg, x):
    B, T, _ = x.shape
    di, n, r = cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    normed = rmsnorm_apply(p["norm"], x, cfg.norm_eps)
    xz = dense_apply(p["in_proj"], normed)
    xs, z = jnp.split(xz, 2, axis=-1)
    xs = jax.nn.silu(_causal_conv_train(xs, p["conv_w"], p["conv_b"]))
    proj = dense_apply(p["x_proj"], xs)
    dt_in, Bm, Cm = jnp.split(proj, [r, r + n], axis=-1)
    dt = jax.nn.softplus(dense_apply(p["dt_proj"], dt_in)
                         .astype(jnp.float32))                 # [B,T,di]
    A = -jnp.exp(p["A_log"])                                   # [di,n]
    dA = jnp.exp(dt[..., None] * A)                            # [B,T,di,n]
    dBx = (dt * xs.astype(jnp.float32))[..., None] * \
        Bm[:, :, None, :].astype(jnp.float32)                  # [B,T,di,n]

    def step(h, inputs):
        dA_t, dBx_t, C_t = inputs
        h = dA_t * h + dBx_t                                   # [B,di,n]
        y = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y

    h0 = jnp.zeros((B, di, n), jnp.float32)
    xs_seq = (jnp.moveaxis(dA, 1, 0), jnp.moveaxis(dBx, 1, 0),
              jnp.moveaxis(Cm.astype(jnp.float32), 1, 0))
    _, ys = jax.lax.scan(step, h0, xs_seq)                     # [T,B,di]
    y = jnp.moveaxis(ys, 0, 1) + xs.astype(jnp.float32) * p["D"]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return dense_apply(p["out_proj"], y)


# ======================================================= block: decode


def _select_rows(mask, new, old):
    """Per-lane (batch-axis-0) select over a block-state pytree: lanes
    where mask is False keep their old state BIT-identically — the
    mechanism that freezes retired/empty lanes under continuous
    batching (a where on the carried state, not a scatter)."""
    def sel(n, o):
        m = mask.reshape((mask.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree.map(sel, new, old)


def apply_block_decode(p, g, cfg, kind, x_t, state, t, *, policy,
                       attn_impl="xla", active=None, return_sig=False):
    """x_t: [B, d]; t: absolute position — scalar int32, or [B] when
    each lane runs on its own clock (continuous batching). Returns
    (x_out [B,d], new_state, probs_or_None). attn_impl: "xla" (grouped
    einsum over the slot cache) or "pallas" (flash-decode kernel;
    interpret mode off-TPU). active: optional [B] bool — lanes marked
    False are masked to the identity: their caches, recurrences and
    policy aux come back bit-identical (retired/empty scheduler
    lanes). return_sig: the speculative verify path (phase A) — the
    third return becomes this position's commit signal instead of the
    raw probs: attention kinds -> {k, v, beta, pkv, auxn} (everything
    cache_replay needs to re-run the eviction transaction), recurrent/
    mamba -> the unmasked {h, conv} tail snapshot. The signal is a
    byproduct of values this function computes anyway, so requesting
    it cannot perturb the decode result."""
    if kind in ("global", "local", "cross"):
        cache = state["cache"] if kind == "cross" else state
        normed = rmsnorm_apply(p["norm1"], x_t, cfg.norm_eps)
        pos = jnp.broadcast_to(jnp.asarray(t, jnp.int32),
                               (x_t.shape[0],))[:, None]
        q, k, v = _qkv(p["attn"], cfg, normed[:, None], pos)
        q_t, k_t, v_t = q[:, 0], k[:, 0], v[:, 0]              # [B,H,D]
        if g is not None and cfg.trimkv:
            beta_t = gates_lib.gate_beta(g, normed)            # [B,Hkv]
        else:
            beta_t = jnp.ones((x_t.shape[0], cfg.num_kv_heads), jnp.float32)
        window = cfg.window if kind == "local" else 0
        # Alg. 1: attend over (cache ∪ provisional new token), THEN
        # evict-if-full — one pass over the old cache serves both the
        # attention read and the eviction blend (§Perf iteration 4)
        # the named scopes (decode_attn, kv_insert) label these ops in
        # the compiled HLO and so in a profiler trace of the chip
        with jax.named_scope("decode_attn"):
            if attn_impl == "pallas":
                # lazy import: the pallas toolchain loads only when the
                # serving path actually selects it (ops.py convention)
                from repro.kernels import ops as kernel_ops
                out, probs, p_new = kernel_ops.decode_attention(
                    q_t, cache["k"], cache["v"], cache["pos"], t,
                    window=window, new_kv=(k_t, v_t), return_probs=True,
                    impl="pallas")
            else:
                out, probs, p_new = decode_attend(
                    q_t, cache, window=window, t=t, new_kv=(k_t, v_t))
        pkv = _probs_to_kv(probs, cfg)
        inc = 1.0 if policy.name == "trimkv" else None
        aux_new = (_probs_to_kv(p_new[..., None], cfg)[..., 0]
                   if policy.needs_attn else None)
        with jax.named_scope("kv_insert"):
            cache = policy.decode_update(cache, pkv, active=active)
            cache = cache_insert(cache, k_t, v_t, beta_t, t,
                                 policy.keep_scores, incoming_score=inc,
                                 incoming_aux=aux_new)
        x = x_t + dense_apply(p["attn"]["wo"],
                              out.reshape(x_t.shape[0], cfg.q_dim)
                              .astype(x_t.dtype))
        if kind == "cross":
            normedx = rmsnorm_apply(p["normx"], x, cfg.norm_eps)
            xo = _cross_attn_decode(p["xattn"], cfg, normedx, state, t,
                                    attn_impl)
            x = x + jnp.tanh(p["xgate"]).astype(x.dtype) * xo
        normed2 = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        ffn_out, _ = _ffn_apply(p["ffn"], normed2[:, None], cfg)
        new_state = ({"cache": cache, "xk": state["xk"], "xv": state["xv"],
                      "mem_len": state["mem_len"]}
                     if kind == "cross" else cache)
        if active is not None:
            new_state = _select_rows(active, new_state, state)
        if return_sig:
            auxn = (aux_new if aux_new is not None
                    else _probs_to_kv(p_new[..., None], cfg)[..., 0])
            sig = {"k": k_t, "v": v_t, "beta": beta_t, "pkv": pkv,
                   "auxn": auxn}
            return x + ffn_out[:, 0], new_state, sig
        return x + ffn_out[:, 0], new_state, probs
    if kind == "recurrent":
        normed = rmsnorm_apply(p["norm1"], x_t, cfg.norm_eps)
        xb = dense_apply(p["in_x"], normed)
        gate = jax.nn.gelu(dense_apply(p["in_gate"], normed))
        xb, conv_state = _causal_conv_step(xb, state["conv"], p["conv_w"],
                                           p["conv_b"])
        r = jax.nn.sigmoid(dense_apply(p["lru_wa"], xb).astype(jnp.float32))
        i = jax.nn.sigmoid(dense_apply(p["lru_wx"], xb).astype(jnp.float32))
        a = jnp.exp(-RG_LRU_C * jax.nn.softplus(p["lru_lam"]) * r)
        h = a * state["h"] + jnp.sqrt(jnp.maximum(1 - a * a, 1e-12)) * \
            (i * xb.astype(jnp.float32))
        x = x_t + dense_apply(p["out"], (h.astype(x_t.dtype) * gate))
        normed2 = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        ffn_out, _ = _ffn_apply(p["ffn"], normed2[:, None], cfg)
        sig = {"h": h, "conv": conv_state}
        new_state = sig
        if active is not None:
            new_state = _select_rows(active, new_state, state)
        return x + ffn_out[:, 0], new_state, (sig if return_sig else None)
    if kind == "mamba":
        out, sig = _mamba_step(p, cfg, x_t, state)
        new_state = sig
        if active is not None:
            new_state = _select_rows(active, new_state, state)
        return x_t + out, new_state, (sig if return_sig else None)
    raise ValueError(kind)


def _cross_attn_decode(p, cfg, x_t, state, t, attn_impl):
    """Decode-time cross-attention over the per-lane memory slab,
    masked by state["mem_len"] — the memory is presented as a pseudo
    slot cache (valid slots at position 0, slots >= mem_len at -1), so
    both impls reuse the decode-attention mask plumbing: the XLA path
    runs cache.memory_attend (grouped einsum, no materialized GQA
    repeat) and the pallas path runs the flash-decode kernel. A lane
    whose memory was invalidated (mem_len == 0, e.g. reset between
    requests) reads exactly zero memory. x_t: [B, d] -> [B, d]."""
    B = x_t.shape[0]
    q = _split_heads(dense_apply(p["wq"], x_t), cfg.num_heads,
                     cfg.head_dim)                         # [B,Hq,Dh]
    S = state["xk"].shape[1]
    if attn_impl == "pallas":
        # lazy import: the pallas toolchain loads only when the serving
        # path actually selects it (ops.py convention)
        from repro.kernels import ops as kernel_ops
        pos = jnp.broadcast_to(memory_pos(state["mem_len"], S),
                               (B, cfg.num_kv_heads, S))
        out = kernel_ops.decode_attention(
            q, jnp.moveaxis(state["xk"], 1, 2),
            jnp.moveaxis(state["xv"], 1, 2), pos, t, impl="pallas")
    else:
        out = memory_attend(q, state["xk"], state["xv"],
                            state["mem_len"])
    return dense_apply(p["wo"],
                       out.reshape(B, cfg.q_dim).astype(x_t.dtype))


def _probs_to_kv(probs, cfg):
    """Fold grouped-query probs [B,Hq,M] to per-kv-head [B,Hkv,M]."""
    B, Hq, M = probs.shape
    group = Hq // cfg.num_kv_heads
    return probs.reshape(B, cfg.num_kv_heads, group, M).mean(axis=2)


def _mamba_step(p, cfg, x_t, state):
    di, n, r = cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    normed = rmsnorm_apply(p["norm"], x_t, cfg.norm_eps)
    xz = dense_apply(p["in_proj"], normed)
    xs, z = jnp.split(xz, 2, axis=-1)
    xs, conv_state = _causal_conv_step(xs, state["conv"], p["conv_w"],
                                       p["conv_b"])
    xs = jax.nn.silu(xs)
    proj = dense_apply(p["x_proj"], xs)
    dt_in, Bm, Cm = jnp.split(proj, [r, r + n], axis=-1)
    dt = jax.nn.softplus(dense_apply(p["dt_proj"], dt_in)
                         .astype(jnp.float32))                 # [B,di]
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt[..., None] * A)                            # [B,di,n]
    dBx = (dt * xs.astype(jnp.float32))[..., None] * \
        Bm[:, None, :].astype(jnp.float32)
    h = dA * state["h"] + dBx
    y = jnp.einsum("bdn,bn->bd", h, Cm.astype(jnp.float32))
    y = y + xs.astype(jnp.float32) * p["D"]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x_t.dtype)
    return dense_apply(p["out_proj"], y), {"h": h, "conv": conv_state}


# ============================================ block: speculative verify
#
# Draft/verify speculative decoding (docs/serving.md §Speculative
# decoding) scores C = spec_k + 1 candidate positions per lane in ONE
# dispatch, in two phases:
#
#   * apply_block_verify (phase A, "score"): an inner lax.scan runs
#     apply_block_decode ITSELF — the same function, on the same
#     [B, d] shapes — once per candidate position against an evolving
#     SCRATCH copy of the block state, with return_sig=True so each
#     position's eviction transaction (k/v/beta, per-slot probs,
#     incoming aux — on the pallas impl straight from the flash-decode
#     kernel's probs/p_new outputs, i.e. the kernels reconstruct the
#     eviction signal for speculated positions exactly as for real
#     ones) is recorded on the side. Because every op is literally the
#     decode op at the decode shape, the logits at every correctly-fed
#     position are bit-identical to sequential decode BY CONSTRUCTION
#     (no reliance on chunk-vs-decode GEMM accumulation order, which
#     XLA does NOT guarantee row-stable across batch shapes): position
#     0 is always fed the true carry, and if draft j-1 matched the
#     model's token, position j saw exactly the cache sequential
#     decode would have had. The scratch state is DISCARDED.
#   * apply_block_verify_commit (phase B, "commit" = bounded rollback):
#     once the accepted prefix length n_commit[b] is known, replay only
#     the first n_commit positions' transactions from the ROUND-ENTRY
#     state (core.cache.cache_replay); rejected positions never touch
#     durable state, so they cannot have perturbed victim selection
#     under ANY eviction policy. Recurrent/SSM/conv tails are committed
#     by selecting the stacked per-position snapshot at n_commit - 1.
#
# MoE blocks are NOT verifiable: _moe_apply's expert capacity couples
# rows across (B, T), breaking per-row bit-identity — the serving layer
# refuses spec_k > 0 for that family (the same coupling already breaks
# its dense parity oracle, see ROADMAP).


def apply_block_verify(p, g, cfg, kind, x, state, t, *, policy,
                       attn_impl="xla", live=None):
    """Phase A of a speculative verify round. x: [B, C, d] residual
    stream for the C candidate positions; t: round-entry per-lane clock
    ([B] or scalar); live: [B] bool lanes in this round. Returns
    (x_out [B, C, d], sig) where sig carries everything phase B needs,
    stacked on axis 1: attention kinds -> {k, v, beta, pkv, auxn}
    per-position eviction signals; recurrent/mamba -> {h, conv}
    per-position state snapshots. The state itself is NOT mutated (the
    scratch state the inner scan evolves is discarded)."""
    B, C, _ = x.shape
    if live is None:
        live = jnp.ones((B,), bool)
    if cfg.family == "moe" and cfg.num_experts > 0:
        raise ValueError(
            "speculative verify is unsupported for MoE blocks: expert "
            "capacity couples tokens across the [B, C] grid, so "
            "speculative scoring cannot be bit-identical per row")
    tb = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))

    def step(st, xs):
        x_t, j = xs
        x_o, st, sig_t = apply_block_decode(
            p, g, cfg, kind, x_t, st, tb + j, policy=policy,
            attn_impl=attn_impl, active=live, return_sig=True)
        return st, (x_o, sig_t)

    _, (rows, sig_c) = jax.lax.scan(
        step, state,
        (jnp.moveaxis(x, 1, 0), jnp.arange(C, dtype=jnp.int32)))
    return (jnp.moveaxis(rows, 0, 1),
            jax.tree.map(lambda a: jnp.moveaxis(a, 0, 1), sig_c))


def apply_block_verify_commit(cfg, kind, state, sig, t, n_commit, live,
                              policy):
    """Phase B of a speculative verify round: commit the accepted
    prefix (bounded rollback). state: the ROUND-ENTRY block state;
    sig: apply_block_verify's per-position signal pack; n_commit: [B]
    accepted-prefix length (0..C, 0 for non-live lanes); t: round-entry
    clock. Attention kinds replay the first n_commit positions' cache
    transactions (core.cache.cache_replay — rejected positions never
    touch durable state); recurrent/mamba tails select the stacked
    snapshot at position n_commit - 1; cross xk/xv/mem_len are
    untouched (memory is read-only at decode). Returns the new block
    state, bit-identical to sequentially decoding only the accepted
    prefix."""
    if kind in ("global", "local", "cross"):
        cache = state["cache"] if kind == "cross" else state
        inc = 1.0 if policy.name == "trimkv" else None
        new_cache = cache_replay(cache, sig["k"], sig["v"], sig["beta"],
                                 sig["pkv"], sig["auxn"], t, n_commit,
                                 live, policy, incoming_score=inc)
        if kind == "cross":
            return {"cache": new_cache, "xk": state["xk"],
                    "xv": state["xv"], "mem_len": state["mem_len"]}
        return new_cache
    take = live & (n_commit > 0)

    def sel(stacked, old):
        idx = jnp.maximum(n_commit - 1, 0).astype(jnp.int32)
        idx = idx.reshape((-1,) + (1,) * (stacked.ndim - 1))
        picked = jnp.take_along_axis(stacked, idx, axis=1)[:, 0]
        m = take.reshape((-1,) + (1,) * (old.ndim - 1))
        return jnp.where(m, picked, old)

    return {kk: sel(sig[kk], state[kk]) for kk in ("h", "conv")}


# ====================================================== block: prefill


def apply_block_prefill(p, g, cfg, kind, x, state, *, policy, budget,
                        memory=None, mem_len=None, obs_window=32,
                        q_offset=0, attn_impl="xla"):
    """Single-shot prefill over x [B,T,d] with an empty prior state:
    full (chunked) attention over the sequence, then compress the chunk
    into the bounded cache via top-M keep scores. memory: [B,S,d] cross
    tokens (vision / encoder output); mem_len: per-row valid memory
    length ([B] or scalar; None = all S rows valid). Returns
    (x_out, new_state, aux). attn_impl "pallas" routes the sequence
    attention through the retention flash kernel (any q_offset, even
    traced — the CP shard path runs the kernel per shard; interpret
    off-TPU)."""
    B, T, _ = x.shape
    if kind in ("global", "local", "cross"):
        cache_in = state["cache"] if kind == "cross" else state
        normed = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        positions = q_offset + jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        q, k, v = _qkv(p["attn"], cfg, normed, positions)
        window = cfg.window if kind == "local" else 0
        # pallas routes through _attend_full too: the retention kernel
        # honors a (traced) q_offset, so the context-parallel shard
        # prefill runs the kernel per shard instead of falling back
        out = _attend_full(cfg, q, k, v, causal=True, window=window,
                           q_offset=q_offset, attn_impl=attn_impl)
        if g is not None and cfg.trimkv:
            beta_c = jnp.moveaxis(gates_lib.gate_beta(g, normed), 1, 2)
        else:
            beta_c = jnp.ones((B, cfg.num_kv_heads, T), jnp.float32)
        # policy aux for chunk tokens: pooled attention of the last
        # obs_window queries over all keys (SnapKV/H2O prefill signal)
        aux_c = jnp.zeros((B, cfg.num_kv_heads, T), jnp.float32)
        if policy.needs_attn:
            W = min(obs_window, T)
            q_obs = q[:, -W:]
            probs = _obs_probs(q_obs, k, positions, q_offset + T - W,
                               window)
            aux_c = probs                                      # [B,Hkv,T]
        k_c = jnp.moveaxis(k, 1, 2)                            # [B,Hkv,T,D]
        v_c = jnp.moveaxis(v, 1, 2)
        pos_c = jnp.broadcast_to(positions[:, None],
                                 (B, cfg.num_kv_heads, T)).astype(jnp.int32)
        t_end = q_offset + T - 1
        chunk_scores = policy.chunk_scores(pos_c=pos_c, beta_c=beta_c,
                                           aux_c=aux_c, k_c=k_c, t=t_end)
        cache = cache_topm_merge(cache_in, k_c, v_c, beta_c, pos_c, aux_c,
                                 t_end, policy.keep_scores, chunk_scores)
        x = x + dense_apply(p["attn"]["wo"], out.reshape(B, T, cfg.q_dim))
        new_state = cache
        if kind == "cross":
            mem_kv = make_memory_kv(p["xattn"], cfg, memory)
            normedx = rmsnorm_apply(p["normx"], x, cfg.norm_eps)
            xo = cross_attn_apply(p["xattn"], cfg, normedx, mem_kv,
                                  mem_len=mem_len)
            x = x + jnp.tanh(p["xgate"]).astype(x.dtype) * xo
            ml = jnp.full((B,), memory.shape[1], jnp.int32) \
                if mem_len is None else \
                jnp.broadcast_to(jnp.asarray(mem_len, jnp.int32), (B,))
            new_state = {"cache": cache, "xk": mem_kv[0],
                         "xv": mem_kv[1], "mem_len": ml}
        normed2 = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        ffn_out, _ = _ffn_apply(p["ffn"], normed2, cfg)
        return x + ffn_out, new_state, None
    if kind == "recurrent":
        # run the train-mode block, and reconstruct the final recurrent
        # state (h after T steps + last W-1 pre-conv inputs) for decoding
        normed = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        xb_raw = dense_apply(p["in_x"], normed)
        gate = jax.nn.gelu(dense_apply(p["in_gate"], normed))
        xb = _causal_conv_train(xb_raw, p["conv_w"], p["conv_b"])
        r = jax.nn.sigmoid(dense_apply(p["lru_wa"], xb).astype(jnp.float32))
        i = jax.nn.sigmoid(dense_apply(p["lru_wx"], xb).astype(jnp.float32))
        a_log = -RG_LRU_C * jax.nn.softplus(p["lru_lam"]) * r
        bx = i * xb.astype(jnp.float32)
        h_seq = _rg_lru_scan(a_log, bx, state["h"])
        h_last = h_seq[:, -1]
        x = x + dense_apply(p["out"], h_seq.astype(x.dtype) * gate)
        normed2 = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        ffn_out, _ = _ffn_apply(p["ffn"], normed2, cfg)
        new_state = {"h": h_last,
                     "conv": _conv_tail(xb_raw, cfg.conv_width)}
        return x + ffn_out, new_state, None
    if kind == "mamba":
        out, new_state = _mamba_prefill(p, cfg, x, state)
        return x + out, new_state, None
    raise ValueError(kind)


def _conv_tail(xb_raw, W):
    """Last W-1 pre-conv inputs, left-padded if the sequence is short."""
    B, T, C = xb_raw.shape
    if T >= W - 1:
        return xb_raw[:, T - (W - 1):]
    pad = (W - 1) - T
    return jnp.pad(xb_raw, ((0, 0), (pad, 0), (0, 0)))


def _mamba_prefill(p, cfg, x, state):
    B, T, _ = x.shape
    di, n, r = cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    normed = rmsnorm_apply(p["norm"], x, cfg.norm_eps)
    xz = dense_apply(p["in_proj"], normed)
    xs_raw, z = jnp.split(xz, 2, axis=-1)
    xs = jax.nn.silu(_causal_conv_train(xs_raw, p["conv_w"], p["conv_b"]))
    proj = dense_apply(p["x_proj"], xs)
    dt_in, Bm, Cm = jnp.split(proj, [r, r + n], axis=-1)
    dt = jax.nn.softplus(dense_apply(p["dt_proj"], dt_in)
                         .astype(jnp.float32))
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt[..., None] * A)
    dBx = (dt * xs.astype(jnp.float32))[..., None] * \
        Bm[:, :, None, :].astype(jnp.float32)

    def step(h, inputs):
        dA_t, dBx_t, C_t = inputs
        h = dA_t * h + dBx_t
        y = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y

    xs_seq = (jnp.moveaxis(dA, 1, 0), jnp.moveaxis(dBx, 1, 0),
              jnp.moveaxis(Cm.astype(jnp.float32), 1, 0))
    h_last, ys = jax.lax.scan(step, state["h"], xs_seq)
    y = jnp.moveaxis(ys, 0, 1) + xs.astype(jnp.float32) * p["D"]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    new_state = {"h": h_last, "conv": _conv_tail(xs_raw, cfg.conv_width)}
    return dense_apply(p["out_proj"], y), new_state


# ================================================ block: chunked prefill


def _chunk_attend(q, k_c, v_c, cache, chunk_pos, window):
    """Attention of a prefill chunk over (existing cache ∪ chunk), with
    per-head cache positions. Materializes [B,Hq,C,M+C] — the XLA
    reference for the flash kernel in kernels/chunk_attention.py (paper
    Sec B.3 chunked-prefill setting); the single-shot prefill and
    dry-run use chunked_attention instead.

    q: [B,C,Hq,D]; k_c,v_c: [B,C,Hkv,D]; chunk_pos: [C] or [B,C] int32
    absolute positions of the chunk tokens, -1 marking padded tail
    positions (padded queries get zero output / zero probs; padded keys
    are never attended; the [B,C] form lets every ragged request in a
    mixed-length admission batch mark its own tail). Returns
    (out [B,C,Hq,D], probs_cache [B,Hkv,C,M] — per-chunk-query attention
    over the cache region, for H2O-style accumulation)."""
    B, C, Hq, D = q.shape
    Hkv = k_c.shape[2]
    M = cache["pos"].shape[-1]
    group = Hq // Hkv
    cp2 = jnp.broadcast_to(jnp.atleast_2d(chunk_pos), (B, C))
    keys = jnp.concatenate(
        [cache["k"].astype(jnp.float32),
         jnp.moveaxis(k_c, 1, 2).astype(jnp.float32)], axis=2)  # [B,Hkv,M+C,D]
    vals = jnp.concatenate(
        [cache["v"].astype(jnp.float32),
         jnp.moveaxis(v_c, 1, 2).astype(jnp.float32)], axis=2)
    pos = jnp.concatenate(
        [cache["pos"],
         jnp.broadcast_to(cp2[:, None], (B, Hkv, C))], axis=2)
    keys_r = jnp.repeat(keys, group, axis=1)
    vals_r = jnp.repeat(vals, group, axis=1)
    pos_r = jnp.repeat(pos, group, axis=1)                   # [B,Hq,M+C]
    # queries head-major before the dot: the "bchd" operand made the
    # CPU backend sum each score in an order that depended on B, so a
    # lane's scores were not bit-identical between batch 1 and batch B
    s = jnp.einsum("bhcd,bhnd->bhcn",
                   jnp.moveaxis(q, 1, 2).astype(jnp.float32), keys_r)
    s = s / np.sqrt(D)
    qpos = cp2[:, None, :, None]
    dist = qpos - pos_r[:, :, None, :]
    mask = (pos_r[:, :, None, :] >= 0) & (dist >= 0)
    if window > 0:
        mask = mask & (dist < window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    out = jnp.einsum("bhcn,bhnd->bchd", p, vals_r)
    probs_cache = p[..., :M].reshape(B, Hkv, group, C, M).mean(axis=2)
    return out.astype(q.dtype), probs_cache


def apply_block_prefill_chunk(p, g, cfg, kind, x, state, t0, *, policy,
                              obs_window=32, n_valid=None,
                              attn_impl="xla"):
    """Continue prefill with chunk x [B,C,d] given existing state.
    t0: absolute position of the chunk's first token — scalar, or [B]
    when lanes run on their own clocks (ragged continuous-batching
    admission: every request's chunk starts at its own position).
    Cross blocks read their memory K/V (and the per-lane mem_len mask)
    from the state — install it up front with
    transformer.install_memory.

    n_valid: number of real tokens in the chunk — None (= all C), a
    scalar (uniform batch), or a [B] vector (ragged prompts: each
    request marks its own tail). Tail positions beyond n_valid are
    PADDING: they carry position -1, are masked out of attention,
    contribute zero policy aux, and can never win a cache slot — so one
    closure shape serves any mix of prompt lengths. Rows whose n_valid
    is 0 (a request already fully prefilled inside a longer grid) are
    frozen bit-identically: their caches, recurrences and clocks come
    back untouched. attn_impl "pallas" routes the chunk attention
    through the flash kernel (kernels.chunk_attention; interpret
    off-TPU)."""
    B, C, _ = x.shape
    ragged = n_valid is not None and jnp.ndim(n_valid) == 1
    row_ok = (n_valid > 0) if ragged else None
    if kind in ("global", "local", "cross"):
        cache = state["cache"] if kind == "cross" else state
        normed = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        idx = jnp.arange(C)
        t0b = jnp.broadcast_to(jnp.asarray(t0, jnp.int32), (B,))
        positions = t0b[:, None] + idx[None, :]
        nvb = (jnp.full((B,), C, jnp.int32) if n_valid is None else
               jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (B,)))
        chunk_pos = jnp.where(idx[None, :] < nvb[:, None], positions,
                              -1).astype(jnp.int32)           # [B,C]
        t_end = t0b + nvb - 1                                 # [B]
        q, k, v = _qkv(p["attn"], cfg, normed, positions)
        window = cfg.window if kind == "local" else 0
        with jax.named_scope("chunk_attn"):
            if attn_impl == "pallas":
                # lazy import: the pallas toolchain loads only when the
                # serving path actually selects it (ops.py convention).
                # needs_attn=False policies discard probs_cache, so the
                # kernel skips those outputs entirely
                from repro.kernels import ops as kernel_ops
                out, probs_cache = kernel_ops.chunk_attention(
                    q, k, v, cache, chunk_pos, window=window,
                    need_probs=policy.needs_attn, impl="pallas")
            else:
                out, probs_cache = _chunk_attend(q, k, v, cache,
                                                 chunk_pos, window)
        if g is not None and cfg.trimkv:
            beta_c = jnp.moveaxis(gates_lib.gate_beta(g, normed), 1, 2)
        else:
            beta_c = jnp.ones((B, cfg.num_kv_heads, C), jnp.float32)
        aux_c = jnp.zeros((B, cfg.num_kv_heads, C), jnp.float32)
        if policy.needs_attn:
            W = min(obs_window, C)
            aux_c = _obs_probs_chunk_lanes(q, k, chunk_pos, nvb,
                                           t_end - W + 1, window, W)
            # accumulate chunk-query attention mass into cache aux (H2O);
            # padded queries were zeroed in the attend, so they add none
            cache = dict(cache)
            cache["aux"] = cache["aux"] + probs_cache.sum(axis=2)
        k_c = jnp.moveaxis(k, 1, 2)
        v_c = jnp.moveaxis(v, 1, 2)
        pos_c = jnp.broadcast_to(chunk_pos[:, None],
                                 (B, cfg.num_kv_heads, C))
        chunk_scores = policy.chunk_scores(pos_c=pos_c, beta_c=beta_c,
                                           aux_c=aux_c, k_c=k_c, t=t_end)
        with jax.named_scope("kv_merge"):
            new_cache = cache_topm_merge(cache, k_c, v_c, beta_c, pos_c,
                                         aux_c, t_end, policy.keep_scores,
                                         chunk_scores)
        x = x + dense_apply(p["attn"]["wo"], out.reshape(B, C, cfg.q_dim))
        new_state = new_cache
        if kind == "cross":
            mem_kv = (state["xk"], state["xv"])
            normedx = rmsnorm_apply(p["normx"], x, cfg.norm_eps)
            xo = cross_attn_apply(p["xattn"], cfg, normedx, mem_kv,
                                  mem_len=state["mem_len"])
            x = x + jnp.tanh(p["xgate"]).astype(x.dtype) * xo
            new_state = {"cache": new_cache, "xk": state["xk"],
                         "xv": state["xv"], "mem_len": state["mem_len"]}
        normed2 = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        ffn_out, _ = _ffn_apply(p["ffn"], normed2, cfg)
        if row_ok is not None:
            # rows with an empty chunk (already fully prefilled inside a
            # longer ragged grid) keep their state bit-identically — the
            # top-M merge above may reorder their slots otherwise
            new_state = _select_rows(row_ok, new_state, state)
        return x + ffn_out, new_state, None
    if kind == "recurrent":
        # continue the recurrence: conv sees [conv_state, chunk]
        normed = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        xb_raw = dense_apply(p["in_x"], normed)
        gate = jax.nn.gelu(dense_apply(p["in_gate"], normed))
        ext = jnp.concatenate([state["conv"], xb_raw], axis=1)
        xb = _conv_with_history(ext, p["conv_w"], p["conv_b"],
                                cfg.conv_width, C)
        r = jax.nn.sigmoid(dense_apply(p["lru_wa"], xb).astype(jnp.float32))
        i = jax.nn.sigmoid(dense_apply(p["lru_wx"], xb).astype(jnp.float32))
        a_log = -RG_LRU_C * jax.nn.softplus(p["lru_lam"]) * r
        bx = i * xb.astype(jnp.float32)
        if n_valid is not None:
            # padded steps become the identity recurrence (a=1, input 0)
            # so the carried h after C steps IS h at the last real token;
            # per-lane n_valid masks each ragged request's own tail
            valid = _valid_steps(n_valid, B, C)[..., None]
            a_log = jnp.where(valid, a_log, 0.0)
            bx = jnp.where(valid, bx, 0.0)
        h_seq = _rg_lru_scan(a_log, bx, state["h"])
        x = x + dense_apply(p["out"], h_seq.astype(x.dtype) * gate)
        normed2 = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        ffn_out, _ = _ffn_apply(p["ffn"], normed2, cfg)
        new_state = {"h": h_seq[:, -1],
                     "conv": _conv_tail_chunk(ext, cfg.conv_width, n_valid)}
        if row_ok is not None:
            new_state = _select_rows(row_ok, new_state, state)
        return x + ffn_out, new_state, None
    if kind == "mamba":
        out, new_state = _mamba_prefill_chunk(p, cfg, x, state,
                                              n_valid=n_valid)
        if row_ok is not None:
            new_state = _select_rows(row_ok, new_state, state)
        return x + out, new_state, None
    raise ValueError(kind)


def _conv_with_history(ext, w, b, W, C):
    """ext: [B, (W-1)+C, ch] — depthwise causal conv emitting C outputs."""
    out = sum(ext[:, i:i + C] * w[i] for i in range(W))
    return out + b


def _valid_steps(n_valid, B, C):
    """[B, C] bool: step j of row b is a real token (j < n_valid_b).
    n_valid may be a scalar (uniform batch) or [B] (ragged)."""
    nvb = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (B,))
    return jnp.arange(C)[None, :] < nvb[:, None]


def _conv_tail_chunk(ext, W, n_valid):
    """Conv state after a (possibly padded) chunk: the W-1 pre-conv
    inputs ending at the last REAL token. ext: [B, (W-1)+C, ch]; real
    inputs occupy ext[:, W-1 : W-1+n_valid]. n_valid scalar or [B]
    (ragged: each row slices at its own tail)."""
    if n_valid is None:
        return ext[:, -(W - 1):]
    B, _, ch = ext.shape
    nvb = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (B,))
    return jax.vmap(
        lambda e, s: jax.lax.dynamic_slice(e, (s, 0), (W - 1, ch)))(ext, nvb)


def _mamba_prefill_chunk(p, cfg, x, state, n_valid=None):
    B, C, _ = x.shape
    di, n, r = cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    normed = rmsnorm_apply(p["norm"], x, cfg.norm_eps)
    xz = dense_apply(p["in_proj"], normed)
    xs_raw, z = jnp.split(xz, 2, axis=-1)
    ext = jnp.concatenate([state["conv"], xs_raw], axis=1)
    xs = jax.nn.silu(_conv_with_history(ext, p["conv_w"], p["conv_b"],
                                        cfg.conv_width, C))
    proj = dense_apply(p["x_proj"], xs)
    dt_in, Bm, Cm = jnp.split(proj, [r, r + n], axis=-1)
    dt = jax.nn.softplus(dense_apply(p["dt_proj"], dt_in)
                         .astype(jnp.float32))
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt[..., None] * A)
    dBx = (dt * xs.astype(jnp.float32))[..., None] * \
        Bm[:, :, None, :].astype(jnp.float32)
    if n_valid is not None:
        # padded steps: h = 1*h + 0 so h_last is h at the last real token
        valid = _valid_steps(n_valid, B, C)[..., None, None]
        dA = jnp.where(valid, dA, 1.0)
        dBx = jnp.where(valid, dBx, 0.0)

    def step(h, inputs):
        dA_t, dBx_t, C_t = inputs
        h = dA_t * h + dBx_t
        return h, jnp.einsum("bdn,bn->bd", h, C_t)

    xs_seq = (jnp.moveaxis(dA, 1, 0), jnp.moveaxis(dBx, 1, 0),
              jnp.moveaxis(Cm.astype(jnp.float32), 1, 0))
    h_last, ys = jax.lax.scan(step, state["h"], xs_seq)
    y = jnp.moveaxis(ys, 0, 1) + xs.astype(jnp.float32) * p["D"]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    new_state = {"h": h_last,
                 "conv": _conv_tail_chunk(ext, cfg.conv_width, n_valid)}
    return dense_apply(p["out_proj"], y), new_state


def _obs_probs_chunk(q, k, chunk_pos, n_valid, obs_start, window, W):
    """Padding-robust obs-window signal for chunked prefill: mean
    attention over the chunk keys of the last W REAL chunk queries.
    The W query rows are cut with a static-shape dynamic_slice ending
    at the last real token (start = clamp(n_valid - W)), so the work
    stays [B,Hq,W,C] — NOT [B,Hq,C,C] — and the padded tail chunk
    reuses the same closure. q: [B,C,Hq,D]; k: [B,C,Hkv,D]; chunk_pos:
    [C] int32 with -1 marking padding -> [B,Hkv,C]."""
    B, C, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    start = jnp.clip(jnp.asarray(n_valid, jnp.int32) - W, 0, C - W)
    q_obs = jax.lax.dynamic_slice_in_dim(q, start, W, axis=1)
    q_pos = jax.lax.dynamic_slice_in_dim(chunk_pos, start, W, axis=0)
    kr = jnp.repeat(k, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q_obs.astype(jnp.float32),
                   kr.astype(jnp.float32)) / np.sqrt(D)
    qpos = q_pos[None, None, :, None]
    kpos = chunk_pos[None, None, None, :]
    dist = qpos - kpos
    mask = (kpos >= 0) & (dist >= 0)
    if window > 0:
        mask = mask & (dist < window)
    s = jnp.where(mask, s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    probs = jnp.where(mask, probs, 0.0)
    # padded rows (q_pos=-1, n_valid < W) drop out of the obs mean
    obs = (q_pos >= obs_start) & (q_pos >= 0)                  # [W]
    n_obs = jnp.maximum(jnp.sum(obs.astype(jnp.float32)), 1.0)
    probs = jnp.sum(probs * obs[None, None, :, None], axis=2) / n_obs
    return probs.reshape(B, Hkv, group, C).mean(axis=2)        # [B,Hkv,C]


def _obs_probs_chunk_lanes(q, k, chunk_pos, n_valid, obs_start, window, W):
    """Per-lane _obs_probs_chunk: under ragged continuous batching each
    request has its own tail (chunk_pos row), valid count and obs-window
    placement, so the static-shape obs slice is vmapped over the batch.
    q: [B,C,Hq,D]; k: [B,C,Hkv,D]; chunk_pos: [B,C]; n_valid/obs_start:
    [B] -> [B,Hkv,C]."""
    def one(qb, kb, cp, nv, start):
        return _obs_probs_chunk(qb[None], kb[None], cp, nv, start,
                                window, W)[0]
    return jax.vmap(one)(q, k, chunk_pos, n_valid, obs_start)


def _obs_probs(q_obs, k, positions, obs_start, window):
    """Mean attention of obs-window queries over all keys, folded to kv
    heads. q_obs: [B,W,Hq,D]; k: [B,T,Hkv,D] -> [B,Hkv,T]."""
    B, W, Hq, D = q_obs.shape
    T, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    kr = jnp.repeat(k, group, axis=2)
    s = jnp.einsum("bwhd,bthd->bhwt", q_obs.astype(jnp.float32),
                   kr.astype(jnp.float32)) / np.sqrt(D)
    q_pos = obs_start + jnp.arange(W)
    dist = q_pos[None, None, :, None] - positions[:, None, None, :]
    mask = dist >= 0
    if window > 0:
        mask = mask & (dist < window)
    s = jnp.where(mask, s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).mean(axis=2)            # [B,Hq,T]
    return probs.reshape(B, Hkv, group, T).mean(axis=2)
