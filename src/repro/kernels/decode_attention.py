"""Pallas TPU kernel: flash-decode over the bounded slot cache.

One query token attends to the M-slot cache (slot-dense layout, empty
slots masked by pos < 0; optional sliding-window mask). This is the
TRIM-KV serving hot path: O(M) per step regardless of context length —
the structural basis of the paper's Table 6 throughput claim.

Grid: (B*Hq, n_m) with online-softmax accumulation across the slot
blocks in VMEM scratch. GQA via index-map aliasing (bh // group).

Serving integration (why this kernel can drive eviction): besides the
attention output it can return the normalized per-slot probabilities and
— when the in-flight token's K/V are passed via ``new_kv`` — the mass
the new token received. Those two signals are exactly what the
attention-aux policies (H2O / R-KV / SnapKV) accumulate, so the kernel
is a drop-in for ``cache.decode_attend``. The in-flight token is a
separate [.., 1, D] operand merged into the online softmax in the final
grid block — NEVER concatenated onto the slot dim: M+1 does not divide
an SPMD mesh and the concat would copy the whole cache every step (the
refuted pattern documented in core/cache.py §Perf iteration 4).

Probs are reconstructed flash-style: each slot block stores its
unnormalized ``exp(s - m_block)`` tile plus the running max at that
block; the final (max, denom) pair rescales every tile outside the
kernel — no [., M] tensor ever lives in VMEM beyond one block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, pos_ref, kn_ref, vn_ref, t_ref,
                   o_ref, *rest, m_block, n_m, window, M, hq, has_new,
                   want_probs):
    if want_probs:
        praw_ref, mblk_ref, mfin_ref, lfin_ref, pn_ref = rest[:5]
        m_scr, l_scr, acc_scr = rest[5:]
    else:
        m_scr, l_scr, acc_scr = rest
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                        # [1, D]
    k = k_ref[0].astype(jnp.float32)                        # [bm, D]
    v = v_ref[0].astype(jnp.float32)
    pos = pos_ref[0]                                        # [1, bm] int32
    # per-lane clock: t is [B] in SMEM (continuous batching runs each
    # lane at its own position); grid dim 0 walks B*Hq rows
    t = t_ref[pl.program_id(0) // hq]
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    slot = mi * m_block + jax.lax.broadcasted_iota(jnp.int32, (1, m_block), 1)
    ok = (pos >= 0) & (slot < M)
    if window > 0:
        ok = ok & ((t - pos) < window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    # all-masked block with m still at NEG_INF: exp(0)=1 — zero it here
    p = jnp.where(ok, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    if want_probs:
        praw_ref[0] = p
        mblk_ref[0, 0] = m_new[:, None]

    @pl.when(mi == n_m - 1)
    def _finish():
        m_fin, l_fin, acc = m_scr[...], l_scr[...], acc_scr[...]
        if has_new:
            # online-softmax merge of the in-flight token (position t:
            # always causal-visible, window distance 0)
            k_n = kn_ref[0].astype(jnp.float32)             # [1, D]
            v_n = vn_ref[0].astype(jnp.float32)
            s_n = jnp.sum(q * k_n, axis=-1) * scale         # [1]
            m2 = jnp.maximum(m_fin, s_n)
            a = jnp.exp(m_fin - m2)
            p_n = jnp.exp(s_n - m2)
            l_fin = l_fin * a + p_n
            acc = acc * a[:, None] + p_n[:, None] * v_n
            m_fin = m2
            if want_probs:
                pn_ref[0] = (p_n / jnp.maximum(l_fin, 1e-30))[:, None]
        o_ref[0] = (acc / jnp.maximum(l_fin, 1e-30)[:, None]
                    ).astype(o_ref.dtype)
        if want_probs:
            mfin_ref[0] = m_fin[:, None]
            lfin_ref[0] = l_fin[:, None]


def decode_attention_pallas(q_t, k_cache, v_cache, pos, t, *, window=0,
                            m_block=512, interpret=True, new_kv=None,
                            return_probs=False):
    """q_t: [B,Hq,D]; k_cache/v_cache: [B,Hkv,M,D]; pos: [B,Hkv,M] int32
    (-1 empty); t: current position — scalar, or [B] when each lane runs
    on its own clock (continuous batching).

    new_kv: optional (k_t, v_t) [B,Hkv,D] — the in-flight token, merged
    into the online softmax as a provisional entry at position t
    (Alg. 1 appends before attending).
    return_probs: also return the normalized attention over the M cache
    slots ([B,Hq,M] f32) and, with new_kv, the new token's own received
    mass ([B,Hq] f32) — the signals the eviction policies consume.

    Returns [B,Hq,D] (q dtype), or (out, probs) / (out, probs, p_new)
    per the flags above.
    """
    B, Hq, D = q_t.shape
    Hkv, M = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    has_new = new_kv is not None

    qh = q_t.reshape(B * Hq, 1, D)
    kh = k_cache.reshape(B * Hkv, M, D)
    vh = v_cache.reshape(B * Hkv, M, D)
    # every per-row operand and output is 3-D (or 4-D) with a unit
    # middle dim, so each block's last two dims equal the array's or
    # are (8, 128)-aligned — the rule the TPU lowering enforces
    ph = pos.reshape(B * Hkv, 1, M)
    if has_new:
        knh = new_kv[0].reshape(B * Hkv, 1, D)
        vnh = new_kv[1].reshape(B * Hkv, 1, D)
    else:
        knh = jnp.zeros((B * Hkv, 1, D), q_t.dtype)
        vnh = jnp.zeros((B * Hkv, 1, D), q_t.dtype)
    m_block = min(m_block, max(M, 8))
    n_m = -(-M // m_block)
    pad = n_m * m_block - M
    if pad:
        kh = jnp.pad(kh, ((0, 0), (0, pad), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pad), (0, 0)))
        ph = jnp.pad(ph, ((0, 0), (0, 0), (0, pad)), constant_values=-1)
    t_arr = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))
    Mp = n_m * m_block

    kernel = functools.partial(_decode_kernel, m_block=m_block, n_m=n_m,
                               window=window, M=M, hq=Hq, has_new=has_new,
                               want_probs=return_probs)
    out_specs = [pl.BlockSpec((1, 1, D), lambda bh, mi: (bh, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((B * Hq, 1, D), q_t.dtype)]
    if return_probs:
        # probs outputs only when asked: a needs_attn=False serving path
        # skips the O(M) f32 praw writes entirely
        row = pl.BlockSpec((1, 1, 1), lambda bh, mi: (bh, 0, 0))
        out_specs += [
            pl.BlockSpec((1, 1, m_block), lambda bh, mi: (bh, 0, mi)),
            pl.BlockSpec((1, 1, 1, 1), lambda bh, mi: (bh, mi, 0, 0)),
            row, row, row,
        ]
        one = jax.ShapeDtypeStruct((B * Hq, 1, 1), jnp.float32)
        out_shape += [
            jax.ShapeDtypeStruct((B * Hq, 1, Mp), jnp.float32),
            jax.ShapeDtypeStruct((B * Hq, n_m, 1, 1), jnp.float32),
            one, one, one,
        ]
    res = pl.pallas_call(
        kernel,
        grid=(B * Hq, n_m),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda bh, mi: (bh, 0, 0)),
            pl.BlockSpec((1, m_block, D), lambda bh, mi: (bh // group, mi, 0)),
            pl.BlockSpec((1, m_block, D), lambda bh, mi: (bh // group, mi, 0)),
            pl.BlockSpec((1, 1, m_block),
                         lambda bh, mi: (bh // group, 0, mi)),
            pl.BlockSpec((1, 1, D), lambda bh, mi: (bh // group, 0, 0)),
            pl.BlockSpec((1, 1, D), lambda bh, mi: (bh // group, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attn",
    )(qh, kh, vh, ph, knh, vnh, t_arr)
    if not return_probs:
        return res[0].reshape(B, Hq, D)
    out, praw, mblk, mfin, lfin, p_new = res
    out = out.reshape(B, Hq, D)
    praw = praw.reshape(B * Hq, Mp)
    mblk = mblk.reshape(B * Hq, n_m)
    mfin, lfin = mfin.reshape(B * Hq, 1), lfin.reshape(B * Hq, 1)
    # flash-style reconstruction: rescale each block's exp(s - m_block)
    # tile by exp(m_block - m_final) and divide by the final denominator
    scale = jnp.exp(jnp.repeat(mblk, m_block, axis=1) - mfin)
    probs = (praw * scale / jnp.maximum(lfin, 1e-30)).reshape(B, Hq, Mp)
    if has_new:
        return out, probs[..., :M], p_new.reshape(B, Hq)
    return out, probs[..., :M]
