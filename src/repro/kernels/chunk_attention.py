"""Pallas TPU kernel: flash chunk-query attention over cache ∪ chunk.

The chunked-prefill hot path (paper Sec B.3, the LocRet protocol): every
query of a C-token prefill chunk attends to the M-slot bounded cache AND
(causally) to the chunk itself. The XLA reference (`blocks._chunk_attend`)
concatenates the chunk keys onto the slot dim and materializes the full
[B, Hq, C, M+C] score tensor; this kernel streams (m_block / c_block) key
tiles through VMEM with an online softmax instead, so VMEM stays O(block)
regardless of M or C.

Grid: (B*Hq, n_q, n_m + n_c) — the last grid dim walks the M cache
blocks FIRST, then the C chunk-key blocks. The chunk keys are a SEPARATE
operand, never concatenated onto the slot dim (M+C does not divide an
SPMD mesh and the concat would copy the whole cache every chunk — the
same refuted pattern documented for decode in core/cache.py §Perf
iteration 4). Index maps clamp each operand to its own range; revisited
output blocks keep their contents until the final visit flushes them.

Serving integration: besides the attention output the kernel returns
``probs_cache`` — the normalized per-chunk-query attention over the M
cache slots, folded to kv heads — which is exactly the H2O accumulation
signal `apply_block_prefill_chunk` adds into ``cache["aux"]``. Probs are
reconstructed flash-style (the decode kernel's scheme, generalized to
q_block rows): each cache block stores its unnormalized ``exp(s - m_blk)``
tile plus the running row-max at that block; the final (max, denom) pair
rescales every tile outside the kernel.

Masking matches `_chunk_attend` exactly: a key participates iff its
position >= 0 and dist = q_pos - k_pos >= 0 (and dist < window when
windowed). Chunk positions come in as an explicit [C] operand with -1
marking the padded tail, so padded queries emit zero output / zero probs
and padded keys are never attended.

Target: TPU v5e — blocks default 128 (MXU-aligned), f32 accumulation.
Validated on CPU via interpret=True against `_chunk_attend`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _chunk_kernel(q_ref, ck_ref, cv_ref, cpos_ref, kk_ref, kv_ref, kp_ref,
                  qp_ref, o_ref, *rest, sm_scale, window, n_m, n_kv,
                  want_probs):
    if want_probs:
        praw_ref, mblk_ref, mfin_ref, lfin_ref = rest[:4]
        m_scr, l_scr, acc_scr = rest[4:]
    else:
        m_scr, l_scr, acc_scr = rest
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                       # [bq, D]
    qpos = qp_ref[0]                                       # [bq, 1] int32

    def accum(k, v, kpos):
        """One online-softmax step over a key tile (kpos: [1, bk]);
        returns the unnormalized prob tile (for the cache-probs
        reconstruction)."""
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        dist = qpos - kpos
        mask = (kpos >= 0) & (dist >= 0)
        if window > 0:
            mask = mask & (dist < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        # all-masked rows keep m at NEG_INF: exp(0)=1 — zero them here
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        return p

    @pl.when(ki < n_m)
    def _cache_block():
        p = accum(ck_ref[0], cv_ref[0], cpos_ref[0])
        if want_probs:
            # store the tile + the running max it was scaled by; the
            # wrapper rescales by exp(m_blk - m_final)/l_final (flash
            # reconstruction)
            praw_ref[0] = p
            mblk_ref[0, 0] = m_scr[...][:, None]

    @pl.when(ki >= n_m)
    def _chunk_block():
        accum(kk_ref[0], kv_ref[0], kp_ref[0])

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)
        if want_probs:
            mfin_ref[0] = m_scr[...][:, None]
            lfin_ref[0] = l_scr[...][:, None]


def chunk_attention_pallas(q, k_c, v_c, cache_k, cache_v, cache_pos,
                           chunk_pos, *, window=0, need_probs=True,
                           q_block=128, m_block=128, c_block=128,
                           interpret=True):
    """q: [B,C,Hq,D]; k_c,v_c: [B,C,Hkv,D] (the chunk's keys/values);
    cache_k/cache_v: [B,Hkv,M,D]; cache_pos: [B,Hkv,M] int32 (-1 empty);
    chunk_pos: [C] or [B,C] int32 absolute chunk positions (-1 = padded
    tail). The per-batch form carries ragged prompts: each request in a
    mixed-length admission batch marks its own tail padding, so ONE
    kernel call serves a whole continuous-batching prefill grid.

    Returns (out [B,C,Hq,D] in q dtype,
             probs_cache [B,Hkv,C,M] f32 — normalized chunk-query
             attention over the cache slots, GQA-folded; the H2O
             accumulation signal — or None with need_probs=False:
             needs_attn=False policies (TRIM-KV, StreamingLLM) discard
             it, and skipping the outputs saves the O(B·Hq·C·M) f32 HBM
             writes + the host-side rescale, mirroring the decode
             kernel's return_probs switch).
    """
    B, C, Hq, D = q.shape
    Hkv, M = cache_k.shape[1], cache_k.shape[2]
    group = Hq // Hkv

    qh = jnp.moveaxis(q, 2, 1).reshape(B * Hq, C, D)
    kh = jnp.moveaxis(k_c, 2, 1).reshape(B * Hkv, C, D)
    vh = jnp.moveaxis(v_c, 2, 1).reshape(B * Hkv, C, D)
    ck = cache_k.reshape(B * Hkv, M, D)
    cv = cache_v.reshape(B * Hkv, M, D)
    # per-row position operands and stat outputs carry a unit dim, so
    # every block's last two dims equal the array's or are (8, 128)-
    # aligned — the rule the TPU lowering enforces
    cp = cache_pos.reshape(B * Hkv, 1, M)

    q_block = min(q_block, max(C, 8))
    m_block = min(m_block, max(M, 8))
    c_block = min(c_block, max(C, 8))
    n_q = -(-C // q_block)
    n_m = -(-M // m_block)
    n_c = -(-C // c_block)
    n_kv = n_m + n_c
    pq, pm, pc = n_q * q_block - C, n_m * m_block - M, n_c * c_block - C
    if pq:
        qh = jnp.pad(qh, ((0, 0), (0, pq), (0, 0)))
    if pm:
        ck = jnp.pad(ck, ((0, 0), (0, pm), (0, 0)))
        cv = jnp.pad(cv, ((0, 0), (0, pm), (0, 0)))
        cp = jnp.pad(cp, ((0, 0), (0, 0), (0, pm)), constant_values=-1)
    if pc:
        kh = jnp.pad(kh, ((0, 0), (0, pc), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pc), (0, 0)))
    # chunk positions enter twice: per-q-block query positions as a
    # column [B, Cq, 1] and per-c-block chunk-key positions as a row
    # [B, 1, Cc] — padded with -1 on both axes, one row per batch
    # element (ragged prompts mark per-request tails)
    cp2 = jnp.broadcast_to(jnp.atleast_2d(chunk_pos.astype(jnp.int32)),
                           (B, C))
    qp_q = jnp.pad(cp2, ((0, 0), (0, pq)), constant_values=-1)[:, :, None]
    qp_c = jnp.pad(cp2, ((0, 0), (0, pc)), constant_values=-1)[:, None, :]
    Cq, Mp = n_q * q_block, n_m * m_block

    kernel = functools.partial(_chunk_kernel, sm_scale=1.0 / np.sqrt(D),
                               window=window, n_m=n_m, n_kv=n_kv,
                               want_probs=need_probs)

    # the last grid dim covers cache blocks then chunk blocks; each
    # operand's index map clamps to its own range (out-of-range visits
    # re-address the last block, which is never read then)
    cache_i = lambda ki: jnp.minimum(ki, n_m - 1)
    chunk_i = lambda ki: jnp.clip(ki - n_m, 0, n_c - 1)
    out_specs = [
        pl.BlockSpec((1, q_block, D), lambda bh, qi, ki: (bh, qi, 0)),
    ]
    out_shape = [jax.ShapeDtypeStruct((B * Hq, Cq, D), q.dtype)]
    if need_probs:
        out_specs += [
            pl.BlockSpec((1, q_block, m_block),
                         lambda bh, qi, ki: (bh, qi, cache_i(ki))),
            pl.BlockSpec((1, 1, q_block, 1),
                         lambda bh, qi, ki: (bh, cache_i(ki), qi, 0)),
            pl.BlockSpec((1, q_block, 1), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, q_block, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ]
        out_shape += [
            jax.ShapeDtypeStruct((B * Hq, Cq, Mp), jnp.float32),
            jax.ShapeDtypeStruct((B * Hq, n_m, Cq, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * Hq, Cq, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * Hq, Cq, 1), jnp.float32),
        ]
    res = pl.pallas_call(
        kernel,
        grid=(B * Hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, q_block, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, m_block, D),
                         lambda bh, qi, ki: (bh // group, cache_i(ki), 0)),
            pl.BlockSpec((1, m_block, D),
                         lambda bh, qi, ki: (bh // group, cache_i(ki), 0)),
            pl.BlockSpec((1, 1, m_block),
                         lambda bh, qi, ki: (bh // group, 0, cache_i(ki))),
            pl.BlockSpec((1, c_block, D),
                         lambda bh, qi, ki: (bh // group, chunk_i(ki), 0)),
            pl.BlockSpec((1, c_block, D),
                         lambda bh, qi, ki: (bh // group, chunk_i(ki), 0)),
            pl.BlockSpec((1, 1, c_block),
                         lambda bh, qi, ki: (bh // Hq, 0, chunk_i(ki))),
            pl.BlockSpec((1, q_block, 1),
                         lambda bh, qi, ki: (bh // Hq, qi, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block, D), jnp.float32),
        ],
        interpret=interpret,
        name="chunk_attn",
    )(qh, ck, cv, cp, kh, vh, qp_c, qp_q)

    out = res[0][:, :C].reshape(B, Hq, C, D)
    out = jnp.moveaxis(out, 1, 2)
    if not need_probs:
        return out, None
    _, praw, mblk, mfin, lfin = res
    mblk = jnp.moveaxis(mblk[..., 0], 1, 2)                # [BHq, Cq, n_m]
    mfin, lfin = mfin[..., 0], lfin[..., 0]
    # flash reconstruction: rescale each cache block's exp(s - m_blk)
    # tile by exp(m_blk - m_fin) and divide by the final denominator
    scale = jnp.exp(jnp.repeat(mblk, m_block, axis=2) - mfin[..., None])
    probs = praw * scale / jnp.maximum(lfin, 1e-30)[..., None]
    probs = probs[:, :C, :M].reshape(B, Hq, C, M)
    probs_cache = probs.reshape(B, Hkv, group, C, M).mean(axis=2)
    return out, probs_cache
