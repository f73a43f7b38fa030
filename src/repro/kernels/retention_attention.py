"""Pallas TPU kernel: retention-gated flash attention (training forward).

The paper's FlexAttention score-mod on GPU; here a flash-style TPU kernel
with the retention bias (t - i) * log(beta_i) added to the logits inside
each (q_block, kv_block) VMEM tile (never materializing T x T; DESIGN.md
§2). Online softmax accumulates across the kv grid dimension in VMEM
scratch. GQA is handled by aliasing the kv-head index in the BlockSpec
index map (no materialized repeat).

Target: TPU v5e — q/kv blocks default 128x128 (MXU-aligned), f32
accumulation. Validated on CPU via interpret=True against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(off_ref, q_ref, k_ref, v_ref, lb_ref, o_ref, m_scr,
                  l_scr, acc_scr, *, sm_scale, causal, window, q_block,
                  kv_block, n_kv, t_q, t_kv, use_beta):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                       # [bq, D]
    k = k_ref[0].astype(jnp.float32)                       # [bk, D]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale

    # row/col are TILE indices (bounds checks); absolute query position
    # adds q_offset (SMEM scalar, so traced shard offsets work)
    row = qi * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 0)
    i_pos = ki * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 1)
    t_pos = off_ref[0] + row
    dist = t_pos - i_pos
    mask = (i_pos < t_kv) & (row < t_q)
    if causal:
        mask = mask & (dist >= 0)
    if window > 0:
        mask = mask & (dist < window)
    if use_beta:
        lb = lb_ref[0].astype(jnp.float32)                 # [1, bk]
        s = s + jnp.where(mask, dist.astype(jnp.float32) * lb, 0.0)
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def retention_attention_pallas(q, k, v, log_beta=None, *, causal=True,
                               window=0, q_offset=0, q_block=128,
                               kv_block=128, interpret=True):
    """q: [B,Tq,Hq,D]; k,v: [B,Tk,Hkv,D]; log_beta: [B,Tk,Hkv] or None.
    q_offset: absolute position of q[0] (python int or traced scalar —
    the context-parallel shard prefill passes axis_index * T_loc).
    Returns [B,Tq,Hq,D]."""
    B, Tq, Hq, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    use_beta = log_beta is not None
    if log_beta is None:
        log_beta = jnp.zeros((B, Tk, Hkv), jnp.float32)

    qh = jnp.moveaxis(q, 2, 1).reshape(B * Hq, Tq, D)
    kh = jnp.moveaxis(k, 2, 1).reshape(B * Hkv, Tk, D)
    vh = jnp.moveaxis(v, 2, 1).reshape(B * Hkv, Tk, D)
    # [B*Hkv, 1, Tk]: the unit middle dim keeps the log_beta block's
    # last two dims equal to the array's or (8, 128)-aligned, as the TPU
    # lowering requires
    lbh = jnp.moveaxis(log_beta, 2, 1).reshape(B * Hkv, 1, Tk)

    q_block = min(q_block, max(Tq, 8))
    kv_block = min(kv_block, max(Tk, 8))
    n_q = -(-Tq // q_block)
    n_kv = -(-Tk // kv_block)
    pq, pk = n_q * q_block - Tq, n_kv * kv_block - Tk
    if pq:
        qh = jnp.pad(qh, ((0, 0), (0, pq), (0, 0)))
    if pk:
        kh = jnp.pad(kh, ((0, 0), (0, pk), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pk), (0, 0)))
        lbh = jnp.pad(lbh, ((0, 0), (0, 0), (0, pk)))

    kernel = functools.partial(
        _flash_kernel, sm_scale=1.0 / np.sqrt(D), causal=causal,
        window=window, q_block=q_block, kv_block=kv_block, n_kv=n_kv,
        t_q=Tq, t_kv=Tk, use_beta=use_beta)

    off = jnp.full((1,), q_offset, jnp.int32)
    grid = (B * Hq, n_q, n_kv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, q_block, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, kv_block, D),
                         lambda bh, qi, ki: (bh // group, ki, 0)),
            pl.BlockSpec((1, kv_block, D),
                         lambda bh, qi, ki: (bh // group, ki, 0)),
            pl.BlockSpec((1, 1, kv_block),
                         lambda bh, qi, ki: (bh // group, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, q_block, D),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, n_q * q_block, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block, D), jnp.float32),
        ],
        interpret=interpret,
    )(off, qh, kh, vh, lbh)
    out = out[:, :Tq].reshape(B, Hq, Tq, D)
    return jnp.moveaxis(out, 1, 2)
