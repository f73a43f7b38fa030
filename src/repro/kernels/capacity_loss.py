"""Pallas TPU kernel: capacity loss L_cap (paper Eq. 5).

GPU original: custom Triton kernel (paper Sec 4.2 "Hardware-aware
Computation"). TPU adaptation: tile the lower-triangular (t, i) plane in
VMEM blocks; accumulate S_t = sum_{i<=t} exp((t-i) * log beta_i) across
the i-grid dimension in scratch, emit the hinge contribution per row
block. Never materializes T x T.

Output: per-(B*H, t-block) partial sums; ops.py reduces to the scalar
mean. Forward-only kernel — training uses the chunked XLA path
(core.losses.capacity_loss_chunked) for autodiff; this kernel is the
serving/analysis fast path and the oracle-checked TPU artifact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cap_kernel(lb_ref, out_ref, s_scr, *, block, M, T, n_blk):
    ti = pl.program_id(1)
    ii = pl.program_id(2)

    @pl.when(ii == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    lb = lb_ref[0].astype(jnp.float32)                      # [1, block]
    t_pos = ti * block + jax.lax.broadcasted_iota(
        jnp.int32, (block, block), 0)
    i_pos = ii * block + jax.lax.broadcasted_iota(
        jnp.int32, (block, block), 1)
    dist = t_pos - i_pos
    mask = (dist >= 0) & (i_pos < T)
    # mask BEFORE exp (dist<0 x lb<0 would overflow to inf; also keeps
    # the VPU exp lane free of specials)
    expo = jnp.where(mask, dist.astype(jnp.float32) * lb, -1e9)
    pw = jnp.exp(expo)
    # S_t accumulates as a [block, 1] column: Mosaic reduces 2-D tiles,
    # not 1-D vectors
    s_scr[...] = s_scr[...] + jnp.sum(pw, axis=1, keepdims=True)

    @pl.when(ii == n_blk - 1)
    def _finish():
        t_vec = ti * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, 1), 0)
        contrib = jnp.maximum(s_scr[...] - M, 0.0) / (
            t_vec.astype(jnp.float32) + 1.0)
        contrib = jnp.where(t_vec < T, contrib, 0.0)
        out_ref[0, 0] = jnp.sum(contrib, keepdims=True)


def capacity_loss_pallas(beta, M: float, *, block: int = 256,
                         interpret=True):
    """beta: [B, T, H] -> scalar mean over (B, H) of
    (1/T) sum_t (1/t) max(0, S_t - M)."""
    B, T, H = beta.shape
    # [B*H, 1, T] input and [B*H, n_blk, 1, 1] partials: unit dims keep
    # every block's last two dims equal to the array's or (8, 128)-
    # aligned, as the TPU lowering requires
    lb = jnp.log(jnp.maximum(
        jnp.moveaxis(beta, 1, 2).reshape(B * H, 1, T).astype(jnp.float32),
        1e-30))
    block = min(block, max(T, 8))
    n_blk = -(-T // block)
    pad = n_blk * block - T
    if pad:
        lb = jnp.pad(lb, ((0, 0), (0, 0), (0, pad)))

    kernel = functools.partial(_cap_kernel, block=block, M=float(M), T=T,
                               n_blk=n_blk)
    partial = pl.pallas_call(
        kernel,
        grid=(B * H, n_blk, n_blk),
        in_specs=[pl.BlockSpec((1, 1, block),
                               lambda bh, ti, ii: (bh, 0, ii))],
        out_specs=pl.BlockSpec((1, 1, 1, 1),
                               lambda bh, ti, ii: (bh, ti, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, n_blk, 1, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32)],
        interpret=interpret,
    )(lb)
    return jnp.sum(partial) / (B * H) / T
