"""Compile the Pallas kernels and one serving program for a DESCRIBED
TPU v5e chip, with no chip attached (the TPU compiler ships with
libtpu). Interpret mode checks neither the TPU lowering's block-shape
rule (a block's last two dims must equal the array's or be multiples of
(8, 128)) nor Mosaic's layouts and VMEM limits; these compiles do, at
the widths of trimkv-paper-4b (Hq 32, Hkv 8, head_dim 128).

The topology is described inside a module fixture — never at import —
so every pytest-xdist worker collects the same tests and only the one
that runs this file loads libtpu. Everything compiles in this process.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.capacity_loss import capacity_loss_pallas
from repro.kernels.chunk_attention import chunk_attention_pallas
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.retention_attention import retention_attention_pallas
from repro.models import transformer as T
from repro.serve import build_engine

ARCH = "trimkv-paper-4b"
# serving shapes: 8 lanes, budget M, prefill chunk C
LANES, M, C = 8, 1024, 512


@pytest.fixture(scope="module")
def one_chip():
    # libtpu would otherwise write compiler logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off here
    # (reset, since the process may already have opened it)
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _specs(sharding, *shapes_dtypes):
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes_dtypes]


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _dims():
    cfg = get_config(ARCH)
    return cfg.num_heads, cfg.num_kv_heads, cfg.head_dim


def test_decode_kernel_compiles(one_chip):
    """The serving decode call: probs + in-flight token, m_block 512."""
    hq, hkv, d = _dims()
    bf = jnp.bfloat16
    args = _specs(one_chip, ((LANES, hq, d), bf), ((LANES, hkv, M, d), bf),
                  ((LANES, hkv, M, d), bf), ((LANES, hkv, M), jnp.int32),
                  ((LANES,), jnp.int32), ((LANES, hkv, d), bf),
                  ((LANES, hkv, d), bf))

    def f(q, k, v, pos, t, kn, vn):
        return decode_attention_pallas(q, k, v, pos, t, new_kv=(kn, vn),
                                       return_probs=True, m_block=512,
                                       interpret=False)

    compiled = _compile(f, *args)
    assert "tpu_custom_call" in compiled.as_text()
    out, probs, p_new = jax.eval_shape(f, *args)
    assert probs.shape == (LANES, hq, M) and p_new.shape == (LANES, hq)


@pytest.mark.parametrize("need_probs", [False, True])
def test_chunk_kernel_compiles(one_chip, need_probs):
    """Chunked prefill: C-token chunk over an M-slot cache, the default
    q/m/c blocks of 128 the serving path uses."""
    hq, hkv, d = _dims()
    bf = jnp.bfloat16
    args = _specs(one_chip, ((LANES, C, hq, d), bf),
                  ((LANES, C, hkv, d), bf), ((LANES, C, hkv, d), bf),
                  ((LANES, hkv, M, d), bf), ((LANES, hkv, M, d), bf),
                  ((LANES, hkv, M), jnp.int32), ((LANES, C), jnp.int32))

    def f(q, k, v, ck, cv, cp, pos):
        return chunk_attention_pallas(q, k, v, ck, cv, cp, pos,
                                      need_probs=need_probs,
                                      interpret=False)

    assert "tpu_custom_call" in _compile(f, *args).as_text()


def test_retention_kernel_compiles(one_chip):
    """Retention-gated training attention over a 2048-token window."""
    hq, hkv, d = _dims()
    bf = jnp.bfloat16
    t = 2048
    args = _specs(one_chip, ((1, t, hq, d), bf), ((1, t, hkv, d), bf),
                  ((1, t, hkv, d), bf), ((1, t, hkv), jnp.float32))

    def f(q, k, v, lb):
        return retention_attention_pallas(q, k, v, lb, interpret=False)

    assert "tpu_custom_call" in _compile(f, *args).as_text()


def test_capacity_loss_kernel_compiles(one_chip):
    """The capacity-loss kernel ops.capacity_loss picks on a TPU."""
    _, hkv, _ = _dims()
    (beta,) = _specs(one_chip, ((2, 4096, hkv), jnp.float32))
    compiled = _compile(lambda b: capacity_loss_pallas(b, 256.0,
                                                       interpret=False),
                        beta)
    assert "tpu_custom_call" in compiled.as_text()


def test_segment_program_takes_weights_as_arguments(one_chip, monkeypatch):
    """One decode-segment program of the lane scheduler at full width
    and 2 layers, from eval_shape shapes: the Pallas kernels are in it,
    the weights are counted as arguments, and the lowered text stays
    small — a weight compiled in as a constant would add its bytes."""
    # off the chip ops.py picks interpret mode; steer it to the chip's
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_config(ARCH), num_layers=2)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    kp, kg = jax.random.split(jax.random.PRNGKey(0))
    params = on_chip(jax.eval_shape(lambda k: T.init_params(k, cfg), kp))
    gates = on_chip(jax.eval_shape(lambda k: T.init_gate_params(k, cfg),
                                   kg))
    weight_bytes = sum(s.size * s.dtype.itemsize
                       for s in jax.tree.leaves((params, gates)))
    assert weight_bytes > 1.5e9        # full width: ~1.6 GB of embeddings
    eng = build_engine(cfg, params, gates, attn_impl="pallas",
                       policy="trimkv", budget=512, prefill_chunk=C,
                       decode_segment=16)
    state = on_chip(jax.eval_shape(
        lambda: T.init_decode_state(cfg, LANES, 512)))
    tok, active, n_emitted, max_new, eos = _specs(
        one_chip, ((LANES,), jnp.int32), ((LANES,), jnp.bool_),
        ((LANES,), jnp.int32), ((LANES,), jnp.int32), ((LANES,), jnp.int32))
    (keys,) = _specs(one_chip, ((LANES, 2), jnp.uint32))
    (n_real,) = _specs(one_chip, ((), jnp.int32))
    lowered = eng.lane_closures(True)["segment"].lower(
        state, tok, keys, active, n_emitted, max_new, eos, 16, n_real)
    assert len(lowered.as_text()) < 10 * 2 ** 20
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes >= weight_bytes
