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
import re

import jax
import jax.numpy as jnp
import numpy as np
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


def _kernel_named(text, name):
    """The compiled HLO holds a Pallas kernel whose custom call bears
    `name` (the pallas_call's name=, which a profiler trace shows)."""
    return re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call\(", text)


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
    assert _kernel_named(compiled.as_text(), "decode_attn")
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

    text = _compile(f, *args).as_text()
    assert "tpu_custom_call" in text
    assert _kernel_named(text, "chunk_attn")


def test_retention_kernel_compiles(one_chip):
    """Retention-gated training attention over a 2048-token window."""
    hq, hkv, d = _dims()
    bf = jnp.bfloat16
    t = 2048
    args = _specs(one_chip, ((1, t, hq, d), bf), ((1, t, hkv, d), bf),
                  ((1, t, hkv, d), bf), ((1, t, hkv), jnp.float32))

    def f(q, k, v, lb):
        return retention_attention_pallas(q, k, v, lb, interpret=False)

    text = _compile(f, *args).as_text()
    assert "tpu_custom_call" in text
    assert _kernel_named(text, "retention_attn")


def test_capacity_loss_kernel_compiles(one_chip):
    """The capacity-loss kernel ops.capacity_loss picks on a TPU."""
    _, hkv, _ = _dims()
    (beta,) = _specs(one_chip, ((2, 4096, hkv), jnp.float32))
    compiled = _compile(lambda b: capacity_loss_pallas(b, 256.0,
                                                       interpret=False),
                        beta)
    assert "tpu_custom_call" in compiled.as_text()
    assert _kernel_named(compiled.as_text(), "capacity_loss")


@pytest.fixture(scope="module")
def full_width(one_chip):
    """The lane scheduler's engine at full width and 2 layers, from
    eval_shape shapes placed on the described chip, with the lane
    state and per-lane operand specs: (engine, state, tok, keys,
    active, n_emitted, max_new, eos, bytes of the weights)."""
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
    eng = build_engine(cfg, params, gates, attn_impl="pallas",
                       policy="trimkv", budget=512, prefill_chunk=C,
                       decode_segment=16)
    state = on_chip(jax.eval_shape(
        lambda: T.init_decode_state(cfg, LANES, 512)))
    tok, active, n_emitted, max_new, eos = _specs(
        one_chip, ((LANES,), jnp.int32), ((LANES,), jnp.bool_),
        ((LANES,), jnp.int32), ((LANES,), jnp.int32), ((LANES,), jnp.int32))
    (keys,) = _specs(one_chip, ((LANES, 2), jnp.uint32))
    return (eng, state, tok, keys, active, n_emitted, max_new, eos,
            weight_bytes)


@pytest.fixture(scope="module")
def segment(one_chip, full_width):
    """One decode-segment program compiled for the described chip:
    (lowered, compiled, bytes of the weights)."""
    eng, state, tok, keys, active, n_emitted, max_new, eos, wb = full_width
    (n_real,) = _specs(one_chip, ((), jnp.int32))
    # off the chip ops.py picks interpret mode; steer it to the chip's
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret", lambda: False)
        lowered = eng.lane_closures(True)["segment"].lower(
            state, tok, keys, active, n_emitted, max_new, eos, 16, n_real)
        return lowered, lowered.compile(), wb


def test_segment_program_takes_weights_as_arguments(segment):
    """The Pallas kernels are in the segment program, the weights are
    counted as arguments, and the lowered text stays small — a weight
    compiled in as a constant would add its bytes."""
    lowered, compiled, weight_bytes = segment
    assert weight_bytes > 1.5e9        # full width: ~1.6 GB of embeddings
    assert len(lowered.as_text()) < 10 * 2 ** 20
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes >= weight_bytes


def test_segment_program_names_its_kernel_and_cache_update(segment):
    """The names a profiler trace of the chip shows: the decode kernel's
    custom call is decode_attn, and the decode attention and the cache
    update carry their named scopes in the op_name metadata."""
    text = segment[1].as_text()
    assert _kernel_named(text, "decode_attn")
    for scope in ("decode_attn", "kv_insert"):
        assert re.search(rf'op_name="[^"]*/{scope}/', text), scope


def test_mixed_program_runs_the_chunk_one_lane_at_a_time(one_chip,
                                                         full_width):
    """The interleaved mixed program of an unsharded engine compiles
    for the chip with its chunk sub-step compacted: the chunk kernel
    runs on one lane's [1, C] chunk per pass, not on the [LANES, C]
    grid."""
    eng, state, tok, keys, active, n_emitted, max_new, eos, _ = full_width
    chunks, nv, fin = _specs(one_chip, ((1, LANES, C), jnp.int32),
                             ((1, LANES), jnp.int32),
                             ((1, LANES), jnp.bool_))
    assert eng.mixed_compact
    cl = eng.lane_closures(True)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret", lambda: False)
        text = cl["mixed"].lower(state, tok, keys, active, n_emitted,
                                 max_new, eos, chunks, nv, fin,
                                 keys).compile().as_text()
    assert "tpu_custom_call" in text
    assert _kernel_named(text, "decode_attn")
    hq, _, d = _dims()
    outs = re.findall(r"%chunk_attn(?:\.\d+)? = (\S+?)\{[^\n]*custom-call\(",
                      text)
    # the kernel's output rows are (lanes x heads, C, head_dim): one lane
    assert outs and all(o == f"bf16[{hq},{C},{d}]" for o in outs), outs


def test_mixed_program_is_one_executable_for_any_admission_count():
    """No shape of the compacted mixed program depends on how many
    lanes admit: one-lane and three-lane admissions run one executable
    (on the CPU, a tiny engine)."""
    cfg = dataclasses.replace(
        get_config(ARCH), num_layers=2, d_model=64, d_ff=128, num_heads=4,
        num_kv_heads=2, head_dim=16, vocab_size=64)
    eng = build_engine(cfg, T.init_params(jax.random.PRNGKey(0), cfg),
                       T.init_gate_params(jax.random.PRNGKey(1), cfg),
                       budget=16, prefill_chunk=8, decode_segment=4)
    mixed = eng.lane_closures(True)["mixed"]
    lanes = 4
    zeros = jnp.zeros((lanes,), jnp.int32)
    for admitting in ([1], [0, 2, 3]):
        nv = np.zeros((2, lanes), np.int32)
        nv[0, admitting] = 8
        fin = nv > 0
        out = mixed(eng.fresh_state(lanes), zeros,
                    jnp.zeros((lanes, 2), jnp.uint32),
                    jnp.zeros((lanes,), bool), zeros, zeros + 4, zeros - 1,
                    jnp.ones((2, lanes, 8), jnp.int32), jnp.asarray(nv),
                    jnp.asarray(fin), jnp.zeros((lanes, 2), jnp.uint32))
        # a lane finishing its prompt at step 0 decodes at step 1
        np.testing.assert_array_equal(np.asarray(out[0]["t"]),
                                      nv.sum(axis=0) + fin[0])
    assert mixed._cache_size() == 1
