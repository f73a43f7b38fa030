"""Random weights made on the device from the run's seed.

`make_weights` builds every tensor in ONE jitted call, in the type it
is served in: matrices in bf16, norm scales and the retention gates in
float32. The layout is the benchmark's own (`canonical`): per-layer
tensors stacked on a leading layer axis under plain names. The plain
reference reads this layout; `to_program` re-nests the same arrays
(no copy) into the tree the program under test takes.

Scales: projections N(0, 1/fan_in); embedding N(0, 1); norm scales
1 + N(0, 0.1); QKV biases N(0, 0.1). The retention gate's output layer
and bias are drawn so that a gate logit is about N(4.5, 1.4), which
spreads beta = sigmoid(logit) over roughly (0.95, 0.9995) as a trained
gate does; at the program's training init (bias 18) beta is 1.0 in
float32 and every keep score ties.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import seeds

GATE_W2_SCALE = 0.1
GATE_BIAS_RANGE = (3.5, 5.5)
NORM_SCALE_STD = 0.1
BIAS_STD = 0.1


def padded_vocab(vocab: int) -> int:
    """Rows of the embedding and columns of the head: the vocabulary
    rounded up to 256 (the program masks the padding out of the
    logits; the reference never reads it)."""
    return (vocab + 255) // 256 * 256


def canonical_shapes(m: dict) -> dict:
    """name -> (shape, dtype) of every canonical tensor of model `m`
    (the `model` block of a configuration file)."""
    L, d, F = m["num_layers"], m["d_model"], m["d_ff"]
    q, kv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    G, Vp = m["gate_hidden"], padded_vocab(m["vocab_size"])
    bf = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[m["dtype"]]
    f32 = jnp.float32
    shapes = {
        "embed": ((Vp, d), bf),
        "unembed": ((d, Vp), bf),
        "final_norm": ((d,), f32),
        "attn_norm": ((L, d), f32),
        "wq": ((L, d, q), bf),
        "wk": ((L, d, kv), bf),
        "wv": ((L, d, kv), bf),
        "wo": ((L, q, d), bf),
        "mlp_norm": ((L, d), f32),
        "w_gate": ((L, d, F), bf),
        "w_up": ((L, d, F), bf),
        "w_down": ((L, F, d), bf),
        "gate_w1": ((L, d, G), f32),
        "gate_w2": ((L, G, m["num_kv_heads"]), f32),
        "gate_b": ((L, m["num_kv_heads"]), f32),
    }
    if m["qkv_bias"]:
        shapes.update(bq=((L, q), bf), bk=((L, kv), bf), bv=((L, kv), bf))
    return shapes


def _draw(name, key, shape, dtype):
    if name.endswith("norm"):
        return (1.0 + NORM_SCALE_STD * jax.random.normal(key, shape)
                ).astype(dtype)
    if name == "gate_b":
        lo, hi = GATE_BIAS_RANGE
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if name in ("bq", "bk", "bv"):
        return (BIAS_STD * jax.random.normal(key, shape)).astype(dtype)
    if name == "embed":
        return jax.random.normal(key, shape).astype(dtype)
    scale = GATE_W2_SCALE if name == "gate_w2" else 1.0 / np.sqrt(shape[-2])
    return (scale * jax.random.normal(key, shape)).astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(items):
    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(items))
        return {n: _draw(n, k, s, dt)
                for k, (n, s, dt) in zip(keys, items)}
    return make


def make_weights(m: dict, seed: int) -> dict:
    """All canonical tensors of model `m`, on the default device, drawn
    from `seed` in one jitted call."""
    items = tuple((n, s, dt) for n, (s, dt) in
                  sorted(canonical_shapes(m).items()))
    return _maker(items)(jax.random.PRNGKey(seeds.int31(seed, "weights")))


def to_program(w: dict):
    """(params, gates) in the tree the program's dense 'global' model
    takes: one scanned unit of one block, tensors stacked per layer.
    The arrays are the same objects as in `w`."""
    def dense(name, bias=None):
        out = {"w": w[name]}
        if bias is not None and bias in w:
            out["b"] = w[bias]
        return out

    block = {
        "norm1": {"scale": w["attn_norm"]},
        "attn": {"wq": dense("wq", "bq"), "wk": dense("wk", "bk"),
                 "wv": dense("wv", "bv"), "wo": dense("wo")},
        "norm2": {"scale": w["mlp_norm"]},
        "ffn": {"gate": dense("w_gate"), "up": dense("w_up"),
                "down": dense("w_down")},
    }
    params = {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
              "unembed": {"w": w["unembed"]}, "layers": (block,),
              "tail": ()}
    gates = {"layers": ({"w1": {"w": w["gate_w1"]},
                         "w2": {"w": w["gate_w2"]}, "b": w["gate_b"]},),
             "tail": ()}
    return params, gates


def check_layout(params, gates, expected_params, expected_gates):
    """Raise unless (params, gates) have the tree, shapes and dtypes of
    the program's own init (`expected_*`: jax.eval_shape of it)."""
    for what, got, want in (("params", params, expected_params),
                            ("gates", gates, expected_gates)):
        g = jax.tree_util.tree_structure(got)
        e = jax.tree_util.tree_structure(want)
        if g != e:
            raise ValueError(f"{what}: tree {g} is not the program's {e}")
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            if a.shape != b.shape or a.dtype != b.dtype:
                raise ValueError(f"{what}: leaf {a.shape} {a.dtype} is "
                                 f"not the program's {b.shape} {b.dtype}")
