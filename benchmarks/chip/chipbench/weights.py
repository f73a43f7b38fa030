"""Random weights made on the device from the run's seed.

The layout belongs to the configuration's reference
(`references/<reference>.py`): its `canonical_shapes(m)` names every
tensor as `{name: (shape, dtype, role)}`, the plain reference reads
that layout, and its `to_program(w, m)` re-nests the same arrays (no
copy) into the tree the program under test takes. Nothing here knows
a model: `make_weights` draws whatever tensors it is given, in ONE
jitted call, by role, and `check_layout` holds a re-nested tree
against the program's own init.

Roles and their draws:
  matrix     N(0, 1/fan_in), fan_in the second-to-last axis
  embed      N(0, 1)
  norm       1 + N(0, 0.1)
  bias       N(0, 0.1)
  gate_out   N(0, 0.1): the retention gate's output layer
  gate_bias  uniform(3.5, 5.5): the retention gate's bias
The gate's output layer and bias make a gate logit about N(4.5, 1.4),
which spreads beta = sigmoid(logit) over roughly (0.95, 0.9995) as a
trained gate does; at the program's training init (bias 18) beta is
1.0 in float32 and every keep score ties.
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


def _draw(role, key, shape, dtype):
    if role == "norm":
        return (1.0 + NORM_SCALE_STD * jax.random.normal(key, shape)
                ).astype(dtype)
    if role == "gate_bias":
        lo, hi = GATE_BIAS_RANGE
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if role == "bias":
        return (BIAS_STD * jax.random.normal(key, shape)).astype(dtype)
    if role == "embed":
        return jax.random.normal(key, shape).astype(dtype)
    if role == "gate_out":
        scale = GATE_W2_SCALE
    elif role == "matrix":
        scale = 1.0 / np.sqrt(shape[-2])
    else:
        raise ValueError(f"unknown weight role {role!r}")
    return (scale * jax.random.normal(key, shape)).astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(items):
    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(items))
        return {n: _draw(r, k, s, dt)
                for k, (n, s, dt, r) in zip(keys, items)}
    return make


def make_weights(shapes: dict, seed: int) -> dict:
    """Every tensor of `shapes` ({name: (shape, dtype, role)}, a
    reference's `canonical_shapes`), on the default device, drawn from
    `seed` in one jitted call: names in sorted order, one key each."""
    items = tuple((n, tuple(s), dt, r) for n, (s, dt, r) in
                  sorted(shapes.items()))
    return _maker(items)(jax.random.PRNGKey(seeds.int31(seed, "weights")))


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
