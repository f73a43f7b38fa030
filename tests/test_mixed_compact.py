"""The mixed program's compacted chunk sub-step (T._chunk_substep with
compact=True: only the lanes holding a chunk run, one [1, C] lane per
pass) against the lane-aligned [B, C] sub-step that lane-sharded meshes
keep: the same state and grids must give bit-identical tokens,
emissions, health flags and state, and lanes without a chunk must come
out of the chunk sub-step exactly as they went in."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ServeConfig, get_smoke_config
from repro.core.policies import make_policy
from repro.models import transformer as T

B, C, M, STEPS = 4, 8, 16, 2
# prompt lengths that fill the lanes before each case rearranges them
WARM = [13, 20, 6, 9]


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        get_smoke_config("trimkv-paper-4b"), num_layers=2, d_model=64,
        d_ff=128, num_heads=4, num_kv_heads=2, vocab_size=64,
        gate_bias_init=3.0)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    gates = T.init_gate_params(jax.random.PRNGKey(1), cfg)
    return cfg, params, gates


@pytest.fixture(scope="module", params=["xla", "pallas"])
def progs(model, request):
    """The jitted programs of one attention impl, compiled once for
    every case (the cases share all shapes)."""
    cfg, params, gates = model
    attn_impl = request.param
    serve = ServeConfig(budget=M, policy="trimkv", prefill_chunk=C,
                        attn_impl=attn_impl)
    policy = make_policy(serve)

    def mixed(compact):
        return jax.jit(lambda *a: T.mixed_step_loop(
            params, gates, cfg, *a, policy, serve, attn_impl=attn_impl,
            compact=compact))

    return {
        "warm": jax.jit(lambda: _warm_state(cfg, params, gates, policy,
                                            serve)),
        "compact": mixed(True), "aligned": mixed(False),
        "substep": jax.jit(lambda s, c, n: T._chunk_substep(
            params, gates, cfg, c, s, policy, serve, n, True)),
    }


def _warm_state(cfg, params, gates, policy, serve):
    """Every lane holds a prompt (ragged lengths) in its cache."""
    rng = np.random.RandomState(11)
    n = -(-max(WARM) // C)
    grid = np.zeros((n, B, C), np.int32)
    nv = np.zeros((n, B), np.int32)
    for lane, L in enumerate(WARM):
        toks = rng.randint(0, 64, size=L)
        for j in range(n):
            part = toks[j * C:(j + 1) * C]
            grid[j, lane, :len(part)] = part
            nv[j, lane] = len(part)
    state, _ = T.prefill_chunk_loop(params, gates, cfg, jnp.asarray(grid),
                                    jnp.asarray(nv),
                                    T.init_decode_state(cfg, B, M),
                                    policy, serve)
    return state


# each case: per-lane role for the segment, and the chunk schedule
# {lane: [(step, n_valid), ...]} with the last entry finishing unless
# the lane is listed in "open" (still mid-prompt after the segment).
# Roles: "dec" decoding, "new" a freshly reset admitting lane, "mid" a
# lane whose state already holds earlier chunks of its prompt, "idle".
CASES = {
    "none": ({0: "dec", 1: "dec", 2: "dec", 3: "idle"}, {}, ()),
    "one": ({0: "dec", 1: "new", 2: "dec", 3: "idle"},
            {1: [(0, 5)]}, ()),
    "three": ({0: "new", 1: "new", 2: "new", 3: "dec"},
              {0: [(0, 6)], 1: [(0, 8), (1, 3)], 2: [(0, 8)]}, ()),
    "all": ({0: "new", 1: "new", 2: "new", 3: "new"},
            {0: [(0, 8), (1, 8)], 1: [(0, 1)], 2: [(0, 7)],
             3: [(0, 8), (1, 2)]}, (0,)),
    # lane 2 continues a prompt and stays open; lane 0 finishes at
    # step 0 beside it
    "mid": ({0: "new", 1: "dec", 2: "mid", 3: "idle"},
            {0: [(0, 4)], 2: [(0, 8), (1, 8)]}, (2,)),
}


def _operands(state, case):
    roles, sched, still_open = CASES[case]
    rng = np.random.RandomState(5)
    fresh = np.array([roles[l] == "new" for l in range(B)])
    state = T.reset_lanes(state, jnp.asarray(fresh))
    active = np.array([roles[l] == "dec" for l in range(B)])
    tok = rng.randint(0, 64, size=B).astype(np.int32)
    chunks = np.zeros((STEPS, B, C), np.int32)
    nv = np.zeros((STEPS, B), np.int32)
    fin = np.zeros((STEPS, B), bool)
    for lane, parts in sched.items():
        for j, n in parts:
            chunks[j, lane, :n] = rng.randint(0, 64, size=n)
            nv[j, lane] = n
        if lane not in still_open:
            fin[parts[-1][0], lane] = True
    new_keys = rng.randint(0, 2 ** 31, size=(B, 2)).astype(np.uint32)
    ints = lambda v: jnp.full((B,), v, jnp.int32)
    return (state, jnp.asarray(tok), jnp.zeros((B, 2), jnp.uint32),
            jnp.asarray(active), ints(1), ints(40), ints(-1),
            jnp.asarray(chunks), jnp.asarray(nv), jnp.asarray(fin),
            jnp.asarray(new_keys))


def _rows(state, lane):
    out = [state["t"][lane]]
    if state["layers"] is not None:
        out += [a[:, lane] for a in jax.tree.leaves(state["layers"])]
    out += [a[lane] for a in jax.tree.leaves(state["tail"])]
    return [np.asarray(a) for a in out]


@pytest.mark.parametrize("case", list(CASES))
def test_compact_chunk_substep_matches_lane_aligned(progs, case):
    args = _operands(progs["warm"](), case)
    got = progs["compact"](*args)
    want = progs["aligned"](*args)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    # the chunk sub-step alone: lanes without a chunk this step are
    # never computed and come back bit-identical
    state, chunks, nv = args[0], args[7], args[8]
    for j in range(STEPS):
        out, _ = progs["substep"](state, chunks[j], nv[j])
        for lane in range(B):
            if int(nv[j, lane]) == 0:
                for a, b in zip(_rows(out, lane), _rows(state, lane)):
                    np.testing.assert_array_equal(a, b)
        state = out
    # idle lanes (no chunk, not decoding) leave the whole program as
    # they entered it
    roles = CASES[case][0]
    for lane in (l for l in range(B) if roles[l] == "idle"):
        for a, b in zip(_rows(got[0], lane), _rows(args[0], lane)):
            np.testing.assert_array_equal(a, b)
