"""The program's own trace names (docs/serving.md §Tracing): the
scheduler's work counters against an independent count of what its
dispatches ran, the named scopes in a compiled serving program, and
the serve.* host spans in a profiler trace of one step."""
import dataclasses
import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.serve import Request, Scheduler, build_engine

C, SEG, LANES = 8, 4, 2


def _engine(mesh=None):
    cfg = dataclasses.replace(
        get_smoke_config("trimkv-paper-4b"), num_layers=2, d_model=64,
        d_ff=128, num_heads=4, num_kv_heads=2, vocab_size=64,
        gate_bias_init=3.0)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    gates = T.init_gate_params(jax.random.PRNGKey(1), cfg)
    return build_engine(cfg, params, gates, mesh=mesh, budget=16,
                        policy="trimkv", prefill_chunk=C,
                        decode_segment=SEG)


@pytest.fixture(scope="module")
def engine():
    return _engine()


def _requests(lens, max_new):
    rng = np.random.RandomState(3)
    return [Request(rid=i, prompt=rng.randint(0, 64, size=n).astype(
        np.int32), max_new=m, seed=i) for i, (n, m) in enumerate(
            zip(lens, max_new))]


def _spy(sched):
    """Wrap the segment closures: record each pure-decode dispatch's
    static scan length (the bucket) and each mixed dispatch's chunk
    grid, as the device receives them."""
    seen = {"buckets": [], "grids": []}
    segment, mixed = sched._segment, sched._mixed_nomem

    def seg(*args):
        seen["buckets"].append(args[7])
        return segment(*args)

    def mix(*args):
        nv = np.asarray(args[8])
        seen["grids"].append((list(np.asarray(args[7]).shape),
                              int(nv.sum()), int((nv > 0).sum())))
        return mixed(*args)

    sched._segment, sched._mixed_nomem = seg, mix
    return seen


def _serve_counted(engine, lens, max_new):
    """Serve the requests interleaved; the counters and the spy's
    record of the segment dispatches."""
    sched = Scheduler(engine, n_lanes=LANES, interleaved=True)
    seen = _spy(sched)
    sched.run(_requests(lens, max_new))
    return sched.stats(), seen


def _check_counters(st, seen, lens, rows_of_grid):
    assert seen["buckets"] and seen["grids"]
    assert st["n_decode_steps"] == sum(seen["buckets"])
    assert st["n_mixed_steps"] == sum(s[0] for s, _, _ in seen["grids"])
    assert st["n_chunk_rows"] == sum(rows_of_grid(*g)
                                     for g in seen["grids"])
    assert all(s[1:] == [LANES, C] for s, _, _ in seen["grids"])
    # every prompt row admitted ran once, padding excluded
    assert st["n_chunk_rows_valid"] == sum(lens) == sum(
        v for _, v, _ in seen["grids"])


@pytest.mark.parametrize("lens,max_new", [
    ([5, 11, 19, 8, 14], [4, 8, 4, 8, 4]),   # churn, 1-3 chunk prompts
    ([21, 3], [3, 9]),                        # one long, one short
    ([8, 16], [6, 2]),                        # chunk-aligned prompts
])
def test_work_counters_match_the_dispatches(engine, lens, max_new):
    """Unsharded lanes: the chunk sub-step runs only the (step, lane)
    pairs that hold a chunk, C rows each."""
    st, seen = _serve_counted(engine, lens, max_new)
    _check_counters(st, seen, lens,
                    lambda shape, valid, lane_steps: lane_steps * C)


_ON_MESH = """
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
sys.path.insert(0, sys.argv[1])
import test_tracing as tt
from repro.launch.mesh import make_serving_mesh
st, seen = tt._serve_counted(tt._engine(make_serving_mesh(2, 1)),
                             *json.loads(sys.argv[2]))
print(json.dumps([st, seen]))
"""


def test_work_counters_on_a_lane_sharded_mesh():
    """Lanes sharded over "data" (a 2x1 mesh of virtual CPU devices, in
    a subprocess, as tests/test_shard_serve.py runs them): the mixed
    program keeps the lane-aligned chunk sub-step, so every mixed step
    counts all LANES x C rows."""
    lens, max_new = [5, 11, 19, 8, 14], [4, 8, 4, 8, 4]
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "src"))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-c", _ON_MESH,
         os.path.dirname(os.path.abspath(__file__)),
         json.dumps([lens, max_new])],
        capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    st, seen = json.loads(p.stdout.strip().splitlines()[-1])
    _check_counters(st, seen, lens,
                    lambda shape, valid, lane_steps: shape[0] * LANES * C)
    assert st["n_chunk_rows"] > C * sum(g[2] for g in seen["grids"])


def test_serving_program_carries_the_scope_names(engine):
    """The mixed segment program (decode and chunk sub-steps) names its
    attention and cache-update ops in the compiled HLO metadata."""
    state = engine.fresh_state(LANES)
    b = jnp.zeros((LANES,), jnp.int32)
    args = (state, b, jnp.zeros((LANES, 2), jnp.uint32),
            jnp.zeros((LANES,), bool), b, b, b,
            jnp.zeros((1, LANES, C), jnp.int32),
            jnp.zeros((1, LANES), jnp.int32),
            jnp.zeros((1, LANES), bool), jnp.zeros((LANES, 2), jnp.uint32))
    text = engine.lane_closures(True)["mixed"].lower(*args).compile() \
        .as_text()
    for scope in ("decode_attn", "kv_insert", "chunk_attn", "kv_merge"):
        assert f"/{scope}/" in text, scope


SPANS = ("serve.admit", "serve.plan", "serve.mixed", "serve.decode",
         "serve.wait", "serve.retire")


def test_one_step_writes_the_serve_spans(engine, tmp_path):
    sched = Scheduler(engine, n_lanes=LANES, interleaved=True)
    sched.run(_requests([5], [2]))              # compile outside the trace
    sched.submit(Request(rid=1, prompt=np.arange(5, dtype=np.int32),
                         max_new=6, seed=1))
    with jax.profiler.trace(str(tmp_path)):
        sched.step()    # admit, plan, mixed (1 step), decode rest, retire
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for plane in pd.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("serve.")]
    names = [n for n, _, _ in events]
    for name in SPANS:
        assert name in names, name
    assert names.count("serve.wait") == 2
    outer = [(s, e) for n, s, e in events
             if n in ("serve.mixed", "serve.decode")]
    for n, s, e in events:
        if n == "serve.wait":
            assert any(a <= s and e <= b for a, b in outer)
