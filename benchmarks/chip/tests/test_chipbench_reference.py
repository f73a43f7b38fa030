"""The plain reference: its retention schedule by hand, its agreement
with the program at a tiny size, and the two ways the check must fail:
the lower-precision control, and a run whose timed path is broken
underneath."""
import chipbench_paths  # noqa: F401  (sets the import path)

import io
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cli, correct, spec
from chipbench_tiny import BENCH, make_tree


def _ref():
    return spec.load_module(f"{BENCH}/references/dense_gqa.py", "dense_gqa")


def test_evict_times_by_hand():
    """M=2, chunks of 2, prompt of 3, then 3 decoded positions."""
    beta = jnp.asarray([0.9, 0.5, 0.99, 0.8, 0.95, 0.7], jnp.float32)
    ev = np.asarray(_ref().evict_times(beta, 3, 6, 2, 2))
    never = 2 ** 30
    # chunk {0,1} keeps both; chunk {2} at t=2 keeps 2 (1.0) and 0
    # (0.81) over 1 (0.5), so 1 leaves before query 3; decode t=3
    # evicts 0 (0.729 < 0.99), t=4 evicts 3 (0.8 < 0.98), t=5 evicts 4
    assert ev.tolist() == [4, 3, never, 5, 6, never]


def _run(tmp_path, monkeypatch, workload="tiny.tiny-closed", after=None,
         seed=11):
    root, bench = make_tree(tmp_path, monkeypatch)
    return cli.run_cell(root, bench, workload, seed, 1.5, False,
                        t_start=time.perf_counter(), require_tpu=False,
                        out=io.StringIO(), err=io.StringIO(), after=after)


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 7])
def test_control_fails_where_the_program_passes(tmp_path, monkeypatch, seed):
    import prove
    got = {}

    def after(cell, run, w, seed, picked):
        gap = prove.control_gap(cell, w, picked)
        n_tok = sum(len(r.rs.ids) for r in picked)
        got["ok"], got["checks"] = correct.judge(cell.cell["correct"], gap,
                                                 n_tok, 0)
        got["sent_in_window"] = any(r.due is not None for r in picked)

    res = _run(tmp_path, monkeypatch, after=after, seed=seed)
    assert res["correct"], res["checks"]
    assert not got["ok"], got["checks"]
    assert got["sent_in_window"]


def _alter_tokens(monkeypatch):
    from repro.models import transformer as T
    orig = T.sample_token_lanes

    def altered(logits, keys, **kw):
        tok, k = orig(logits, keys, **kw)
        return (tok + 1) % 300, k
    monkeypatch.setattr(T, "sample_token_lanes", altered)


def _freeze_cache(monkeypatch):
    from repro.models import blocks
    monkeypatch.setattr(blocks, "cache_insert",
                        lambda cache, *a, **kw: cache)


@pytest.mark.parametrize("fault", [_alter_tokens, _freeze_cache])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(tmp_path, monkeypatch)
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]
