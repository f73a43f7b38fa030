"""Each configuration's reference owns the layout of its weights: the
dense layout draws the same tensors, bit for bit, as it did when the
harness held it; a layout that disagrees with the program fails at
set-up; and a configuration of another block pattern (sliding and full
attention, sparse experts) joins the benchmark as added files alone."""
import chipbench_paths  # noqa: F401  (sets the import path)

import filecmp
import hashlib
import json
import os
import shutil

import numpy as np
import pytest

from chipbench import cli, flops, spec, weights
from chipbench_tiny import BENCH, CELL, CORRECT, MODEL, SERVE, make_tree, \
    write

HERE = os.path.dirname(os.path.abspath(__file__))

# sha256 (first 16 hex digits) of each tensor that make_weights drew for
# the tiny MODEL, recorded when the harness itself held the dense layout
DIGESTS = {
    11: {
        "attn_norm": "6cd844a19bc28e8d", "bk": "7406271e4cbc69bb",
        "bq": "6a7daeebdab44122", "bv": "d44306f6f3028ac3",
        "embed": "a377ecff7ddd120a", "final_norm": "afee9cc99b639100",
        "gate_b": "be4abf2089275044", "gate_w1": "607eb2b75c4ea774",
        "gate_w2": "76117ee436be22be", "mlp_norm": "05d029d9d9e6f676",
        "unembed": "2c5a725825f54aed", "w_down": "8131faf6e2e1bff2",
        "w_gate": "1f4a49c3fd3a5dc3", "w_up": "477fc573df76831d",
        "wk": "901dae228e110651", "wo": "8a9ef46e91ae3dee",
        "wq": "645271797da8749e", "wv": "5f7d90a6e726b202"},
    2 ** 31 + 7: {
        "attn_norm": "12f7d56dac96c35e", "bk": "495ab295e5e11147",
        "bq": "1ddcc6e03fbc576f", "bv": "3d2277cf88554048",
        "embed": "53567d1d4f769eb1", "final_norm": "c766e52b9d126ab6",
        "gate_b": "8a773d58e228dcd3", "gate_w1": "e0b8f7cc7243286e",
        "gate_w2": "3e49dab602cc9d04", "mlp_norm": "c0b0184488bec215",
        "unembed": "6b2cfd876b0c5661", "w_down": "65a3c8d236faec58",
        "w_gate": "514ebac61b3f406c", "w_up": "dcd7deb5fee8acd3",
        "wk": "362a5840b1dddb3b", "wo": "561368cfb040dfe0",
        "wq": "dde0d3abf8d30ad4", "wv": "c15c8fc4a209654c"},
}

# the dense layout at trimkv-paper-4b's sizes, as the harness held it
SHAPES_4B = [
    ("attn_norm", (36, 2560), "float32"),
    ("embed", (152064, 2560), "bfloat16"),
    ("final_norm", (2560,), "float32"),
    ("gate_b", (36, 8), "float32"),
    ("gate_w1", (36, 2560, 512), "float32"),
    ("gate_w2", (36, 512, 8), "float32"),
    ("mlp_norm", (36, 2560), "float32"),
    ("unembed", (2560, 152064), "bfloat16"),
    ("w_down", (36, 9728, 2560), "bfloat16"),
    ("w_gate", (36, 2560, 9728), "bfloat16"),
    ("w_up", (36, 2560, 9728), "bfloat16"),
    ("wk", (36, 2560, 1024), "bfloat16"),
    ("wo", (36, 4096, 2560), "bfloat16"),
    ("wq", (36, 2560, 4096), "bfloat16"),
    ("wv", (36, 2560, 1024), "bfloat16"),
]

# four kinds of layer to a unit, three sliding-window and one full, each
# with the program's sparse-expert FFN; 6 layers leave a tail of two
MOE = {**MODEL, "name": "tiny-moe-swa", "family": "moe", "num_layers": 6,
       "attn_pattern": ["local", "local", "local", "global"], "window": 8,
       "num_experts": 4, "experts_per_token": 2, "qkv_bias": False}
MOE_CELL = "tiny-moe-swa.tiny-closed"


def _ref(name, where=os.path.join(BENCH, "references")):
    return spec.load_module(os.path.join(where, name + ".py"), name)


def _digest(a):
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_dense_weights_are_bit_identical(seed):
    w = weights.make_weights(_ref("dense_gqa").canonical_shapes(MODEL), seed)
    assert {n: _digest(a) for n, a in w.items()} == DIGESTS[seed]


def test_dense_layout_at_4b_sizes():
    with open(os.path.join(BENCH, "configs", "trimkv-paper-4b.json")) as f:
        m = json.load(f)["model"]
    got = [(n, tuple(s), np.dtype(dt).name) for n, (s, dt, _) in
           sorted(_ref("dense_gqa").canonical_shapes(m).items())]
    assert got == SHAPES_4B


def test_a_layout_the_program_does_not_take_fails_at_set_up():
    dense = _ref("dense_gqa")
    w = weights.make_weights(dense.canonical_shapes(MOE), 1)
    with pytest.raises(ValueError, match="params: tree"):
        cli.build_program(MOE, {**SERVE, **CELL}, w, dense)


def test_layer_weights_asks_the_reference():
    moe, dense = _ref("tiny_moe_swa", HERE), _ref("dense_gqa")
    d, q, kv, f = 64, 64, 32, 128
    # Q/K/V/O, the router over 4 experts, 2 experts' SwiGLU, the gate
    assert flops.layer_weights(MOE, moe) == (
        2 * d * q + 2 * d * kv + d * 4 + 2 * 3 * d * f + d * 32 + 32 * 2)
    # a reference without its own count takes the dense one
    assert flops.layer_weights(MOE, dense) == flops.layer_weights(MOE)


def _add_moe(root, bench):
    """Add the configuration as files, and its entries to
    BENCHMARK.json; returns the added files, relative to `bench`."""
    added = {os.path.join("references", "tiny_moe_swa.py"),
             os.path.join("configs", "tiny-moe-swa.json"),
             os.path.join("cells", MOE_CELL + ".json")}
    shutil.copy(os.path.join(HERE, "tiny_moe_swa.py"),
                os.path.join(bench, "references", "tiny_moe_swa.py"))
    write(os.path.join(bench, "configs", "tiny-moe-swa.json"),
          {"name": MOE["name"], "source": "tests",
           "reference": "tiny_moe_swa", "model": MOE})
    write(os.path.join(bench, "cells", MOE_CELL + ".json"),
          {**CELL, "correct": CORRECT})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench_spec = json.load(f)
    bench_spec["configs"].append(
        {"name": MOE["name"], "source": "tests",
         "file": "bench/configs/tiny-moe-swa.json", "reduced": [],
         "why": "test"})
    bench_spec["workloads"].append(
        {"name": MOE_CELL, "config": MOE["name"], "traffic": "tiny-closed",
         "chips": 1, "why": "test"})
    write(path, bench_spec)
    return added


def _files(top):
    out = set()
    for d, dirs, names in os.walk(top):
        dirs[:] = [x for x in dirs if x not in ("tests", "__pycache__")]
        out |= {os.path.relpath(os.path.join(d, n), top) for n in names}
    return out


def test_moe_swa_configuration_goes_in_as_files(tmp_path, monkeypatch):
    root, bench = make_tree(tmp_path, monkeypatch)
    before = _files(bench)
    added = _add_moe(root, bench)
    cell = spec.load(root, bench, MOE_CELL)
    # set-up builds the program over the reference's layout, which
    # check_layout holds against the program's own init
    _, w, sched, loop, drv = cli.set_up(cell, 5, 3.0, False)
    assert sorted(w) == sorted(cell.reference().canonical_shapes(MOE))
    drv.run_window(loop, 3.0)
    done = [r for r in drv.recs.values()
            if r.due is not None and r.rs.done]
    assert done, "no closed-loop request finished"
    for r in done:
        ids = np.asarray(r.rs.ids)
        assert len(ids) == r.req.max_new
        assert ((ids >= 0) & (ids < MOE["vocab_size"])).all()
    # only files were added: every file the benchmark had is unchanged
    assert _files(bench) == before | added
    _, mismatch, errors = filecmp.cmpfiles(
        BENCH, bench, sorted(_files(BENCH)), shallow=False)
    assert mismatch == [] and errors == []
