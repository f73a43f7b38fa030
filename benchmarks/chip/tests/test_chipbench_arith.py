"""The yardstick's arithmetic against hand counts: kernel FLOPs and
bytes, model FLOPs, the peaks table, and the traffic generators'
determinism."""
import chipbench_paths  # noqa: F401  (sets the import path)

import numpy as np
import pytest

from chipbench import flops, laws, loops, peaks

M4B = {"num_layers": 36, "d_model": 2560, "num_heads": 32,
       "num_kv_heads": 8, "head_dim": 128, "d_ff": 9728,
       "vocab_size": 151936, "gate_hidden": 512}


def test_decode_attn_hand_count():
    f, b = flops.decode_attn(M4B, 512)
    # QK^T and PV: 2 * 2 flops per (head, key, dim), 513 keys with itself
    assert f == 4 * 32 * 513 * 128
    # K and V of 8 heads x 512 slots x 128 dims in bf16, int32 positions,
    # bf16 query, in-flight k/v, f32 output
    assert b == (2 * 8 * 512 * 128 * 2 + 8 * 512 * 4 + 32 * 128 * 2
                 + 2 * 8 * 128 * 2 + 32 * 128 * 4)


def test_model_flops_hand_count():
    per_layer = (2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560
                 + 3 * 2560 * 9728 + 2560 * 512 + 512 * 8)
    assert flops.layer_weights(M4B) == per_layer
    assert flops.decode_token_flops(M4B, 100) == (
        36 * (2 * per_layer + 4 * 32 * 128 * 100) + 2 * 2560 * 151936)
    assert flops.prefill_chunk_flops(M4B, 0, 1) == 36 * (
        2 * per_layer + 4 * 32 * 128)


def test_roofline_seconds_takes_the_binding_bound():
    p = peaks.peaks("TPU v5 lite")
    assert flops.roofline_seconds(197e12, 0, p) == pytest.approx(1.0)
    assert flops.roofline_seconds(0, 819e9, p) == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


@pytest.mark.parametrize("law", [{"law": "uniform", "lo": 128, "hi": 512},
                                 {"law": "loguniform", "lo": 16, "hi": 128}])
def test_lengths_are_stratified(law):
    x = laws.lengths(law, 100)
    assert x.min() >= law["lo"] and x.max() <= law["hi"]
    assert np.all(np.diff(x) >= 0)


@pytest.mark.parametrize("arrivals", [{"law": "poisson"},
                                      {"law": "gamma", "cv": 2.0}])
def test_gaps_keep_the_rate(arrivals):
    g = laws.gaps(arrivals, 4.0, 200)
    assert g.sum() == pytest.approx(50.0)
    cv = g.std() / g.mean()
    assert cv == pytest.approx(1.0 if arrivals["law"] == "poisson" else 2.0,
                               rel=0.25)


OPEN = {"loop": "open", "arrivals": {"law": "gamma", "cv": 2.0},
        "rate": 5.0, "prompt_len": {"law": "loguniform", "lo": 64, "hi": 512},
        "output_len": {"law": "loguniform", "lo": 16, "hi": 256}}
CLOSED = {"loop": "closed", "clients": 8, "requests_per_client": 3,
          "prompt_len": {"law": "uniform", "lo": 128, "hi": 512},
          "output_len": {"law": "loguniform", "lo": 512, "hi": 3072}}


def _draw(traffic, seed):
    g = loops.make(traffic, seed=seed, seconds=10, vocab=1000)
    reqs = g.warm() + (g.reqs if hasattr(g, "reqs") else
                       [r for q in g.queues for r in q])
    return [(r.rid, r.prompt.tolist(), r.max_new, r.due) for r in reqs]


@pytest.mark.parametrize("traffic", [OPEN, CLOSED])
def test_generators_are_deterministic_per_seed(traffic):
    big = 2 ** 31 + 12345
    assert _draw(traffic, big) == _draw(traffic, big)
    a, b = _draw(traffic, 1), _draw(traffic, 2)
    assert a != b
    # the same sizes, in another order
    assert sorted(len(p) for _, p, _, _ in a) == sorted(
        len(p) for _, p, _, _ in b)
    assert sorted(n for _, _, n, _ in a) == sorted(n for _, _, n, _ in b)


def test_closed_loop_starts_mid_request_and_refills():
    g = loops.make(CLOSED, seed=3, seconds=10, vocab=1000)
    warm = g.warm()
    assert len(warm) == 8 and {r.client for r in warm} == set(range(8))
    assert all(r.max_new >= 16 for r in warm)
    assert max(len(r.prompt) for r in warm) > 512     # part already made
    g.done(warm[0], 1.5)
    (nxt,) = g.due(2.0)
    assert nxt.client == warm[0].client and nxt.due == 1.5
    assert g.due(3.0) == []
