"""A test-only configuration's weight layout: a repeating unit of
sliding-window ("local") and full ("global") attention blocks, each
with the program's sparse-expert FFN, and a retention gate per block.
The tests copy it into a benchmark tree as
`references/tiny_moe_swa.py`, beside a config and a cell, to show that
such a model joins the benchmark as added files alone. It has no
`position_logits`: it lays out weights and counts them, nothing more.

Canonical names: `u<i>.<tensor>` for position i of the unit, stacked
over the R repeats of the unit; `t<j>.<tensor>` for the j-th layer of
the tail (num_layers % len(unit)), unstacked.
"""
from __future__ import annotations

import jax.numpy as jnp

from chipbench.weights import padded_vocab


def _counts(m):
    """(unit, repeats R, tail) as the program groups the layers."""
    unit, L = tuple(m["attn_pattern"]), m["num_layers"]
    return unit, L // len(unit), unit[:L % len(unit)]


def _block(m, lead):
    """name -> (shape, dtype, role) of one block's tensors, each with
    the leading axes `lead`."""
    d, f, E = m["d_model"], m["d_ff"], m["num_experts"]
    q, kv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    G, Hk = m["gate_hidden"], m["num_kv_heads"]
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {
        "attn_norm": ((d,), f32, "norm"), "wq": ((d, q), bf, "matrix"),
        "wk": ((d, kv), bf, "matrix"), "wv": ((d, kv), bf, "matrix"),
        "wo": ((q, d), bf, "matrix"), "mlp_norm": ((d,), f32, "norm"),
        "router": ((d, E), f32, "matrix"),
        "expert_gate": ((E, d, f), bf, "matrix"),
        "expert_up": ((E, d, f), bf, "matrix"),
        "expert_down": ((E, f, d), bf, "matrix"),
        "gate_w1": ((d, G), f32, "matrix"),
        "gate_w2": ((G, Hk), f32, "gate_out"),
        "gate_b": ((Hk,), f32, "gate_bias"),
    }
    return {n: (lead + s, dt, r) for n, (s, dt, r) in out.items()}


def canonical_shapes(m: dict) -> dict:
    unit, R, tail = _counts(m)
    Vp, d = padded_vocab(m["vocab_size"]), m["d_model"]
    shapes = {"embed": ((Vp, d), jnp.bfloat16, "embed"),
              "unembed": ((d, Vp), jnp.bfloat16, "matrix"),
              "final_norm": ((d,), jnp.float32, "norm")}
    for i in range(len(unit)):
        shapes.update({f"u{i}.{n}": v for n, v in _block(m, (R,)).items()})
    for j in range(len(tail)):
        shapes.update({f"t{j}.{n}": v for n, v in _block(m, ()).items()})
    return shapes


def _nest(w, p):
    block = {
        "norm1": {"scale": w[p + "attn_norm"]},
        "attn": {k: {"w": w[p + k]} for k in ("wq", "wk", "wv", "wo")},
        "norm2": {"scale": w[p + "mlp_norm"]},
        "ffn": {"router": {"w": w[p + "router"]},
                "gate_w": w[p + "expert_gate"],
                "up_w": w[p + "expert_up"],
                "down_w": w[p + "expert_down"]},
    }
    gate = {"w1": {"w": w[p + "gate_w1"]}, "w2": {"w": w[p + "gate_w2"]},
            "b": w[p + "gate_b"]}
    return block, gate


def to_program(w: dict, m: dict):
    unit, R, tail = _counts(m)
    units = [_nest(w, f"u{i}.") for i in range(len(unit))]
    tails = [_nest(w, f"t{j}.") for j in range(len(tail))]
    params = {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
              "unembed": {"w": w["unembed"]},
              "layers": tuple(b for b, _ in units),
              "tail": tuple(b for b, _ in tails)}
    gates = {"layers": tuple(g for _, g in units),
             "tail": tuple(g for _, g in tails)}
    return params, gates


def layer_weights(m: dict) -> int:
    """Weights a token multiplies per layer: Q/K/V/O, the router, the
    experts it is routed to, and the retention gate."""
    d, f, E, k = (m["d_model"], m["d_ff"], m["num_experts"],
                  m["experts_per_token"])
    q, kv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    G, Hk = m["gate_hidden"], m["num_kv_heads"]
    return 2 * d * q + 2 * d * kv + d * E + 3 * k * d * f + d * G + G * Hk
