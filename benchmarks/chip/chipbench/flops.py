"""Operations and bytes that the algorithm needs, from shapes alone.

Model FLOPs follow the repo's roofline arithmetic (2 per weight per
token forward; attention 4 * Hq * Dh per visible key per layer, with
the visible keys capped by the budget M), copied here so that the
program can change without moving the yardstick. A kernel's bytes are
those its algorithm must move: each retained K/V slab read once per
lane, kv head and layer, the queries read and the output written once;
nothing that a faster kernel could skip (re-reads of a slab per query
head, probabilities nobody consumes) is counted.

`m` is the `model` block of a configuration file; `ref`, where given,
is its reference module, which may count its own layer's weights.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def layer_weights(m: dict, ref=None) -> int:
    """Weights a token multiplies per layer: the reference's own count
    where it defines `layer_weights(m)` (a sparse FFN counts only the
    experts a token runs), else Q/K/V/O, the dense SwiGLU MLP and the
    retention gate."""
    if hasattr(ref, "layer_weights"):
        return ref.layer_weights(m)
    d, F, G = m["d_model"], m["d_ff"], m["gate_hidden"]
    q = m["num_heads"] * m["head_dim"]
    kv = m["num_kv_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * F + d * G + \
        G * m["num_kv_heads"]


def head_flops(m: dict) -> float:
    return 2.0 * m["d_model"] * m["vocab_size"]


def attn_flops(m: dict, keys: float) -> float:
    """QK^T and PV of one query over `keys` visible keys, one layer."""
    return 4.0 * m["num_heads"] * m["head_dim"] * keys


def decode_token_flops(m: dict, keys: float, ref=None) -> float:
    """One decoded token through every layer and the head."""
    L = m["num_layers"]
    return L * (2.0 * layer_weights(m, ref) + attn_flops(m, keys)) + \
        head_flops(m)


def prefill_chunk_flops(m: dict, kept: int, n: int, ref=None) -> float:
    """n prompt tokens of one chunk through every layer, attending to
    `kept` retained keys and causally to the chunk (the head runs for
    the prompt's last token only; see prefill_head)."""
    L = m["num_layers"]
    pairs = n * kept + n * (n + 1) / 2.0
    return L * (2.0 * layer_weights(m, ref) * n
                + attn_flops(m, 1.0) * pairs)


def decode_attn(m: dict, keys: int):
    """(flops, bytes) of the decode attention of one lane at one layer:
    one query per head against `keys` retained keys plus itself."""
    Hq, Hk, D = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops = attn_flops(m, keys + 1)
    byts = (2 * Hk * keys * D * BF16      # K and V slabs, read once
            + Hk * keys * F32             # their positions
            + Hq * D * BF16               # the query
            + 2 * Hk * D * BF16           # the in-flight key and value
            + Hq * D * F32)               # the output
    return flops, byts


def roofline_seconds(flops: float, byts: float, peak: dict) -> float:
    """The least time the chip could take: the larger of compute and
    memory time at its peaks."""
    return max(flops / peak["bf16_flops"], byts / peak["hbm_bytes_per_s"])
