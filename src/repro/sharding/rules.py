"""Logical-axis sharding rules with divisibility guards (DESIGN.md §5).

MaxText-style: every parameter / activation leaf is matched by its tree
path and rank to a tuple of *logical* axes for its trailing dims; logical
axes map to mesh axes with a divisibility guard — if a dim is not
divisible by the mesh-axis product the assignment is dropped (replicated
on that dim) instead of failing. Leading dims introduced by layer
stacking (lax.scan over repeats) are always replicated.

Mesh axes:
  "pod"   across pods (multi-pod only)
  "data"  data parallel / FSDP
  "model" tensor parallel (Megatron column/row split)

Guards matter because the assigned archs are hostile on purpose: 10 / 40
/ 24 heads, 8 / 40 experts, vocab 49155 / 256206 — none divide 16 evenly
without the padded-vocab trick and the fused-head fallback.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# --------------------------------------------------------------- helpers


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def fsdp_axes(mesh: Mesh):
    """The combined data-parallel axes ("pod","data") present in mesh."""
    names = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return names if names else None


def lanes_sharded(mesh) -> bool:
    """True when serving lanes shard over the data axes of `mesh`
    (("pod","data") of size > 1); False with no mesh or a model-only
    mesh."""
    return mesh is not None and _axis_size(mesh, fsdp_axes(mesh)) > 1


def _guard(mesh: Mesh, dim: int, axes):
    """Return `axes` if dim divides evenly over them, else None."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    axes = tuple(a for a in axes if a in mesh.shape)
    if not axes:
        return None
    if dim % _axis_size(mesh, axes) != 0:
        return None
    return axes if len(axes) > 1 else axes[0]


def pick(mesh: Mesh, dim: int, *candidates, used=()):
    """First candidate (tuple of mesh axes) that divides `dim` and does
    not reuse an already-used axis."""
    flat_used = set()
    for u in used:
        if u is None:
            continue
        flat_used.update((u,) if isinstance(u, str) else u)
    for cand in candidates:
        g = _guard(mesh, dim, cand)
        if g is None:
            continue
        gset = {g} if isinstance(g, str) else set(g)
        if gset & flat_used:
            continue
        return g
    return None


def _spec(mesh: Mesh, shape, trailing):
    """Right-align `trailing` dim assignments onto `shape` with guards."""
    n = len(shape)
    k = len(trailing)
    dims = [None] * n
    used = []
    for j, want in enumerate(trailing):
        i = n - k + j
        if i < 0:
            continue
        got = pick(mesh, shape[i], want, used=used)
        dims[i] = got
        used.append(got)
    while dims and dims[-1] is None:            # P(None,..) == P()
        dims.pop()
    return P(*dims)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


# --------------------------------------------------------- param rules

# (regex over path, trailing logical dims). Logical dims are expressed
# directly as candidate mesh axes; "FSDP" is substituted per-mesh.
FSDP = "__fsdp__"

_PARAM_RULES: Sequence[Tuple[str, Tuple]] = (
    # embedding / unembedding: vocab-parallel + FSDP on d_model
    (r"(^|/)embed$",                   ("model", FSDP)),
    (r"(^|/)unembed/w$",               (FSDP, "model")),
    # attention: column-parallel QKV (fused head dim), row-parallel out
    (r"(^|/)(attn|xattn)/(wq|wk|wv)/w$", (FSDP, "model")),
    (r"(^|/)(attn|xattn)/(wq|wk|wv)/b$", ("model",)),
    (r"(^|/)(attn|xattn)/wo/w$",       ("model", FSDP)),
    (r"(^|/)(attn|xattn)/wo/b$",       (None,)),
    # dense FFN: column-parallel up/gate, row-parallel down
    (r"(^|/)ffn/(gate|up)/w$",         (FSDP, "model")),
    (r"(^|/)ffn/down/w$",              ("model", FSDP)),
    # MoE: expert-parallel when E divides, else TP on d_ff (guards pick)
    (r"(^|/)ffn/router/w$",            (FSDP, None)),
    (r"(^|/)ffn/(gate_w|up_w)$",       ("model", FSDP, "model")),
    (r"(^|/)ffn/down_w$",              ("model", "model", FSDP)),
    # RG-LRU (Griffin): width dim is TP
    (r"(^|/)(in_x|in_gate)/w$",        (FSDP, "model")),
    (r"(^|/)(lru_wa|lru_wx)/w$",       (None, "model")),
    (r"(^|/)out/w$",                   ("model", FSDP)),
    (r"(^|/)lru_lam$",                 ("model",)),
    # Mamba-1: d_inner is TP
    (r"(^|/)in_proj/w$",               (FSDP, "model")),
    (r"(^|/)x_proj/w$",                ("model", None)),
    (r"(^|/)dt_proj/w$",               (None, "model")),
    (r"(^|/)dt_proj/b$",               ("model",)),
    (r"(^|/)A_log$",                   ("model", None)),
    (r"(^|/)D$",                       ("model",)),
    (r"(^|/)out_proj/w$",              ("model", FSDP)),
    # depthwise conv (recurrent + mamba): channel dim is TP
    (r"(^|/)conv_w$",                  (None, "model")),
    (r"(^|/)conv_b$",                  ("model",)),
    # vision projector
    (r"(^|/)vis_proj/w$",              (None, FSDP)),
    # norms / scalars / retention gates: replicated
    (r".*",                            ()),
)


_ATTN_W = re.compile(r"(^|/)(attn|xattn)/(wq|wk|wv|wo)/(w|b)$")


def param_spec(mesh: Mesh, path_str: str, shape, *,
               fsdp: bool = True, q_tp: bool = True,
               kv_tp: bool = True) -> P:
    """fsdp=False: tensor-parallel only (weights replicated over the
    data axes). The serving path uses this when the TP footprint fits
    HBM — decode must not all-gather weights every step (§Perf it. 2).

    q_tp / kv_tp: whether the q / kv HEAD COUNT divides the model axis.
    Column-sharding the fused QKV dim when heads do NOT divide makes
    the [T, fused] -> [T, H, Dh] reshape unshardable, and XLA reshards
    the full activation every layer (measured 25 TB/chip of all-reduce
    on qwen train_4k — §Perf train iteration 1). When heads don't
    divide, attention weights are replicated on the model axis instead
    (FSDP still shards storage); FFN stays TP.
    """
    fsdp_ax = fsdp_axes(mesh) if fsdp else None
    m = _ATTN_W.search(path_str)
    if m:
        which, kind = m.group(3), m.group(4)
        tp = q_tp if which in ("wq", "wo") else (q_tp and kv_tp)
        if not tp:
            if kind == "b":
                return P()
            trailing = ((None, fsdp_ax) if which == "wo"
                        else (fsdp_ax, None))
            return _spec(mesh, shape, trailing)
    for pat, trailing in _PARAM_RULES:
        if re.search(pat, path_str):
            trailing = tuple(fsdp_ax if t == FSDP else t for t in trailing)
            return _spec(mesh, shape, trailing)
    return P()


def param_shardings(mesh: Mesh, params, *, fsdp: bool = True,
                    q_tp: bool = True, kv_tp: bool = True):
    """Pytree of NamedSharding for a params/grads pytree (shapes may be
    jax.ShapeDtypeStruct or arrays)."""
    def one(path, leaf):
        return NamedSharding(mesh, param_spec(mesh, _path_str(path),
                                              leaf.shape, fsdp=fsdp,
                                              q_tp=q_tp, kv_tp=kv_tp))
    return jax.tree_util.tree_map_with_path(one, params)


def attn_tp_flags(cfg, mesh):
    """(q_tp, kv_tp) divisibility of head counts by the model axis."""
    m = mesh.shape.get("model", 1)
    if not cfg.has_attention():
        return True, True
    return cfg.num_heads % m == 0, cfg.num_kv_heads % m == 0


# Mesh registry for context-parallel attention (set by the launch
# builders before tracing; blocks.py reads it at trace time).
_CP_MESH = None


def set_cp_mesh(mesh) -> None:
    global _CP_MESH
    _CP_MESH = mesh


def get_cp_mesh():
    return _CP_MESH


def replicated(mesh: Mesh, tree):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


# ----------------------------------------------------- activation rules


def batch_spec(mesh: Mesh, shape) -> P:
    """Token-like input [B, T] or [B]: batch over combined data axes."""
    fsdp = fsdp_axes(mesh)
    dims = [pick(mesh, shape[0], fsdp)] + [None] * (len(shape) - 1)
    return P(*dims)


def batch_shardings(mesh: Mesh, batch):
    return jax.tree.map(
        lambda leaf: NamedSharding(mesh, batch_spec(mesh, leaf.shape)),
        batch)


def _cache_dims(mesh: Mesh, b, hkv, m):
    """Allocator for bounded-cache tensors [..., B, Hkv, M(, Dh)]:
    B -> data axes; Hkv -> model if divisible, else M -> model; leftover
    data axes spill onto M when B doesn't shard (long_500k batch=1)."""
    fsdp = fsdp_axes(mesh)
    d_b = pick(mesh, b, fsdp)
    d_h = pick(mesh, hkv, "model", used=(d_b,))
    d_m = pick(mesh, m, ("pod", "data", "model"), ("data", "model"),
               ("pod", "data"), ("data",), "model", used=(d_b, d_h))
    return d_b, d_h, d_m


def state_spec(mesh: Mesh, path_str: str, shape) -> P:
    """Decode/prefill state leaves. Layer-stacked leaves carry extra
    leading dims; rules are right-aligned.

    Covers EVERY leaf `T.init_decode_state` can produce (audited against
    `jax.eval_shape` per registered config by tests/test_sharding.py):
    bounded caches (k/v/beta/pos/aux), cross-memory slabs (xk/xv) and
    their per-lane valid lengths (mem_len), recurrent/ssm tails (h/conv)
    and the per-lane clock (t). Falling through to P() is reserved for
    genuinely replicated leaves — an unmatched per-lane leaf is a drift
    bug, not a default."""
    n = len(shape)
    if n == 0:
        return P()
    key = path_str.rsplit("/", 1)[-1]
    if key in ("t", "mem_len"):                 # [.., B] per-lane scalars
        fsdp = fsdp_axes(mesh)
        b = pick(mesh, shape[-1], fsdp)
        return P(*([None] * (n - 1)), b)
    if key in ("k", "v"):                       # [.., B, Hkv, M, Dh]
        if n < 4:
            return P()
        b, h, m = _cache_dims(mesh, shape[-4], shape[-3], shape[-2])
        return P(*([None] * (n - 4)), b, h, m, None)
    if key in ("beta", "pos", "aux"):           # [.., B, Hkv, M]
        if n < 3:
            return P()
        b, h, m = _cache_dims(mesh, shape[-3], shape[-2], shape[-1])
        return P(*([None] * (n - 3)), b, h, m)
    if key in ("xk", "xv"):                     # [.., B, S, Hkv, Dh]
        if n < 4:
            return P()
        fsdp = fsdp_axes(mesh)
        b = pick(mesh, shape[-4], fsdp)
        h = pick(mesh, shape[-2], "model", used=(b,))
        s = None if h is not None else pick(mesh, shape[-3], "model",
                                            used=(b,))
        return P(*([None] * (n - 4)), b, s, h, None)
    if key == "h":        # griffin [(R,) B, W] | mamba [(R,) B, di, n]
        # Rank alone cannot split stacked-griffin [R, B, W] from
        # unstacked-mamba [B, di, n]; the PATH can — layer-stacked
        # leaves live under "layers/" (lane dim 1), tail leaves are
        # unstacked (lane dim 0). Either way the TP channel dim (W /
        # d_inner) sits immediately after the lane dim.
        fsdp = fsdp_axes(mesh)
        lane = 1 if path_str.startswith("layers") else 0
        if n < lane + 2:
            return P()
        dims = [None] * n
        b = pick(mesh, shape[lane], fsdp)
        dims[lane] = b
        dims[lane + 1] = pick(mesh, shape[lane + 1], "model", used=(b,))
        while dims and dims[-1] is None:
            dims.pop()
        return P(*dims)
    if key == "conv":                           # [.., B, W-1, C]
        if n < 3:
            return P()
        fsdp = fsdp_axes(mesh)
        b = pick(mesh, shape[-3], fsdp)
        c = pick(mesh, shape[-1], "model", used=(b,))
        return P(*([None] * (n - 3)), b, None, c)
    return P()


def state_shardings(mesh: Mesh, state):
    def one(path, leaf):
        return NamedSharding(mesh, state_spec(mesh, _path_str(path),
                                              leaf.shape))
    return jax.tree_util.tree_map_with_path(one, state)


# ----------------------------------------------------- serving operands


def lane_operand_spec(mesh: Mesh, shape, lane_axis: int = 0) -> P:
    """Scheduler closure operands that carry the lane/batch axis at
    `lane_axis` — per-lane bookkeeping (tok/keys/active/n_emitted/
    max_new/eos/lane masks, spec history, health flags), chunk grids
    [n_chunks, B, C] (lane_axis=1) and cross-memory slabs [B, S, feat]:
    the lane axis shards over the combined data axes (divisibility-
    guarded — a non-dividing lane count degrades to replication, it
    never fails), every other dim is replicated. The "model" axis never
    appears here: these operands are broadcast to every tensor-parallel
    shard of a lane group."""
    fsdp = fsdp_axes(mesh)
    dims = [None] * len(shape)
    if shape:
        dims[lane_axis] = pick(mesh, shape[lane_axis], fsdp)
    while dims and dims[-1] is None:
        dims.pop()
    return P(*dims)


def lane_operand_sharding(mesh: Mesh, shape,
                          lane_axis: int = 0) -> NamedSharding:
    return NamedSharding(mesh, lane_operand_spec(mesh, shape, lane_axis))


# -------------------------------------------------------- train bundles


def train_state_shardings(mesh: Mesh, state, *, q_tp: bool = True,
                          kv_tp: bool = True):
    """{"params": frozen base (TP+FSDP), "gates"/"opt": replicated}."""
    out = {"params": param_shardings(mesh, state["params"],
                                     q_tp=q_tp, kv_tp=kv_tp),
           "gates": replicated(mesh, state["gates"]),
           "opt": jax.tree.map(
               lambda leaf: NamedSharding(mesh, P()), state["opt"])}
    return out


def describe(shardings) -> str:
    """Human-readable dump of a sharding pytree (debugging aid)."""
    lines = []

    def one(path, s):
        lines.append(f"{_path_str(path)}: {s.spec}")
        return s
    jax.tree_util.tree_map_with_path(one, shardings)
    return "\n".join(lines)
