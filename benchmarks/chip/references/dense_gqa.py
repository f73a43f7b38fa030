"""Plain reference of a dense GQA decoder served with a TRIM-KV bounded
cache (budget M keys per layer and kv head), written from the
published description and independent of the program under test.

Model (Qwen-style): token embedding; per layer a pre-norm attention
block (RMSNorm, Q/K/V projections with optional bias, rotary position
embedding in the half-split form, softmax attention with each group
of query heads sharing one kv head, output projection) and a pre-norm
SwiGLU MLP, both residual; a final RMSNorm and an untied output head.

Retention (TRIM-KV, arXiv:2512.03324 Alg. 1 and Sec B.3): each layer's
gate gives every token a retention beta = sigmoid(MLP(normed input)) per
kv head, and a cached key at position j has keep score beta_j^(t - j) at
time t. A prompt is prefilled in chunks of C positions: a chunk's
queries see the keys kept so far and, causally, the chunk's own keys;
after the chunk the M highest-scoring keys of (kept ∪ chunk), scored at
the chunk's last position, are kept. Then each decoded position t
attends to the kept keys and to itself, after which, if M keys were
kept, the one with the lowest score at time t is evicted, and t is
kept. Attention itself carries no retention bias.

The reference never builds a slot cache: it computes, per layer and kv
head, the first query position at which each key is no longer visible
(`evict_times`), and masks a plain full attention with it. Everything
is float32 with matmuls at `highest` precision, run layer by layer on
blocks of rows so that it fits beside the served weights. With
mode="fp8" every matrix product takes its inputs rounded to float8
e4m3 with a per-tensor (weights) or per-row (activations) scale: the
lower-precision control that the comparison has to reject.

The layout of the weights is this module's too (`canonical_shapes`,
`to_program`): per-layer tensors stacked on a leading layer axis under
plain names, which the reference reads and the harness re-nests into
the program's tree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import padded_vocab

HI = jax.lax.Precision.HIGHEST
ROWS = 2048          # rows per block of the projections and the MLP
Q_BLOCK = 256        # query rows per block of the attention
T_BUCKET = 1024      # padded sequence lengths are multiples of this
N_BUCKET = 512       # padded compared-position counts likewise
GROUP = 4            # sequences per batch of the reference
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def canonical_shapes(m: dict) -> dict:
    """name -> (shape, dtype, role) of every tensor of model `m` (the
    `model` block of a configuration file); roles as
    `chipbench.weights` draws them."""
    L, d, F = m["num_layers"], m["d_model"], m["d_ff"]
    q, kv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    G, Vp = m["gate_hidden"], padded_vocab(m["vocab_size"])
    bf = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[m["dtype"]]
    f32 = jnp.float32
    shapes = {
        "embed": ((Vp, d), bf, "embed"),
        "unembed": ((d, Vp), bf, "matrix"),
        "final_norm": ((d,), f32, "norm"),
        "attn_norm": ((L, d), f32, "norm"),
        "wq": ((L, d, q), bf, "matrix"),
        "wk": ((L, d, kv), bf, "matrix"),
        "wv": ((L, d, kv), bf, "matrix"),
        "wo": ((L, q, d), bf, "matrix"),
        "mlp_norm": ((L, d), f32, "norm"),
        "w_gate": ((L, d, F), bf, "matrix"),
        "w_up": ((L, d, F), bf, "matrix"),
        "w_down": ((L, F, d), bf, "matrix"),
        "gate_w1": ((L, d, G), f32, "matrix"),
        "gate_w2": ((L, G, m["num_kv_heads"]), f32, "gate_out"),
        "gate_b": ((L, m["num_kv_heads"]), f32, "gate_bias"),
    }
    if m["qkv_bias"]:
        shapes.update(bq=((L, q), bf, "bias"), bk=((L, kv), bf, "bias"),
                      bv=((L, kv), bf, "bias"))
    return shapes


def to_program(w: dict, m: dict):
    """(params, gates) in the tree the program's dense 'global' model
    takes: one scanned unit of one block, tensors stacked per layer.
    The arrays are the same objects as in `w`; `m` is not read, since
    the dense layout is the same at every size."""
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


def _q8(x, axis):
    """x rounded to float8 e4m3 under a scale that maps its largest
    magnitude along `axis` (None: the whole tensor) to 448."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(a, w, mode):
    """a [..., k] @ w [k, n] in float32."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "fp8":
        a, w = _q8(a, -1), _q8(w, None)
    return jnp.matmul(a, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    """x [..., T, H, D]; pos [T]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs          # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _row_blocks(fn, x):
    """fn over blocks of ROWS rows of x [R, ...] (R a multiple of ROWS
    or smaller than it)."""
    R = x.shape[0]
    if R <= ROWS:
        return fn(x)
    out = jax.lax.map(fn, x.reshape((R // ROWS, ROWS) + x.shape[1:]))
    return jax.tree.map(lambda o: o.reshape((R,) + o.shape[2:]), out)


def evict_times(beta, prompt_len, total_len, budget, chunk):
    """First query position at which each key stops being visible.

    beta: [T] float32 retention of the keys of one kv head; prompt_len,
    total_len: scalars (positions >= total_len are padding). Returns
    [T] int32; a key never evicted gets 2**30."""
    T = beta.shape[0]
    j = jnp.arange(T, dtype=jnp.int32)
    logb = jnp.log(jnp.maximum(beta, 1e-30))
    never = jnp.int32(2 ** 30)

    def log_score(kept, t):
        return jnp.where(kept, (t - j).astype(jnp.float32) * logb, -jnp.inf)

    def merge(c, carry):
        kept, ev = carry
        end = jnp.minimum((c + 1) * chunk, prompt_len) - 1
        kept = kept | ((j >= c * chunk) & (j <= end))
        s = log_score(kept, end)
        kth = jax.lax.top_k(s, budget)[0][budget - 1]
        keep = kept & (s >= kth)
        return keep, jnp.where(kept & ~keep, end + 1, ev)

    n_chunks = (prompt_len + chunk - 1) // chunk
    kept, ev = jax.lax.fori_loop(
        0, n_chunks, merge,
        (jnp.zeros((T,), bool), jnp.full((T,), never, jnp.int32)))

    def step(t, carry):
        kept, ev = carry
        full = jnp.sum(kept) >= budget
        victim = jnp.argmin(jnp.where(kept, log_score(kept, t), jnp.inf))
        out = full & (j == victim)
        return (kept & ~out) | (j == t), jnp.where(out, t + 1, ev)

    _, ev = jax.lax.fori_loop(prompt_len, total_len, step, (kept, ev))
    return ev


def _layer(x, w, l, plen, tlen, *, m, budget, chunk, mode):
    """One decoder layer over x [S, T, d] float32."""
    S, T, d = x.shape
    H, Hk, D = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    lw = {k: jax.lax.dynamic_index_in_dim(v, l, keepdims=False)
          for k, v in w.items()
          if k not in ("embed", "unembed", "final_norm")}
    pos = jnp.arange(T, dtype=jnp.int32)

    def project(rows):
        h = _rms(rows, lw["attn_norm"], eps)
        q, k, v = (_mm(h, lw[n], mode) for n in ("wq", "wk", "wv"))
        if "bq" in lw:
            q = q + lw["bq"].astype(jnp.float32)
            k = k + lw["bk"].astype(jnp.float32)
            v = v + lw["bv"].astype(jnp.float32)
        g = jax.nn.silu(jnp.matmul(h, lw["gate_w1"], precision=HI))
        beta = jax.nn.sigmoid(jnp.matmul(g, lw["gate_w2"], precision=HI)
                              + lw["gate_b"])
        return q, k, v, beta

    q, k, v, beta = _row_blocks(project, x.reshape(S * T, d))
    q = _rope(q.reshape(S, T, H, D), pos, m["rope_theta"])
    k = _rope(k.reshape(S, T, Hk, D), pos, m["rope_theta"])
    v = v.reshape(S, T, Hk, D)
    beta = beta.reshape(S, T, Hk)

    ev = jax.vmap(jax.vmap(
        lambda b, p, n: evict_times(b, p, n, budget, chunk),
        in_axes=(1, None, None)), in_axes=(0, 0, 0))(beta, plen, tlen)
    # ev: [S, Hk, T]

    if mode == "fp8":
        k8 = _q8(k, -1)
        v8 = _q8(v, -1)
    else:
        k8, v8 = k, v
    qg = q.reshape(S, T, Hk, H // Hk, D)

    def attend(qi):
        t0 = qi * Q_BLOCK
        qb = jax.lax.dynamic_slice_in_dim(qg, t0, Q_BLOCK, axis=1)
        if mode == "fp8":
            qb = _q8(qb, -1)
        s = jnp.einsum("sqhgd,skhd->shgqk", qb, k8,
                       precision=HI) / np.sqrt(D)
        tq = t0 + jnp.arange(Q_BLOCK, dtype=jnp.int32)
        vis = ((pos[None, None, None, :] <= tq[None, None, :, None])
               & (tq[None, None, :, None] < ev[:, :, None, :])
               & (pos[None, None, None, :] < tlen[:, None, None, None]))
        s = jnp.where(vis[:, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(vis[:, :, None], p, 0.0)
        if mode == "fp8":
            p = _q8(p, -1)
        return jnp.einsum("shgqk,skhd->sqhgd", p, v8, precision=HI)

    att = jax.lax.map(attend, jnp.arange(T // Q_BLOCK))
    att = jnp.moveaxis(att, 0, 1).reshape(S * T, H * D)
    x = x.reshape(S * T, d) + _row_blocks(
        lambda r: _mm(r, lw["wo"], mode), att)

    def mlp(rows):
        h = _rms(rows, lw["mlp_norm"], eps)
        a = jax.nn.silu(_mm(h, lw["w_gate"], mode)) * _mm(h, lw["w_up"],
                                                          mode)
        return rows + _mm(a, lw["w_down"], mode)

    return _row_blocks(mlp, x).reshape(S, T, d)


@functools.lru_cache(maxsize=None)
def _layer_fn(m_items, budget, chunk, mode):
    m = dict(m_items)
    return jax.jit(functools.partial(_layer, m=m, budget=budget,
                                     chunk=chunk, mode=mode),
                   donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _embed_fn():
    return jax.jit(lambda e, toks: jnp.take(e, toks, axis=0)
                   .astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def _head_fn(m_items, mode):
    m = dict(m_items)
    V = m["vocab_size"]

    @jax.jit
    def head(x, rows, probes, final_norm, unembed):
        """x [S,T,d]; rows [S,N] positions whose logits are read;
        probes [P,S,N] token ids. Returns max logit [S,N], argmax [S,N]
        and the probes' logits [P,S,N]."""
        h = jnp.take_along_axis(x, rows[..., None], axis=1)     # [S,N,d]
        h = _rms(h, final_norm, m["norm_eps"])
        S, N, d = h.shape
        P = probes.shape[0]
        head_w = unembed[:, :V]

        def block(args):
            hb, pb = args                                 # [R,d], [R,P]
            logits = _mm(hb, head_w, mode)                # [R, V]
            at = jnp.take_along_axis(logits, pb, axis=-1)
            return (jnp.max(logits, -1),
                    jnp.argmax(logits, -1).astype(jnp.int32), at)

        R = N_BUCKET
        hs = h.reshape(S * N // R, R, d)
        ps = jnp.moveaxis(probes, 0, -1).reshape(S * N // R, R, P)
        mx, am, at = jax.lax.map(block, (hs, ps))
        return (mx.reshape(S, N), am.reshape(S, N),
                jnp.moveaxis(at.reshape(S, N, P), -1, 0))
    return head


def _bucket(n, b):
    return max(b, -(-n // b) * b)


def position_logits(w, m, seqs, *, budget, chunk, mode="f32",
                    probes=()):
    """Run the reference over each (prompt, served) pair of `seqs`.

    The sequence fed is the prompt followed by every served token but
    the last; the logits compared are those that chose each served
    token: at positions P-1 .. P+N-2. `probes` is a list of per-sequence
    token arrays (each of length N) whose logits are read at those
    positions. Returns, per sequence, a dict with "max" [N] (the
    largest logit), "argmax" [N] and "probes" [len(probes), N]."""
    m_items = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                           for k, v in m.items()))
    out = []
    with jax.default_matmul_precision("highest"):
        for g0 in range(0, len(seqs), GROUP):
            group = seqs[g0:g0 + GROUP]
            n_real = len(group)
            idx = list(range(g0, g0 + n_real))
            group = group + [group[-1]] * (GROUP - n_real)
            lens = [len(p) + len(s) - 1 for p, s in group]
            T = _bucket(max(lens), T_BUCKET)
            N = _bucket(max(len(s) for _, s in group), N_BUCKET)
            toks = np.zeros((GROUP, T), np.int32)
            rows = np.zeros((GROUP, N), np.int32)
            prb = np.zeros((max(len(probes), 1), GROUP, N), np.int32)
            for i, (p, s) in enumerate(group):
                seq = np.concatenate([p, s[:-1]]).astype(np.int32)
                toks[i, :len(seq)] = seq
                rows[i, :len(s)] = len(p) - 1 + np.arange(len(s))
                for k, pr in enumerate(probes):
                    prb[k, i, :len(s)] = pr[idx[min(i, n_real - 1)]]
            plen = jnp.asarray([len(p) for p, _ in group], jnp.int32)
            tlen = jnp.asarray(lens, jnp.int32)
            x = _embed_fn()(w["embed"], jnp.asarray(toks))
            fn = _layer_fn(m_items, budget, chunk, mode)
            for l in range(m["num_layers"]):
                x = fn(x, w, jnp.int32(l), plen, tlen)
            mx, am, at = _head_fn(m_items, mode)(
                x, jnp.asarray(rows), jnp.asarray(prb), w["final_norm"],
                w["unembed"])
            mx, am, at = np.asarray(mx), np.asarray(am), np.asarray(at)
            del x
            for i in range(n_real):
                n = len(group[i][1])
                out.append({"max": mx[i, :n], "argmax": am[i, :n],
                            "probes": at[:len(probes), i, :n]})
    return out
