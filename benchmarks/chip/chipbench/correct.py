"""Whether what the timed path served is right.

Once the window has closed and the program's state is freed, a sample
of the requests served, drawn from the seed, goes through the
configuration's plain reference (prompt and served tokens fed, one
pass). The sample holds the finished request with the most served
tokens and one sent inside the window. At every served position the
reference's best logit minus its logit of the served token is the gap;
the widest gap over the sample is compared with the cell's limit.
Served tokens are greedy, so a sound program only loses to the
reference where rounding reorders nearly tied logits.
"""
from __future__ import annotations

from chipbench import seeds


def sample(run, seed: int, min_tokens: int, max_requests: int):
    """Requests to check: the finished one with the most served tokens;
    one sent inside the window (the finished one with the most tokens,
    else the unfinished one with the most: its lane was reused after a
    retire); then finished ones in the seed's order until `min_tokens`
    served tokens or `max_requests`."""
    t1 = run.window[1]
    done = [r for r in run.recs
            if r.rs.done and r.done is not None and r.done <= t1]
    if not done:
        return []
    done.sort(key=lambda r: (-r.seen, r.req.rid))
    picked = [done[0]]
    sent = [r for r in run.recs if r.due is not None and r.seen >= 2
            and r is not done[0]]
    if sent:
        picked.append(max(sent, key=lambda r: (r.rs.done, r.seen,
                                                -r.req.rid)))
    rest = [r for r in done if all(r is not p for p in picked)]
    order = seeds.rng(seed, "sample").permutation(len(rest))
    for i in order:
        if (sum(r.seen for r in picked) >= min_tokens
                or len(picked) >= max_requests):
            break
        picked.append(rest[i])
    return picked


def served_gaps(cell, w, seqs, probes=None):
    """Per sequence, the reference's best logit minus its logit of each
    probe token (default: the served tokens), at every served
    position."""
    ref = cell.reference()
    serve = cell.serve
    probes = probes or [[s for _, s in seqs]]
    out = ref.position_logits(w, cell.config["model"], seqs,
                              budget=serve["budget"],
                              chunk=serve["prefill_chunk"], probes=probes)
    return [[o["max"] - o["probes"][k] for o in out]
            for k in range(len(probes))]


def judge(lim: dict, gap, n_tok: int, failed: int):
    """(correct, {name: {"value", "limit"}}) of one widest gap, the
    tokens it was read over and the failed requests, against the cell's
    limits `lim`."""
    checks = {
        "max_logit_gap": {"value": gap, "limit": lim["max_logit_gap"]},
        "tokens_compared": {"value": n_tok, "limit": lim["min_tokens"]},
        "failed_requests": {"value": failed, "limit": 0},
    }
    ok = (gap is not None and gap <= lim["max_logit_gap"]
          and n_tok >= lim["min_tokens"] and failed == 0)
    return ok, checks


def check(cell, run, w, seed: int, *, failed: int):
    """(correct, {name: {"value", "limit"}}, the sampled records)."""
    lim = cell.cell["correct"]
    picked = sample(run, seed, lim["sample_tokens"], lim["sample_requests"])
    vocab = cell.config["model"]["vocab_size"]
    seqs = [(r.req.prompt, r.rs.ids) for r in picked]
    in_vocab = all(((s >= 0) & (s < vocab)).all() for _, s in seqs)
    n_tok = sum(len(s) for _, s in seqs)
    gap = None
    if seqs and in_vocab:
        gaps = served_gaps(cell, w, seqs)[0]
        gap = max(float(g.max()) for g in gaps)
    ok, checks = judge(lim, gap, n_tok, failed)
    return ok, checks, picked
