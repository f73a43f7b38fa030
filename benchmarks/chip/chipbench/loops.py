"""Load generators, found by the `loop` key of a traffic file.

A generator hands the client requests: `warm()` before the window
(closed loops start every client mid-request, so the window opens in
steady state), `due(now)` during it (now: seconds since the window
opened), and hears `done(req, now)` when a request finishes. Each
request carries its `due` time on the window's clock; warm requests
carry None.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from chipbench import laws, seeds


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray
    max_new: int
    client: int = -1
    due: Optional[float] = None


def _tokens(rng, vocab, n):
    return rng.integers(0, vocab, size=int(n), dtype=np.int64).astype(
        np.int32)


class OpenLoop:
    """Requests sent on a schedule whatever the server does: the gaps
    of `arrivals` at a fixed `rate` (requests per second)."""

    def __init__(self, traffic, *, seed, seconds, vocab):
        rate = float(traffic["rate"])
        n = max(1, int(rate * seconds))
        order = seeds.rng(seed, "order")
        gaps = laws.gaps(traffic["arrivals"], rate, n)
        due = np.cumsum(order.permutation(gaps))
        plen = order.permutation(laws.lengths(traffic["prompt_len"], n))
        olen = order.permutation(laws.lengths(traffic["output_len"], n))
        tok = seeds.rng(seed, "tokens")
        self.reqs = [Req(i, _tokens(tok, vocab, plen[i]), int(olen[i]),
                         due=float(due[i])) for i in range(n)]
        self.next = 0
        self.max_prompt = int(plen.max())

    def warm(self) -> List[Req]:
        return []

    def next_due(self) -> Optional[float]:
        return (self.reqs[self.next].due if self.next < len(self.reqs)
                else None)

    def due(self, now: float) -> List[Req]:
        out = []
        while self.next < len(self.reqs) and self.reqs[self.next].due <= now:
            out.append(self.reqs[self.next])
            self.next += 1
        return out

    def done(self, req: Req, now: float) -> None:
        pass


class ClosedLoop:
    """`clients` callers, each sending its next request when the last
    one finishes. Sizes come from a stratified pool of
    clients x requests_per_client, dealt out in the seed's order. Each
    client starts mid-request: its first request has a prompt that
    covers the part already generated and the rest of its output to
    go; the (prompt, output, fraction done) triples of these starts are
    one fixed multiset, so every seed warms the same shapes. A traffic
    file's `warm_prompt_max` caps the length of those prompts (the part
    already generated is then only partly in the prompt)."""

    def __init__(self, traffic, *, seed, seconds, vocab):
        c = int(traffic["clients"])
        n = c * int(traffic["requests_per_client"])
        order = seeds.rng(seed, "order")
        tok = seeds.rng(seed, "tokens")
        plen = order.permutation(laws.lengths(traffic["prompt_len"], n))
        olen = order.permutation(laws.lengths(traffic["output_len"], n))
        self.queues = [[] for _ in range(c)]
        for i in range(n):
            self.queues[i % c].append(
                Req(c + i, _tokens(tok, vocab, plen[i]), int(olen[i]),
                    client=i % c))
        fixed = np.random.default_rng(0)      # one multiset for all seeds
        wp = fixed.permutation(laws.lengths(traffic["prompt_len"], c))
        wo = fixed.permutation(laws.lengths(traffic["output_len"], c))
        wf = fixed.permutation(laws.quantiles(c))
        min_left = int(traffic.get("min_left", 16))
        cap = traffic.get("warm_prompt_max")
        self.warm_reqs = []
        for slot, i in enumerate(order.permutation(c)):
            done_part = max(min(int(wf[i] * wo[i]), int(wo[i]) - min_left),
                            0)
            P = wp[i] + done_part
            if cap is not None:
                P = min(P, int(cap))
            self.warm_reqs.append(Req(
                slot, _tokens(tok, vocab, P), int(wo[i]) - done_part,
                client=slot))
        self.ready: List[Req] = []
        self.max_prompt = int(plen.max())

    def warm(self) -> List[Req]:
        return list(self.warm_reqs)

    def next_due(self) -> Optional[float]:
        return None

    def due(self, now: float) -> List[Req]:
        out, self.ready = self.ready, []
        return out

    def done(self, req: Req, now: float) -> None:
        q = self.queues[req.client]
        if q:
            nxt = q.pop(0)
            nxt.due = now
            self.ready.append(nxt)


LOOPS = {"open": OpenLoop, "closed": ClosedLoop}


def make(traffic: dict, *, seed: int, seconds: float, vocab: int):
    return LOOPS[traffic["loop"]](traffic, seed=seed, seconds=seconds,
                                  vocab=vocab)
