"""Drives the serving path that users run: `build_engine` and an
interleaved `Scheduler`, fed through the scheduler's own `submit` and
`step` on the benchmark's arrival clock, and read from the client's
side: a token counts when `step` has returned it to the host.

Host spans (`client.submit`, `sched.step`, `client.harvest`) go into
the profiler's trace when one is recording, so that idle gaps on the
device can be put down to what the host was doing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from chipbench.loops import Req

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Programs compiled (or read back from the persistent cache) and
    the seconds that took, counted from JAX's monitoring events."""

    def __init__(self):
        self.n, self.sec, self.hits, self.misses = 0, 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, event, duration_secs, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.sec += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"compiles": self.n, "compile_s": self.sec,
                "cache_hits": self.hits, "cache_misses": self.misses}


@dataclasses.dataclass
class Rec:
    """One request as the client saw it (times on the host clock)."""
    req: Req
    rs: object                       # the scheduler's RequestState
    due: Optional[float]             # when it was due (None: warm)
    sent: float                      # when submit() returned
    first: Optional[float] = None    # harvest of the first token
    last: Optional[float] = None     # harvest of the latest token
    seen: int = 0                    # tokens harvested so far
    in_window: int = 0               # of them, inside the window
    win_first: Optional[float] = None  # first harvest inside the window
    win_first_n: int = 0             # tokens that harvest brought
    done: Optional[float] = None     # harvest that finished it


@dataclasses.dataclass
class Step:
    start: float
    end: float
    busy: int                        # lanes holding a request
    tokens: int                      # tokens harvested by it


def _span(name, on):
    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


class Client:
    def __init__(self, sched, request_cls, *, spans=False):
        self.sched = sched
        self.Request = request_cls
        self.spans = spans
        self.recs: Dict[int, Rec] = {}
        self.live: Dict[int, Rec] = {}
        self.steps: List[Step] = []
        self.window = None

    # ------------------------------------------------------------ client

    def submit(self, req: Req, due: Optional[float]) -> Rec:
        with _span("client.submit", self.spans):
            rs = self.sched.submit(self.Request(
                rid=req.rid, prompt=req.prompt, max_new=req.max_new))
            now = time.perf_counter()
        rec = Rec(req, rs, due, now)
        self.recs[req.rid] = rec
        self.live[req.rid] = rec
        return rec

    def _harvest(self, now: float, counting: bool) -> List[Rec]:
        finished, total = [], 0
        with _span("client.harvest", self.spans):
            for rid, rec in list(self.live.items()):
                n = len(rec.rs.tokens)
                if n > rec.seen:
                    if rec.first is None:
                        rec.first = now
                    rec.last = now
                    if counting:
                        if rec.win_first is None:
                            rec.win_first, rec.win_first_n = now, n - rec.seen
                        rec.in_window += n - rec.seen
                        total += n - rec.seen
                    rec.seen = n
                if rec.rs.terminal:
                    rec.done = now
                    finished.append(rec)
                    del self.live[rid]
        return finished, total

    def step(self, counting: bool):
        t0 = time.perf_counter()
        with _span("sched.step", self.spans):
            self.sched.step()
        t1 = time.perf_counter()
        finished, total = self._harvest(t1, counting)
        busy = sum(1 for r in self.live.values()
                   if r.rs.lane >= 0) + len(finished)
        if counting:
            self.steps.append(Step(t0, t1, busy, total))
        return finished

    # ------------------------------------------------------------ set-up

    def warm_shapes(self, max_prompt: int, chunk: int, vocab: int,
                    first_rid: int) -> None:
        """Serve one request of d chunks for every d the window can
        see, so that each mixed-segment length and the decode
        remainder it leaves are compiled before the window."""
        segment = self.sched.serve.decode_segment
        rng = np.random.default_rng(0)
        depth = min(segment, -(-max_prompt // chunk))
        rid = first_rid
        for d in range(1, depth + 1):
            prompt = rng.integers(0, vocab, size=d * chunk - 1).astype(
                np.int32)
            self.submit(Req(rid, prompt, 1), None)
            rid += 1
            while self.live:
                self.step(counting=False)

    def warm_start(self, reqs: List[Req]) -> None:
        """Submit the generator's warm requests and step until each has
        its first token, i.e. every lane has finished its prefill."""
        recs = [self.submit(r, None) for r in reqs]
        while any(r.first is None and r.done is None for r in recs):
            self.step(counting=False)

    # ------------------------------------------------------------ window

    def run_window(self, loop, seconds: float) -> None:
        t0 = time.perf_counter()
        end = t0 + seconds
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            for req in loop.due(now - t0):
                self.submit(req, t0 + req.due)
            if not self.live:
                nxt = loop.next_due()
                wake = end if nxt is None else min(end, t0 + nxt)
                time.sleep(max(0.0, min(wake - time.perf_counter(), 0.002)))
                continue
            for rec in self.step(counting=True):
                loop.done(rec.req, rec.done - t0)
        self.window = (t0, time.perf_counter())
