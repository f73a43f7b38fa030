"""What one run did, as the metric readers see it, and the client-side
samples and work counts that several readers share."""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from chipbench import flops


@dataclasses.dataclass
class Run:
    cell: dict                  # the workload entry of BENCHMARK.json
    model: dict                 # the configuration file's model block
    serve: dict                 # the serving settings as run (Cell.serve)
    traffic: dict               # the traffic file
    window: tuple               # (start, end) on the host clock
    recs: list                  # client.Rec of every request
    steps: list                 # client.Step of every window step
    peak_bytes: int             # peak bytes in use on the fullest chip
    setup_s: float
    peak: dict                  # the chip's published peaks
    trace: Optional[object] = None   # trace.Trace of a --trace 1 run
    # the window's deltas of the scheduler's counters (Scheduler.stats())
    stats: dict = dataclasses.field(default_factory=dict)
    reference: Optional[object] = None   # the configuration's reference

    @property
    def seconds(self) -> float:
        return self.window[1] - self.window[0]


def percentile(vals, q: float) -> Optional[float]:
    vals = np.asarray(list(vals), np.float64)
    return float(np.percentile(vals, q)) if vals.size else None


def due_in_window(run: Run) -> List:
    t0, t1 = run.window
    return [r for r in run.recs if r.due is not None and r.due <= t1]


def ttft_samples(run: Run) -> List[float]:
    """Due time -> first token harvested, for every request due in the
    window; one still without a token counts at its time so far, a
    failed one as infinite."""
    t1 = run.window[1]
    out = []
    for r in due_in_window(run):
        if r.rs.terminal and not r.rs.done:
            out.append(float("inf"))
        elif r.first is not None and r.first <= t1:
            out.append(r.first - r.due)
        else:
            out.append(t1 - r.due)
    return out


def tpot_samples(run: Run) -> List[float]:
    """Seconds per output token of every request that received tokens
    in two or more harvests inside the window, counted over the window
    alone: (its last harvest - its first harvest in the window) / the
    tokens harvested after that first one. Nothing from set-up or the
    warm start enters."""
    return [(r.last - r.win_first) / (r.in_window - r.win_first_n)
            for r in run.recs
            if r.win_first is not None and r.in_window > r.win_first_n]


def decode_keys(run: Run):
    """For every token harvested in the window that a decode step made
    (all but a request's first, which its prefill made), the number of
    retained keys that step attended to besides its own: token i of a
    request with a P-token prompt is made from position P + i - 1."""
    M = run.serve["budget"]
    keys = []
    for r in run.recs:
        P = len(r.req.prompt)
        for i in range(max(1, r.seen - r.in_window), r.seen):
            keys.append(min(P + i - 1, M))
    return keys


def prefill_chunks(run: Run):
    """(kept keys, valid rows) of every prompt chunk of the requests
    whose first token was harvested in the window."""
    t0, t1 = run.window
    M, C = run.serve["budget"], run.serve["prefill_chunk"]
    out = []
    for r in run.recs:
        if r.first is None or not t0 <= r.first <= t1:
            continue
        P = len(r.req.prompt)
        for c in range(0, P, C):
            out.append((min(c, M), min(C, P - c)))
    return out


def model_flops(run: Run) -> float:
    """Model FLOPs of all the tokens processed in the window."""
    m, ref = run.model, run.reference
    total = sum(flops.decode_token_flops(m, k + 1, ref)
                for k in decode_keys(run))
    chunks = prefill_chunks(run)
    total += sum(flops.prefill_chunk_flops(m, kept, n, ref)
                 for kept, n in chunks)
    n_prompts = sum(1 for kept, _ in chunks if kept == 0)
    return total + n_prompts * flops.head_flops(m)


def mfu(run: Run) -> Optional[float]:
    """Model FLOPs over (seconds inside sched.step spans x bf16 peak),
    in %."""
    if run.trace is None:
        return None
    return 100.0 * model_flops(run) / (run.trace.step_seconds()
                                       * run.peak["bf16_flops"])


def roofline(run: Run, pattern: str, needed) -> Optional[float]:
    """Share (%) of the roofline reached by a kernel: the least time its
    needed work could take at the chip's peaks, over the summed time of
    its events (regex `pattern`) in the trace. `needed` lists
    (flops, bytes, times) of the lane-layer calls of the window."""
    if run.trace is None:
        return None
    events = run.trace.events(pattern)
    spent = sum(e[2] for e in events) / 1e9
    if not events or spent <= 0 or not needed:
        return None
    ideal = sum(n * flops.roofline_seconds(f, b, run.peak)
                for f, b, n in needed)
    return 100.0 * ideal / spent
