"""The program's kernel names in the trace, as the per-layer readers
use them (docs/serving.md §Tracing).

A Pallas kernel's custom call bears its `pallas_call` name
(`decode_attn`, `chunk_attn`), and a TPU trace names an operation's
event by its HLO text, so the kernel's events start with that name. A
trace of a program without these names holds no such event: every
reader built on this module then returns None. (The program's named
scopes do not reach the events that `chipbench.trace.load` keeps: on
the chip an event's detail is empty.)
"""
from __future__ import annotations

import re
from bisect import bisect_left
from typing import Dict, List, Optional


def kernel(name: str) -> str:
    """Regex of a kernel's events, for `Trace.events`: the event's name
    (HLO text "%decode_attn.7 = ...", or the bare instruction name)
    starts with the kernel's."""
    return rf"^%?{re.escape(name)}(\.\d+)?( |$)"


def kernel_events(trace, name: str) -> List[tuple]:
    """Chip 0's events of the kernel `name` in the window."""
    return trace.events(kernel(name))


def step_split(run) -> Optional[Dict[str, list]]:
    """The window's sched.step spans in two kinds: "mixed", in which the
    chunk kernel ran (an admission segment: its mixed steps and the
    pure-decode remainder), and "decode", in which it did not. For each
    kind: [seconds, decode kernel events, chunk kernel events]. Every
    kernel a step runs lies inside its span: the scheduler waits for
    each dispatch's outputs before the step returns. Each model step,
    decode or mixed, runs the decode kernel once per layer, so the
    decode events over the layers count the scheduler's n_decode_steps
    + n_mixed_steps (without speculation: a verify round runs it once
    per candidate position)."""
    if run.trace is None:
        return None
    t = run.trace
    dec = sorted(e[1] for e in kernel_events(t, "decode_attn"))
    if not dec:
        return None
    chunk = sorted(e[1] for e in kernel_events(t, "chunk_attn"))
    lo, hi = t.window()
    out = {"decode": [0.0, 0, 0], "mixed": [0.0, 0, 0]}
    for s, e in t.spans("sched.step"):
        if not lo <= s < hi:
            continue
        nd = bisect_left(dec, e) - bisect_left(dec, s)
        nc = bisect_left(chunk, e) - bisect_left(chunk, s)
        kind = out["mixed" if nc else "decode"]
        kind[0] += (e - s) / 1e9
        kind[1] += nd
        kind[2] += nc
    return out
