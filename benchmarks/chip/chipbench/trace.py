"""The profiler's trace of the window, reduced to a few lists.

`capture` records the window with the Python tracer off. `load` reads
the `.xplane.pb` file with JAX alone and keeps what the metrics need:
the operations of each device (the "XLA Ops" line of every TPU plane)
and the host spans the benchmark put around its own calls. `Trace`
holds that normalized form, which is also what the test fixture
stores, and does the arithmetic on it: busy time as the union of
operation intervals, idle gaps and what the host was doing in them,
and the time of the events of one kernel.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

SPANS = ("bench.window", "sched.step", "client.submit", "client.harvest")
# a device plane's line of operations; the program executions' line
# stands in where a trace has no per-operation line
OPS_LINES = ("XLA Ops", "XLA Modules")
# stats of an operation event that name where it came from, best first
DETAIL_STATS = ("tf_op", "long_name")


@contextlib.contextmanager
def capture(directory: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(directory: str) -> "Trace":
    import jax
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    device, host = {}, []
    for plane in pd.planes:
        m = re.search(r"/device:TPU:(\d+)", plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            name = next((n for n in OPS_LINES if n in lines), None)
            if name is not None:
                device[m.group(1)] = [
                    (ev.name, ev.start_ns, ev.duration_ns, _detail(ev))
                    for ev in lines[name].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.duration_ns)
                         for ev in line.events if ev.name in SPANS]
    if not device:
        raise ValueError(f"no TPU plane with a line of {OPS_LINES} in "
                         f"{paths[-1]}")
    return Trace(device, host)


def _detail(ev) -> str:
    stats = dict(ev.stats)
    for key in DETAIL_STATS:
        if key in stats:
            return str(stats[key])
    return ""


def _merge(intervals: List[Tuple[float, float]]):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a, b) -> float:
    """Total overlap of two merged, sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def op_family(name: str) -> str:
    """An operation's name without its trailing instance number
    ("fusion.123" -> "fusion"). A TPU trace names an operation by its
    HLO text ("%fusion.123 = bf16[...] fusion(...)"): the name before
    " = " is taken, and a custom call (a Pallas kernel) keeps its first
    result's shape, which tells the kernels apart."""
    head, _, rest = name.partition(" = ")
    fam = re.sub(r"[.\-_]\d+$", "", head.lstrip("%").strip())
    if " custom-call(" in rest:
        shape = re.search(r"\w+\[[\d,]*\]", rest)
        fam += f" {shape.group(0)}" if shape else ""
    return fam


# operations that only hold others (a loop, a branch): their time is
# their body's, so the breakdown leaves them out
CONTAINERS = ("while", "conditional", "call")


class Trace:
    """device: {chip: [(op name, start_ns, dur_ns, detail)]}, detail
    being the op's origin in the program (its name scope) where the
    trace gives one; host: [(span name, start_ns, dur_ns)], all on the
    profiler's one clock."""

    def __init__(self, device: Dict[str, list], host: list):
        self.device = {k: [tuple(e) + ("",) * (4 - len(e)) for e in v]
                       for k, v in device.items()}
        self.host = [tuple(e) for e in host]

    # ------------------------------------------------------- (de)serialise

    def to_json(self) -> dict:
        return {"device": self.device, "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(d["device"], d["host"])

    # ------------------------------------------------------------ windows

    def spans(self, name: str):
        return _merge([(s, s + d) for n, s, d in self.host if n == name])

    def window(self) -> Tuple[float, float]:
        """(start_ns, end_ns) of the measured window."""
        w = self.spans("bench.window")
        if not w:
            raise ValueError("the trace holds no bench.window span")
        return w[0][0], w[-1][1]

    def busy(self, chip: str, within=None) -> float:
        """Nanoseconds in which an operation ran on `chip`, inside the
        merged intervals `within` (default: the window)."""
        if within is None:
            within = [list(self.window())]
        ops = _merge([(s, s + d) for _, s, d, _ in self.device[chip]])
        return _overlap(ops, within)

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the chips."""
        return sum(self.busy(c) for c in self.device) / len(self.device) \
            / 1e9

    def window_s(self) -> float:
        s, e = self.window()
        return (e - s) / 1e9

    def step_idle_share(self) -> float:
        """1 - busy / span, over the sched.step spans only."""
        steps = self.spans("sched.step")
        span = sum(e - s for s, e in steps)
        busy = sum(self.busy(c, steps) for c in self.device) / len(
            self.device)
        return 1.0 - busy / span

    def step_seconds(self) -> float:
        return sum(e - s for s, e in self.spans("sched.step")) / 1e9

    # ------------------------------------------------------------ kernels

    def events(self, pattern: str, within=None):
        """(name, start, dur, detail) of chip 0's operations whose name
        or detail matches the regex `pattern`, inside `within`
        (default: the window)."""
        rx = re.compile(pattern)
        lo, hi = self.window() if within is None else within
        chip = sorted(self.device)[0]
        return [e for e in self.device[chip]
                if (rx.search(e[0]) or rx.search(e[3])) and lo <= e[1] < hi]

    # ---------------------------------------------------------- breakdown

    def top_ops(self, n: int = 10):
        """[[operation family, seconds]] of chip 0 in the window, by
        time."""
        lo, hi = self.window()
        chip = sorted(self.device)[0]
        tot = defaultdict(float)
        for name, s, d, _ in self.device[chip]:
            fam = op_family(name)
            if lo <= s < hi and fam not in CONTAINERS:
                tot[fam] += d / 1e9
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10):
        """[[host activity, seconds]]: chip 0's idle time in the
        window, split by the host span that covered it (the benchmark's
        spans do not overlap one another) and "host.other" where none
        did, summed by activity and sorted by time."""
        lo, hi = self.window()
        chip = sorted(self.device)[0]
        ops = _merge([(s, s + d) for _, s, d, _ in self.device[chip]
                      if s + d > lo and s < hi])
        gaps, t = [], lo
        for s, e in ops:
            if s > t:
                gaps.append([t, min(s, hi)])
            t = max(t, e)
        if t < hi:
            gaps.append([t, hi])
        tot = defaultdict(float)
        for name in SPANS[1:]:
            tot[name] = _overlap(gaps, self.spans(name)) / 1e9
        tot["host.other"] = sum(e - s for s, e in gaps) / 1e9 - sum(
            tot.values())
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n] if v > 0]
