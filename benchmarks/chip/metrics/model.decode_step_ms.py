"""Milliseconds per decode step, as the host sees it: the seconds of
the window's sched.step spans in which the chunk kernel did not run
(pure-decode segments), over the decode steps the device ran in them
(one decode_attn kernel event per layer and step; a bucket's padded
steps count, as the scheduler's n_decode_steps counts them)."""
from chipbench import named


def read(run):
    split = named.step_split(run)
    if split is None:
        return None
    seconds, dec_events, _ = split["decode"]
    if not dec_events:
        return None
    return 1e3 * seconds / (dec_events / run.model["num_layers"])
