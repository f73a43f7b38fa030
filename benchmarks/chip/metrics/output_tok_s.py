"""Tokens harvested on the host in the window over the window's
seconds."""


def read(run):
    return sum(r.in_window for r in run.recs) / run.seconds
