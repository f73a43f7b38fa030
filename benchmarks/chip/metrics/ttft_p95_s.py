"""95th percentile of time to first token (s), over every request due
in the window: from when it was due to the harvest of its first token
on the host; one with no token by the window's end counts at its time
so far, a failed one as infinite."""
from chipbench.work import percentile, ttft_samples


def read(run):
    return percentile(ttft_samples(run), 95)
