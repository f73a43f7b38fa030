"""95th percentile of time per output token (ms), over every request
that received tokens in two or more harvests inside the window:
(its last harvest - its first harvest in the window) / the tokens
harvested after that first one. Set-up and the warm start, before the
window, do not enter."""
from chipbench.work import percentile, tpot_samples


def read(run):
    p = percentile(tpot_samples(run), 95)
    return None if p is None else 1000.0 * p
