"""Share (%) of the decode-attention kernel's roofline: the least time
the chip could take for the decode attention the window's tokens
needed (every token harvested in the window that a decode step made,
at every layer: its retained keys read once per kv head,
chipbench.flops.decode_attn) over the summed time of the decode_attn
kernel's events in the trace."""
from collections import Counter

from chipbench import flops, named, work


def read(run):
    if run.trace is None:
        return None
    L = run.model["num_layers"]
    needed = [(*flops.decode_attn(run.model, keys), n * L)
              for keys, n in Counter(work.decode_keys(run)).items()]
    return work.roofline(run, named.kernel("decode_attn"), needed)
