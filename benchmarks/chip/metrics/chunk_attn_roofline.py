"""Share (%) of the chunk-attention kernel's roofline: the least time
the chip could take for the chunk attention the window's prompts
needed (every valid row of every prompt chunk whose request got its
first token in the window, at every layer) over the summed time of
the chunk_attn kernel's events in the trace."""
from collections import Counter

from chipbench import flops, named, work


def needed(m: dict, kept: int, n: int):
    """(flops, bytes) of the chunk attention of one lane at one layer:
    n valid chunk rows, each query attending to `kept` retained keys
    and causally to the chunk. Bytes: the retained K/V slabs and their
    positions read once per kv head, the queries, the chunk's keys and
    values, and the output written once, in bf16 as the kernel's."""
    Hq, Hk, D = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    pairs = n * kept + n * (n + 1) / 2.0
    f = flops.attn_flops(m, 1.0) * pairs
    b = (2 * Hk * kept * D * flops.BF16      # K and V slabs, read once
         + Hk * kept * flops.F32             # their positions
         + n * Hq * D * flops.BF16           # the queries
         + 2 * n * Hk * D * flops.BF16       # the chunk's keys, values
         + n * Hq * D * flops.BF16)          # the output
    return f, b


def read(run):
    if run.trace is None:
        return None
    L = run.model["num_layers"]
    need = [(*needed(run.model, kept, rows), n * L)
            for (kept, rows), n in Counter(work.prefill_chunks(run)).items()]
    return work.roofline(run, named.kernel("chunk_attn"), need)
