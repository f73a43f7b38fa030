"""Share (%) of the prompt rows the chunk sub-step ran that held prompt
tokens: the valid rows of every prompt chunk whose request got its
first token in the window, over lanes x prefill_chunk rows per mixed
step the device ran (one chunk_attn kernel event per layer). The
scheduler counts the same two numbers as n_chunk_rows_valid and
n_chunk_rows."""
from chipbench import named, work


def read(run):
    if run.trace is None:
        return None
    events = named.kernel_events(run.trace, "chunk_attn")
    if not events:
        return None
    mixed_steps = len(events) / run.model["num_layers"]
    rows = mixed_steps * run.serve["lanes"] * run.serve["prefill_chunk"]
    return 100.0 * sum(n for _, n in work.prefill_chunks(run)) / rows
