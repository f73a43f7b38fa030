"""Share (%) of the prompt rows the chunk sub-step ran that held prompt
tokens, from the scheduler's counters over the window:
n_chunk_rows_valid / n_chunk_rows. The program counts the rows it
really ran: C per admitting lane and mixed step on the compacted path,
all lanes x C where a mesh shards the lanes."""


def read(run):
    rows = run.stats.get("n_chunk_rows", 0)
    if not rows:
        return None
    return 100.0 * run.stats["n_chunk_rows_valid"] / rows
