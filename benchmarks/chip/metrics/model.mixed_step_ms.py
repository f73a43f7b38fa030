"""Milliseconds per mixed (prefill + decode) step, as the host sees
it: the seconds of the window's admission segments (sched.step spans
in which the chunk kernel ran) less their pure-decode steps at the
rate of model.decode_step_ms, over the mixed steps the scheduler
counted in the window (n_mixed_steps: one per step of a mixed
dispatch, however many lanes admit in it). Each mixed step also runs
the decode kernel once per layer, so the spans' decode steps are their
decode kernel events / layers less the mixed steps."""
from chipbench import named


def read(run):
    split = named.step_split(run)
    mixed_steps = run.stats.get("n_mixed_steps", 0)
    if split is None or not mixed_steps:
        return None
    L = run.model["num_layers"]
    dec_s, dec_events, _ = split["decode"]
    mix_s, mix_dec_events, chunk_events = split["mixed"]
    if not (dec_events and chunk_events):
        return None
    per_decode = dec_s / (dec_events / L)
    decode_steps = mix_dec_events / L - mixed_steps
    return 1e3 * (mix_s - decode_steps * per_decode) / mixed_steps
