"""The per-layer readers of the program's kernel names
(chipbench.named and the metric files built on it), on a hand-built
trace with named kernel events and the program's serve.* spans: each
value against a hand count, None on a trace without the names, and
the trace reduction the older readers use unmoved by either."""
import chipbench_paths  # noqa: F401  (sets the import path)

import os

import numpy as np
import pytest

from chipbench import flops, named, peaks, spec, work
from chipbench.client import Rec
from chipbench.loops import Req
from chipbench.trace import Trace

MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 300, "gate_hidden": 32}
SERVE = {"budget": 16, "lanes": 4, "prefill_chunk": 8}


def _dec(i, start, named_=True, dur=40):
    # a TPU trace names an op's event by its HLO text, and gives it no
    # detail: the kernel's name is the custom call's (closed_call where
    # the pallas_call has no name=)
    name = "decode_attn" if named_ else "closed_call"
    return (f"%{name}.{i} = (bf16[8,1,16]{{2,1,0}}, f32[8,1,16]) "
            f"custom-call(bf16[8,1,16]{{2,1,0}} %a), "
            f'custom_call_target="tpu_custom_call"', start, dur)


def _chunk(i, start, named_=True, dur=100):
    name = "chunk_attn" if named_ else "closed_call"
    return (f"%{name}.{i} = bf16[8,8,16]{{2,1,0}} custom-call(bf16[8,8,16]"
            f'{{2,1,0}} %a), custom_call_target="tpu_custom_call"', start,
            dur)


def _fusion(i, start, dur):
    return (f"%fusion.{i} = f32[8] fusion(f32[8] %p)", start, dur)


def _chunks(i, start, named_, lanes):
    """The chunk kernel of one layer of the mixed step: one event per
    admitting lane, 100 ns in all."""
    dur = 100 // lanes
    return [_chunk(i + 10 * n, start + n * dur, named_, dur)
            for n in range(lanes)]


def hand_trace(named_=True, lanes=1):
    """Window 0..1000 ns, 2 layers. A decode segment of 2 steps in the
    step 0..300, and in the step 400..800 an admission segment: one
    mixed step in which `lanes` lanes admit, then one decode step (its
    decode kernels 30 ns, no fusion). The other steps run the decode
    kernel (40 ns) and a fusion (5 ns) per layer, the mixed one also
    the chunk kernel (100 ns, split between the admitting lanes) and a
    second fusion (10 ns); a loop (110..205) holds the second decode
    step."""
    ops = [_dec(1, 10, named_), _fusion(1, 50, 5),
           _dec(2, 60, named_), _fusion(2, 100, 5),
           ("%while.9 = (s32[]) while((s32[]) %t)", 110, 95),
           _dec(3, 110, named_), _fusion(3, 150, 5),
           _dec(4, 160, named_), _fusion(4, 200, 5),
           _dec(5, 410, named_), _fusion(5, 450, 5),
           *_chunks(6, 460, named_, lanes), _fusion(6, 560, 10),
           _dec(7, 570, named_), _fusion(7, 610, 5),
           *_chunks(8, 615, named_, lanes), _fusion(8, 715, 10),
           _dec(9, 730, named_, dur=30), _dec(10, 760, named_, dur=30)]
    host = [("bench.window", 0, 1000), ("sched.step", 0, 300),
            ("sched.step", 400, 400), ("client.harvest", 300, 50)]
    # the program's spans: on the clock of the others, nested in steps
    host += [("serve.admit", 0, 2), ("serve.decode", 5, 290),
             ("serve.wait", 20, 270), ("serve.retire", 296, 3),
             ("serve.admit", 400, 3), ("serve.plan", 403, 2),
             ("serve.mixed", 405, 390), ("serve.wait", 410, 380)]
    return Trace({"0": ops}, host)


def _rec(rid, prompt_len, first, seen, in_window):
    return Rec(Req(rid, np.zeros(prompt_len, np.int32), 8), None, None,
               0.0, first=first, seen=seen, in_window=in_window)


PROMPTS = (5, 3)      # prompt lengths of the lanes admitting in the window


def hand_run(trace, lanes=1, counters=True):
    # a request decoding through the window (prompt 3, tokens 3 and 4
    # harvested in it, made from positions 4 and 5) and `lanes` admitted
    # in it in one mixed step (prompts of one chunk, first token only),
    # with the scheduler's counters of that step: C rows per admitting
    # lane
    recs = [_rec(0, 3, -5.0, 4, 2)]
    recs += [_rec(1 + n, PROMPTS[n], 2.0, 1, 1) for n in range(lanes)]
    stats = {"n_mixed_steps": 1, "n_chunk_rows": lanes * 8,
             "n_chunk_rows_valid": sum(PROMPTS[:lanes])} if counters else {}
    return work.Run(cell={}, model=MODEL, serve=SERVE, traffic={},
                    window=(0.0, 10.0), recs=recs, steps=[], peak_bytes=0,
                    setup_s=0.0, peak=peaks.peaks("TPU v5 lite"),
                    trace=trace, stats=stats)


def metric(name):
    return spec.load_module(
        os.path.join(chipbench_paths.BENCH, "metrics", name + ".py"),
        "metric_" + name.replace(".", "_"))


NEW = ("decode_attn_roofline", "chunk_attn_roofline",
       "sched.chunk_row_fill", "model.decode_step_ms",
       "model.mixed_step_ms")


def test_kernel_events_and_step_split():
    t = hand_trace()
    assert [e[1] for e in named.kernel_events(t, "decode_attn")] == [
        10, 60, 110, 160, 410, 570, 730, 760]
    assert [e[1] for e in named.kernel_events(t, "chunk_attn")] == [
        460, 615]
    # [seconds, decode kernel events, chunk kernel events] per kind
    assert named.step_split(hand_run(t)) == {
        "decode": [pytest.approx(300e-9), 4, 0],
        "mixed": [pytest.approx(400e-9), 4, 2]}


@pytest.mark.parametrize("lanes", [1, 2])
def test_readers_against_hand_counts(lanes):
    run = hand_run(hand_trace(lanes=lanes), lanes)
    p = run.peak
    # two layers of decode attention for keys 4 and 5, in 6 x 40 ns and
    # 2 x 30 ns
    ideal = 2 * sum(flops.roofline_seconds(*flops.decode_attn(MODEL, k), p)
                    for k in (4, 5))
    assert metric("decode_attn_roofline").read(run) == pytest.approx(
        100 * ideal / 300e-9)
    # two layers of a 5-row (and a 3-row) chunk over no kept keys, in
    # 2 x 100 ns
    chunk = metric("chunk_attn_roofline")
    ideal = 2 * sum(flops.roofline_seconds(*chunk.needed(MODEL, 0, n), p)
                    for n in PROMPTS[:lanes])
    assert chunk.read(run) == pytest.approx(100 * ideal / 200e-9)
    # 5 (and 3) valid rows of 8 per admitting lane, from the counters
    assert metric("sched.chunk_row_fill").read(run) == pytest.approx(
        100 * sum(PROMPTS[:lanes]) / (8 * lanes))
    # 300 ns for 2 decode steps; the admission segment's 400 ns less its
    # one decode step at 150 ns, for the 1 mixed step the counters hold
    # however many lanes' chunk kernels it ran
    assert metric("model.decode_step_ms").read(run) == pytest.approx(
        150e-6)
    assert metric("model.mixed_step_ms").read(run) == pytest.approx(
        250e-6)


def test_chunk_attn_needed_hand_count():
    m = {"num_heads": 32, "num_kv_heads": 8, "head_dim": 128}
    f, b = metric("chunk_attn_roofline").needed(m, 512, 100)
    # QK^T and PV of 100 queries over 512 kept keys and the causal chunk
    assert f == 4 * 32 * 128 * (100 * 512 + 100 * 101 / 2)
    # K/V slabs and positions once, queries, chunk K/V, bf16 output
    assert b == (2 * 8 * 512 * 128 * 2 + 8 * 512 * 4 + 100 * 32 * 128 * 2
                 + 2 * 100 * 8 * 128 * 2 + 100 * 32 * 128 * 2)


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_without_the_names(name):
    """A program without the names (the trace of a parent commit) or
    the counters, and an untraced run, give no reading rather than a
    wrong one."""
    bare = hand_trace(named_=False)
    assert metric(name).read(hand_run(bare, counters=False)) is None
    assert metric(name).read(hand_run(None, counters=False)) is None


def test_older_reductions_are_unmoved_by_names_and_spans():
    t = hand_trace()
    # ops [10,55) [60,105) [110,205) [410,455) [460,725) [730,790)
    assert t.busy("0") == 555
    assert t.step_seconds() == pytest.approx(700e-9)
    assert t.step_idle_share() == pytest.approx(1 - 555 / 700)
    # idle in steps 10+5+5+95 and 10+5+5+10 ns; in the harvest 50 ns;
    # under no span [205,300) aside, 300..400 and 790..1000: 250 ns
    gaps = dict(t.idle_gaps())
    assert gaps == {"sched.step": pytest.approx(145e-9),
                    "client.harvest": pytest.approx(50e-9),
                    "host.other": pytest.approx(250e-9)}
    assert t.top_ops() == [
        ["decode_attn bf16[8,1,16]", pytest.approx(300e-9)],
        ["chunk_attn bf16[8,8,16]", pytest.approx(200e-9)],
        ["fusion", pytest.approx(50e-9)]]
    bare = hand_trace(named_=False)
    assert bare.busy("0") == t.busy("0")
    assert bare.step_idle_share() == t.step_idle_share()
    assert bare.idle_gaps() == t.idle_gaps()
