"""Trace reduction: busy time as a union of operation intervals, idle
share inside the steps, idle gaps put down to host spans, and a
kernel's events, on a hand-built trace."""
import chipbench_paths  # noqa: F401  (sets the import path)

import json

import pytest

from chipbench.trace import Trace, op_family

def hand_trace():
    # window 0..100 ns; two steps 10..50 and 60..100; ops overlap
    device = {"0": [("fusion.1", 12, 10), ("fusion.2", 15, 10),
                    ("decode_kernel.7", 30, 15), ("fusion.3", 62, 30),
                    ("copy.4", 105, 5)]}
    host = [("bench.window", 0, 100), ("sched.step", 10, 40),
            ("sched.step", 60, 32), ("client.harvest", 92, 8)]
    return Trace(device, host)


def test_busy_is_a_union_inside_the_window():
    t = hand_trace()
    # [12,25) + [30,45) + [62,92): 13 + 15 + 30 ns; the op at 105 is out
    assert t.busy("0") == 58
    assert t.busy_s() == pytest.approx(58e-9)
    assert t.window_s() == pytest.approx(100e-9)


def test_idle_share_counts_only_the_steps():
    t = hand_trace()
    assert t.step_seconds() == pytest.approx(72e-9)
    assert t.step_idle_share() == pytest.approx(1 - 58 / 72)


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    gaps = dict(hand_trace().idle_gaps())
    # gaps [0,12) [25,30) [45,62) [92,100): inside steps 2+5+5+2 ns,
    # in the harvest 8 ns, under no span 10+10 ns
    assert gaps == {"sched.step": pytest.approx(14e-9),
                    "client.harvest": pytest.approx(8e-9),
                    "host.other": pytest.approx(20e-9)}


def test_kernel_events_and_op_families():
    t = hand_trace()
    assert [e[0] for e in t.events(r"decode_kernel")] == ["decode_kernel.7"]
    assert op_family("fusion.123") == "fusion"
    # a TPU trace names an op by its HLO text; kernels keep their shape
    assert op_family("%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %p)") == \
        "fusion"
    assert op_family('%closed_call.43 = (bf16[1024,1,128]{2,1,0}, f32[4]) '
                     'custom-call(bf16[1024,1,128]{2,1,0} %a), '
                     'custom_call_target="tpu_custom_call"') == \
        "closed_call bf16[1024,1,128]"
    assert t.top_ops()[0] == ["fusion", pytest.approx(50e-9)]


def test_round_trip():
    t = hand_trace()
    u = Trace.from_json(json.loads(json.dumps(t.to_json())))
    assert u.busy("0") == t.busy("0")
    assert u.idle_gaps() == t.idle_gaps()
