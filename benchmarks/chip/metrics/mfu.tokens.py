"""Model FLOPs of every token processed in the window (decode and
prefill; 2 per weight plus attention over at most M retained keys)
over the seconds inside sched.step spans times the bf16 peak (%)."""
from chipbench.work import mfu


def read(run):
    return mfu(run)
