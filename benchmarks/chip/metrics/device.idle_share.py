"""Share of the time inside sched.step spans in which no operation ran
on the device (%): 1 - busy / span, from the trace. Taken inside the
steps so that an open loop's waits for arrivals do not count."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.step_idle_share()
