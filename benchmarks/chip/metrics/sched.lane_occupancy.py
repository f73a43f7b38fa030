"""Mean share of lanes holding a request per window step (%), read by
the harness between steps."""


def read(run):
    if not run.steps:
        return None
    lanes = run.serve["lanes"]
    return 100.0 * sum(s.busy for s in run.steps) / (lanes * len(run.steps))
