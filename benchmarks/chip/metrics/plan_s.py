"""Planner wall time: the summed ``plan`` spans (host clock) that
``repro.compile`` records on the deployment's tracer."""


def read(run):
    if run.dep is None:
        return None
    spans = run.dep.tracer.by_name("plan")
    return sum(s.dur for s in spans) if spans else None
