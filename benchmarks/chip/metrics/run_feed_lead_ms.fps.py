"""Median over the window's ``Deployment.run`` calls of the time from the
start of the call's ``run`` span to the end of its first ``run.stack``
span: the host's time in the call before the device has any work
(``chipbench.spans``).  Where a call stacks its frames once this is
that one stack; where it feeds them in chunks, the first chunk's."""

from chipbench import spans


def read(run):
    w = spans.window(run)
    if w is None:
        return None
    got, t0, t1 = w
    calls = [s for s in got if s.name == "run" and t0 <= s.ts < t1]
    stacks = [s for s in got if s.name == "run.stack"]
    leads = []
    for c in calls:
        mine = [s for s in stacks if s.track == c.track
                and c.ts <= s.ts and s.end <= c.end]
        if mine:
            leads.append(min(mine, key=lambda s: s.ts).end - c.ts)
    return spans.median_ms(leads)
