"""The program's own spans of a run, for the per-layer readers that read
them.

The spans are the ones ``run.dep.tracer`` holds: the deployment's
tracer, where ``Deployment.run`` records its calls and where the
fleet's launcher records its frames and merges its workers' spans at
shutdown, all on the program's ``perf_counter`` timeline (seconds since
the tracer's ``epoch``).  The window is
``[run.t_start + run.setup_s, + run.window_s]`` on that clock.

A reader gets ``None`` where the program records no such span (a
program older than its spans), and where the tracer's rings evicted
spans from inside the window, which would leave it incomplete.
"""

from __future__ import annotations

import math

from .stats import quantile


def window(run):
    """``(spans, t0, t1)``: the tracer's spans and the window on its
    timeline; ``None`` where there is nothing sound to read."""
    tr = getattr(run.dep, "tracer", None)
    epoch = getattr(tr, "epoch", None)
    if epoch is None or math.isnan(run.setup_s) or math.isnan(run.window_s):
        return None
    t0 = run.t_start + run.setup_s - epoch
    if getattr(tr, "evicted_until", -math.inf) > t0:
        return None
    return tr.spans, t0, t0 + run.window_s


def per_call(run, name: str) -> list[float] | None:
    """Seconds of the spans named ``name`` inside each ``Deployment.run``
    call (a ``run`` span) that starts in the window, summed per call."""
    w = window(run)
    if w is None:
        return None
    spans, t0, t1 = w
    calls = [s for s in spans if s.name == "run" and t0 <= s.ts < t1]
    parts = [s for s in spans if s.name == name]
    out = [sum(p.dur for p in parts if p.track == c.track
               and c.ts <= p.ts and p.end <= c.end) for c in calls]
    return out if calls and parts else None


def per_frame(run, names: set[str], track: str | None = None
              ) -> list[float] | None:
    """Seconds of the spans named in ``names`` (on ``track``, or any),
    summed per frame (``fid``) over the frames whose ``frame`` span
    starts in the window; frames without such spans are left out."""
    w = window(run)
    if w is None:
        return None
    spans, t0, t1 = w
    fids = {s.attr("fid") for s in spans
            if s.name == "frame" and t0 <= s.ts < t1}
    sums: dict[int, float] = {}
    for s in spans:
        if s.name in names and (track is None or s.track == track):
            fid = s.attr("fid")
            if fid in fids:
                sums[fid] = sums.get(fid, 0.0) + s.dur
    return list(sums.values()) or None


def median_ms(values: list[float] | None) -> float | None:
    """Nearest-rank median of seconds, in milliseconds."""
    return None if not values else quantile(values, 50) * 1e3
