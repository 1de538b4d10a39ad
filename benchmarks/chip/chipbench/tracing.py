"""Device trace of a traced run, reduced to the numbers the per-layer
metrics read.

The client wraps its measured window in a host annotation named
:data:`WINDOW` and its own host steps in annotations that start with
``chipbench.``.  From JAX's profiler trace this module takes, per chip
used, the device's XLA op events (the ``XLA Ops`` line of each
``/device:TPU:<i>`` plane) clipped to the window:

* busy: the union of the op intervals; idle share = 1 - busy / window;
* op totals, and the longest idle gaps, each labelled by the host event
  that overlaps it most.

Host events come from every host thread but the TPU runtime's own task
threads (``pjrt-*``), which hold millions of events and say nothing of
what the client or the program was doing.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

WINDOW = "chipbench.window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
#: characters of an op's name kept in the breakdown
OP_CHARS = 120


def short_op(name: str) -> str:
    """An op's name for the breakdown: TPU ``XLA Ops`` are named by their
    HLO text; drop the layouts and keep the head (name, shape, inputs)."""
    return _LAYOUT.sub("", name)[:OP_CHARS]


@dataclass
class Trace:
    window_s: float
    busy_s: list[float]                 # per chip, inside the window
    ops: dict[str, float] = field(default_factory=dict)   # all chips
    gaps: list[tuple[str, float]] = field(default_factory=list)

    @property
    def chips(self) -> int:
        return len(self.busy_s)

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_mean_s / self.window_s

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[short_op(k), v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce_events(device: dict[int, list[tuple[str, float, float]]],
                  host: list[tuple[str, float, float]],
                  window: tuple[float, float], n_gaps: int = 10) -> Trace:
    """``device[chip]``: ``(name, start, end)`` op events;
    ``host``: ``(name, start, end)`` events; all in seconds on one clock.
    Only the parts inside ``window`` count."""
    t0, t1 = window
    busy, ops, gaps = [], {}, []
    others = [(nm, a, b) for nm, a, b in host
              if nm != WINDOW and b - a < 0.5 * (t1 - t0)]
    for chip in sorted(device):
        clipped = [(nm, max(a, t0), min(b, t1))
                   for nm, a, b in device[chip] if b > t0 and a < t1]
        spans = _union([(a, b) for _, a, b in clipped])
        busy.append(sum(b - a for a, b in spans))
        for nm, a, b in clipped:
            ops[nm] = ops.get(nm, 0.0) + (b - a)
        edges = [t0] + [x for ab in spans for x in ab] + [t1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((chip, a, b))
    gaps.sort(key=lambda g: -(g[2] - g[1]))
    labelled = []
    for chip, a, b in gaps[:n_gaps]:
        best, label = 0.0, "no host event"
        for nm, ha, hb in others:
            ov = min(b, hb) - max(a, ha)
            if ov > best:
                best, label = ov, nm
        labelled.append((f"tpu{chip}: {label}", b - a))
    return Trace(window_s=t1 - t0, busy_s=busy, ops=ops, gaps=labelled)


def from_profile(pd, chips: int) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Trace` over
    the ``chips`` lowest-numbered TPU planes."""
    device: dict[int, list] = {}
    host: list[tuple[str, float, float]] = []
    window = None
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chip >= chips:
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                device[chip] = [(ev.name, ev.start_ns * 1e-9,
                                 ev.end_ns * 1e-9) for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name.startswith("pjrt-"):
                    continue
                for ev in line.events:
                    a, b = ev.start_ns * 1e-9, ev.end_ns * 1e-9
                    if ev.name == WINDOW:
                        window = (a, b)
                    host.append((ev.name, a, b))
    if window is None:
        raise RuntimeError(f"trace has no {WINDOW!r} host event")
    if sorted(device) != list(range(chips)):
        raise RuntimeError(f"trace has XLA ops for TPUs {sorted(device)}, "
                           f"want 0..{chips - 1}")
    return reduce_events(device, host, window)


class Profile:
    """``with Profile(chips) as p: ...`` traces the block into a
    temporary directory; ``p.trace`` is then the reduced :class:`Trace`
    and the directory is gone."""

    def __init__(self, chips: int):
        self.chips = chips
        self.trace: Trace | None = None

    def __enter__(self):
        import jax
        self._dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # host annotations and TraceMe only
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        from jax.profiler import ProfileData
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                (path,) = glob.glob(os.path.join(
                    self._dir, "**", "*.xplane.pb"), recursive=True)
                t1 = time.perf_counter()
                pd = ProfileData.from_file(path)
                t2 = time.perf_counter()
                self.trace = from_profile(pd, self.chips)
                print(f"trace: stop {t1 - t0:.1f} s, load {t2 - t1:.1f} s "
                      f"({os.path.getsize(path) / 2**20:.0f} MiB), reduce "
                      f"{time.perf_counter() - t2:.1f} s", file=sys.stderr,
                      flush=True)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False
