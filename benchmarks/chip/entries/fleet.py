"""Entry ``fleet``: ``Deployment.fleet(DistSpec(...))``, single frames.

``DistLauncher`` feeds one thread worker per pipeline stage; worker
``i`` runs stage ``i`` on local device ``i`` and the tensors between
stages travel over the spec's links.  The workers make their weights
from ``DistSpec.seed``, here drawn from ``--seed``; the reference draws
its own from the same key.

Two loops, by the traffic's ``loop``:

``open``
    frames due on the schedule of :func:`chipbench.arrivals.schedule`
    at ``rate_per_s``, whether or not earlier ones are back.  Each is
    timed from its due time to its logits on the host; one that never
    comes back counts as infinitely late.
``closed``
    a new frame whenever fewer than ``DistSpec.max_inflight`` are out.

The launcher resolves results only inside ``DistLauncher._step()``, and
``submit()`` calls it only when the pipe is full, so a client that only
submitted would time completions by its own next call.  This client
therefore calls ``_step()`` itself while it waits for the next due time,
and never lets ``submit()`` block.
"""

from __future__ import annotations

import itertools
import math
import time

import jax
import numpy as np

from chipbench import arrivals, bench

#: after the window, how long frames still out may take to come back
DRAIN_S = 60.0


class Client:
    """Submits frames and timestamps each result as it reaches the host."""

    def __init__(self, launcher, max_inflight: int):
        self.launcher = launcher
        self.max_inflight = max_inflight
        self.submitted = 0
        self.seen = 0
        self.done: dict[int, float] = {}        # fid -> perf_counter

    def collect(self, timeout: float) -> bool:
        """One collect step; False once a worker is dead."""
        with jax.profiler.TraceAnnotation("chipbench.collect"):
            alive = self.launcher._step(timeout=timeout)
        outs = self.launcher.outputs
        new = len(outs) - self.seen
        if new > 0:
            now = time.perf_counter()
            for fid in itertools.islice(reversed(outs), new):
                self.done[fid] = now
            self.seen = len(outs)
        return alive

    @property
    def out(self) -> int:
        return self.submitted - self.seen - len(self.launcher.dropped)

    def submit(self, frame: np.ndarray) -> int:
        with jax.profiler.TraceAnnotation("chipbench.submit"):
            fid = self.launcher.submit(frame)
        self.submitted += 1
        return fid

    def drain(self, deadline: float) -> None:
        while self.out > 0 and time.perf_counter() < deadline:
            if not self.collect(0.05):
                return


def _open_loop(r, client, pool, t) -> tuple[dict[int, float], float]:
    """Submit on schedule; returns each frame's due time and the
    window's start."""
    due = arrivals.schedule(t["rate_per_s"], r.seconds,
                            bench.sub_seed(r.seed, 2))
    due_of: dict[int, float] = {}
    lag = []
    t0 = time.perf_counter()
    alive = True
    for i, d in enumerate(due):
        target = t0 + float(d)
        while alive:
            now = time.perf_counter()
            if now >= target and client.out < client.max_inflight:
                break
            alive = client.collect(min(target - now, 0.05) if now < target
                                   else 0.05)
        if not alive:
            break
        fid = client.submit(pool[i % len(pool)])
        lag.append(time.perf_counter() - target)
        due_of[fid] = target
    r.attempted = len(due)
    if lag:
        r.note(f"generator lag: p50 {np.median(lag) * 1e3:.3f} ms, "
               f"max {max(lag) * 1e3:.3f} ms over {len(lag)} frames")
    # the last frame goes out at its due time, or later where the client
    # lags (as under the profiler); the window is as long as the loop
    r.window_s = max(r.seconds, time.perf_counter() - t0)
    return due_of, t0


def _closed_loop(r, client, pool) -> tuple[dict[int, float], float]:
    due_of: dict[int, float] = {}
    t0 = time.perf_counter()
    end = t0 + r.seconds
    i = 0
    while (now := time.perf_counter()) < end:
        if client.out < client.max_inflight:
            due_of[client.submit(pool[i % len(pool)])] = now
            i += 1
        elif not client.collect(min(0.05, end - now)):
            break
    r.attempted = i
    r.window_s = r.seconds
    return due_of, t0


def start(r: bench.Run):
    """Set-up: deployment, frames, fleet start with its warm-up probe,
    then ``warmup_frames`` frames through the real path.  Returns the
    launcher, the client and the frame pool."""
    import repro
    t = r.cell.traffic
    dep, _ = bench.deploy(r)
    spec = repro.DistSpec(seed=bench.weight_seed(r.seed), **t["dist"])
    pool = bench.frames(r, t["pool_frames"])
    launcher = dep.fleet(spec)
    client = Client(launcher, spec.max_inflight)
    t0 = time.perf_counter()
    try:
        launcher.start()
        r.mark("fleet start")
        for i in range(t["warmup_frames"]):
            while client.out >= client.max_inflight:
                client.collect(0.05)
            client.submit(pool[i % len(pool)])
        client.drain(time.perf_counter() + DRAIN_S)
    except BaseException:
        launcher.shutdown(abort=True)
        raise
    r.warmup_s = time.perf_counter() - t0
    r.mark("warm-up")
    return launcher, client, pool


def measure(r: bench.Run, client: Client, pool) -> tuple[dict, float, float]:
    """The window by the traffic's loop, then the drain.  Returns each
    window frame's due time by fid, the window's start and the end of
    the wait for frames still out."""
    with bench.window(r):
        if r.cell.traffic["loop"] == "open":
            due_of, w0 = _open_loop(r, client, pool, r.cell.traffic)
        else:
            due_of, w0 = _closed_loop(r, client, pool)
    deadline = time.perf_counter() + DRAIN_S
    client.drain(deadline)
    return due_of, w0, deadline


def latencies(client: Client, due_of: dict, deadline: float) -> list[float]:
    """Due time to logits on the host, per frame; a frame that never
    came back is as late as the wait for it."""
    return [client.done.get(f, deadline) - d for f, d in due_of.items()]


def run(r: bench.Run) -> None:
    launcher, client, pool = start(r)
    first = client.submitted
    try:
        due_of, w0, deadline = measure(r, client, pool)
        report = launcher.shutdown()
    except BaseException:
        launcher.shutdown(abort=True)
        raise
    r.dist_report = report
    r.failed = len([f for f, _ in report.dropped if f >= first])
    end = w0 + r.window_s
    r.frames_in_window = sum(1 for f in due_of
                             if client.done.get(f, math.inf) <= end)
    r.missing = r.attempted - sum(1 for f in due_of if f in report.outputs)
    if r.cell.traffic["loop"] == "open":
        r.latencies_s = latencies(client, due_of, deadline)
    for fid in due_of:
        if fid in report.outputs:
            (logits,) = report.outputs[fid].values()
            r.outputs[fid] = np.asarray(logits).reshape(-1)
            r.frame_of[fid] = (fid - first) % len(pool)
    r.note(f"fleet: {len(due_of)} frames in the window, "
           f"{r.frames_in_window} back within it, {r.missing} missing, "
           f"{r.failed} dropped")
