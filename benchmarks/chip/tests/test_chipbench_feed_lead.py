"""The reader of ``run_feed_lead_ms.fps`` on spans laid out by hand: a
program that stacks each call's frames once, and one that feeds them in
chunks; calls outside the window and spans of other tracks left out."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest
from chipbench_tiny import ROOT

from chipbench.bench import Bench
from repro.obs.trace import Tracer

MS = 1e-3
SETUP, WINDOW = 1.0, 0.2


def _run(tr: Tracer):
    return NS(dep=NS(tracer=tr), t_start=tr.epoch, setup_s=SETUP,
              window_s=WINDOW)


def _read(run):
    return Bench(ROOT).module("metrics", "run_feed_lead_ms.fps").read(run)


def _calls(tr: Tracer, chunks: int, first_stack_ms: list[float]) -> None:
    """A call every 20 ms from before the window to past it; call ``i``
    starts with ``first_stack_ms[i % len]`` of stacking, then
    ``chunks - 1`` more stacks, each after a 1 ms stage dispatch."""
    for i in range(-3, 14):
        ts = SETUP + i * 20 * MS
        tr.emit("run", ts, 19 * MS, call=i)
        t = ts + 0.2 * MS
        for k in range(chunks):
            dur = (first_stack_ms[i % len(first_stack_ms)] if k == 0
                   else 2.0) * MS
            tr.emit("run.stack", t, dur, call=i, chunk=k)
            tr.emit("stage", t + dur, 1 * MS, stage="stage0")
            t += dur + 1 * MS
        tr.emit("run.split", t, 1 * MS, call=i)
        # another deployment's stack on its own track, earlier: not read
        tr.emit("run.stack", ts, 0.1 * MS, track="other", chunk=0)


@pytest.mark.parametrize("chunks, first, want", [
    (1, [6.0, 6.4, 6.2], 6.2 + 0.2),      # one stack per call
    (4, [1.5, 1.7, 1.6], 1.6 + 0.2),      # the first of four chunks
])
def test_reads_the_first_stack_of_each_call(chunks, first, want):
    tr = Tracer(epoch=0.0)
    _calls(tr, chunks, first)
    assert _read(_run(tr)) == pytest.approx(want)


def test_reads_nothing_without_stacks_or_after_evictions():
    tr = Tracer(epoch=0.0)
    tr.emit("run", SETUP + 0.01, 19 * MS, call=1)
    assert _read(_run(tr)) is None
    tr = Tracer(epoch=0.0)
    _calls(tr, 2, [1.0])
    tr.note_evicted(1, SETUP + 0.05)
    assert _read(_run(tr)) is None
