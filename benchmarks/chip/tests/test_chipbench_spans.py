"""The readers of the program's spans and compile counter, on tiny CPU
runs of both cells: each reads a value, and none where the tracer's
rings evicted spans from inside the window."""

from __future__ import annotations

import time

import pytest
from chipbench_tiny import tiny_root

from chipbench.bench import Bench, Run

SEED = 2 ** 33 + 5

READERS = {
    "vgg16-224.offline32": ["run_stack_ms.fps", "run_stages_ms.fps",
                            "run_split_ms.fps", "compile_s"],
    "resnet34-224.stream": ["link_wait_ms.p50", "link_codec_ms.p50",
                            "worker_h2d_ms.p50", "worker_d2h_ms.p50",
                            "compile_s"],
}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One finished run per cell, with the program's state kept."""
    bench = Bench(tiny_root(tmp_path_factory.mktemp("chipbench")))
    out = {}
    for cell in READERS:
        run = Run(cell=bench.cell(cell), seed=SEED, seconds=0.5,
                  traced=False, t_start=time.perf_counter())
        run.cell.entry.run(run)
        out[cell] = run
    return bench, out


def _reads(bench, run, cell):
    return {m: bench.module("metrics", m).read(run) for m in READERS[cell]}


@pytest.mark.parametrize("cell", sorted(READERS))
def test_each_reader_reads_a_value(runs, cell):
    bench, by_cell = runs
    got = _reads(bench, by_cell[cell], cell)
    assert all(v is not None and v >= 0.0 for v in got.values()), got
    assert got["compile_s"] > 0.0
    ms = [v for m, v in got.items() if m != "compile_s"]
    assert any(v > 0.0 for v in ms), got
    # a breakdown inside one call or one frame, never longer than it
    assert all(v < 1e3 * by_cell[cell].window_s for v in ms)
    assert {m["name"] for m in bench.metrics(cell, True)} >= set(got)


@pytest.mark.parametrize("cell", sorted(READERS))
def test_readers_read_nothing_after_evictions(runs, cell):
    bench, by_cell = runs
    run = by_cell[cell]
    tr = run.dep.tracer
    t_in = run.t_start + run.setup_s - tr.epoch + 1e-6
    for track, ring in list(tr._rings.items()):
        for _ in range(ring.maxlen):        # push every span out
            tr.emit("frame", t_in, 0.0, track=track, fid=-9)
    assert tr.evicted_until > run.t_start + run.setup_s - tr.epoch
    got = _reads(bench, run, cell)
    assert all(v is None for m, v in got.items() if m != "compile_s"), got
