"""Trace reduction on a small recorded trace: busy union, idle share,
the labelled idle gaps and the op names of the breakdown."""

from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest
from chipbench_tiny import BASE  # noqa: F401  (puts chipbench on the path)

from chipbench import tracing

RECORDED = Path(__file__).with_name("trace_small.json")


def test_busy_union_idle_share_and_gaps():
    ms = 1e-3
    device = {0: [("convolution.3", 0 * ms, 4 * ms),
                  ("fusion.7", 3 * ms, 6 * ms),
                  ("copy.1", 8 * ms, 9 * ms),
                  ("convert.2", 12 * ms, 13 * ms)],
              1: [("_conv2d_kernel", 2 * ms, 7 * ms)]}
    host = [(tracing.WINDOW, 1 * ms, 11 * ms),
            ("chipbench.fetch", 6 * ms, 8.5 * ms),
            ("chipbench.call", 8.6 * ms, 10.6 * ms)]
    t = tracing.reduce_events(device, host, (1 * ms, 11 * ms))
    assert t.window_s == pytest.approx(10 * ms)
    # chip 0: [1,6] + [8,9] inside the window; chip 1: [2,7]
    assert t.busy_s == pytest.approx([6 * ms, 5 * ms])
    assert t.idle_share == pytest.approx(1 - 5.5 / 10)
    assert "convert.2" not in t.ops         # outside the window
    label, gap = t.gaps[0]
    assert gap == pytest.approx(4 * ms)     # chip 1: [7, 11]
    assert label == "tpu1: chipbench.call"
    assert t.breakdown()["device_ops"][0][0] == "_conv2d_kernel"


@pytest.mark.parametrize("name,want", [
    ("%fusion.13 = f32[112,8,16,64]{3,1,2,0:T(8,128)S(1)} fusion("
     "bf16[224,1,7,6,3]{2,4,3,0,1:T(4,128)(2,1)S(1)} %slice.68)",
     "%fusion.13 = f32[112,8,16,64] fusion(bf16[224,1,7,6,3] %slice.68)"),
    ("%copy-done.26 = f32[512]{0:T(512)S(1)} copy-done((f32[512]{0:T(512)"
     "S(1)}, u32[]{:S(2)}) %copy-start.26)",
     "%copy-done.26 = f32[512] copy-done((f32[512], u32[]) %copy-start.26)"),
    ("convolution.12", "convolution.12"),
    ("x" * 300, "x" * tracing.OP_CHARS)])
def test_breakdown_op_names_drop_layouts(name, want):
    assert tracing.short_op(name) == want


def test_recorded_trace_reduces_to_sane_numbers():
    rec = json.loads(RECORDED.read_text())
    device = {0: [(n, a * 1e-9, b * 1e-9) for n, _, a, b in rec["device"]]}
    host = [(n, a * 1e-9, b * 1e-9) for n, a, b in rec["host"]]
    (w,) = [(a, b) for n, a, b in host if n == tracing.WINDOW]
    t = tracing.reduce_events(device, host, w)
    assert 0 < t.busy_s[0] <= t.window_s
    assert 0 <= t.idle_share < 1
    assert t.busy_s[0] == pytest.approx(rec["expect"]["busy_s"], rel=1e-6)


def _ev(name, a_ms, b_ms):
    return NS(name=name, start_ns=a_ms * 1e6, end_ns=b_ms * 1e6)


def test_profile_planes_skip_runtime_threads_and_later_chips():
    tpu = [NS(name="/device:TPU:%d" % i, lines=[NS(name="XLA Ops", events=[
        _ev("fusion.1", 2, 4)])]) for i in range(2)]
    host = NS(name="/host:CPU", lines=[
        NS(name="main/1", events=[_ev(tracing.WINDOW, 0, 10),
                                  _ev("chipbench.fetch", 5, 8)]),
        NS(name="pjrt-tpu-tasks/2", events=[_ev("TpuExecute", 4, 8.9)])])
    t = tracing.from_profile(NS(planes=tpu + [host]), chips=1)
    assert t.chips == 1 and t.busy_s == pytest.approx([2e-3])
    assert t.gaps[0] == ("tpu0: chipbench.fetch", pytest.approx(6e-3))
    with pytest.raises(RuntimeError, match="TPUs"):
        tracing.from_profile(NS(planes=[host]), chips=1)
