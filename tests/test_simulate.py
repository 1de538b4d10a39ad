"""Pipeline simulator invariants (paper Eq. 12 quantities), property-
tested over random stage-time configurations."""

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core import make_pi_cluster, plan, simulate
from repro.core.cost import SegmentCost, StageCost, Device
from repro.core.pipeline_dp import PipelinePlan, StagePlan
from repro.models.cnn import zoo


def _plan_from_times(times):
    stages = []
    for i, t in enumerate(times):
        dev = Device(f"d{i}", 1e9)
        seg = SegmentCost(frozenset({f"n{i}"}), [t * 1e9], t * 1e9,
                          [0.0], [0.0], 0, [0.0])
        stages.append(StagePlan(i, i, [dev], frozenset({f"n{i}"}),
                                StageCost(t, 0.0, [t], seg), [1.0]))
    return PipelinePlan(stages, max(times), sum(times))


@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(1e-4, 10.0), min_size=1, max_size=6),
       st.integers(2, 64))
def test_steady_state_period_is_max_stage(times, frames):
    rep = simulate(_plan_from_times(times), frames=frames)
    assert abs(rep.period - max(times)) < 1e-9
    # makespan = warmup latency + (frames-1) * period
    expect = sum(times) + (frames - 1) * max(times)
    assert abs(rep.makespan - expect) < 1e-6
    for d in rep.devices:
        assert 0.0 <= d.utilization <= 1.0 + 1e-9
        assert d.energy_j >= 0


def test_simulation_matches_plan_on_real_model():
    m = zoo.squeezenet(input_size=(96, 96), scale=0.1)
    cluster = make_pi_cluster([1.5, 1.0, 0.8])
    p = plan(m.graph, cluster, m.input_size)
    rep = simulate(p.pipeline, frames=64)
    assert abs(rep.period - p.period) < 1e-9
    assert rep.throughput_per_min > 0
    # the bottleneck stage's devices are the busiest
    bot = max(range(len(p.pipeline.stages)),
              key=lambda i: p.pipeline.stages[i].cost.total)
    bot_util = max(d.utilization for d in rep.devices if d.stage == bot)
    assert bot_util >= max(d.utilization for d in rep.devices) - 1e-9
