"""Transformer -> PICO graph export (DESIGN.md §4): Algorithm 1 must
treat full attention as a sync point (the Fig. 6 analogue) and the
planner must build balanced pipelines for the assigned archs."""

import pytest

from repro import configs
from repro.api import PlanSpec
from repro.core import make_tpu_cluster, partition_graph, plan
from repro.models.graph_export import export_graph


def test_zamba2_attention_is_a_sync_point():
    cfg = configs.get("zamba2-2.7b")
    g = export_graph(cfg, seq_len=2048)
    assert g.width() == 1  # decoder chain
    res = partition_graph(g, (2048, 1), n_split=4, max_diameter=5)
    assert res.objective == 0
    for p in res.pieces:
        kinds = {g.layers[n].kind for n in p.nodes}
        # a global-RF attention never fuses below a finite-halo mixer
        if "attn" in kinds:
            assert not kinds & {"conv1d", "ssd"}, kinds


@pytest.mark.parametrize("name", ["llama3.2-1b", "mixtral-8x7b",
                                  "mamba2-370m"])
def test_planner_balances_decoder_pipeline(name):
    cfg = configs.get(name)
    g = export_graph(cfg, seq_len=1024)
    cluster = make_tpu_cluster(4)
    p = plan(g, cluster, (1024, 1), spec=PlanSpec(max_diameter=2))
    assert len(p.pipeline.stages) >= 2
    times = [st.cost.total for st in p.pipeline.stages]
    assert max(times) <= 2.5 * (sum(times) / len(times))  # balanced
    # all vertices covered exactly once
    seen = set()
    for st in p.pipeline.stages:
        assert not (seen & st.nodes)
        seen |= st.nodes
    assert seen == set(g.layers)


def test_swa_has_finite_halo():
    cfg = configs.get("mixtral-8x7b")
    g = export_graph(cfg, seq_len=8192)
    attn = g.layers["l0.attn"]
    assert attn.kind == "swa" and not attn.global_rf
    assert attn.kernel[0] == cfg.sliding_window
