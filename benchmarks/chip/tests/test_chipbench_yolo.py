"""The YOLOv2 configuration against the program's graph: FLOPs, conv list
and pool marks at 608, the tiny CPU copy, and whole tiny runs of the
offline cells on the CPU with the chip's look skipped."""

from __future__ import annotations

import pytest
from chipbench_tiny import ROOT, run_cell, tiny_config, tiny_root

from chipbench import counts
from chipbench.bench import Bench

SEED = 2 ** 35 + 17


def _graph_and_layers(cfg):
    from repro.models.cnn import zoo
    layers = Bench(ROOT).module("references", cfg["family"]).layers(cfg)
    model = getattr(zoo, cfg["zoo"])(input_size=tuple(cfg["input_size"]),
                                     scale=cfg["scale"])
    return model, layers


def _conv_lists(model, layers):
    convs = [s for s in model.graph.layers.values() if s.kind == "conv"]
    return ([(s.kernel[0], s.stride[0], s.in_channels, s.out_channels)
             for s in convs],
            [(x["k"], x["stride"], x["cin"], x["cout"]) for x in layers])


def test_frame_flops_are_twice_the_graph_macs():
    model, layers = _graph_and_layers(Bench(ROOT).config("yolov2-608"))
    g, sizes = model.graph, model.full_sizes
    macs = sum(g.layers[n].flops(sizes[n]) for n in g.layers
               if g.layers[n].kind == "conv")
    assert counts.frame_flops(layers) == pytest.approx(2 * macs, rel=1e-12)
    assert counts.frame_flops(layers) / 1e9 == pytest.approx(62.9, abs=0.05)
    mine, theirs = _conv_lists(model, layers)
    assert mine == theirs and len(mine) == 23
    assert [(x["h"], x["w"], x["ho"], x["wo"]) for x in layers] == [
        (sizes[g.preds[n][0]][1], sizes[g.preds[n][0]][0], sizes[n][1],
         sizes[n][0]) if g.preds[n] else (608, 608, 608, 608)
        for n, s in g.layers.items() if s.kind == "conv"]


def test_pool_marks_follow_the_graph():
    """A conv's ``pool`` is set where a max-pool alone consumes it: not
    on the last 38x38 conv, which feeds the route too."""
    model, layers = _graph_and_layers(Bench(ROOT).config("yolov2-608"))
    g = model.graph
    convs = [n for n, s in g.layers.items() if s.kind == "conv"]
    want = [g.layers[g.succs[n][0]].kernel[0]
            if [g.layers[s].kind for s in g.succs[n]] == ["pool"] else None
            for n in convs]
    assert [x["pool"] for x in layers] == want
    assert want.count(2) == 4


def test_tiny_config_builds_the_same_convs():
    cfg = tiny_config(Bench(ROOT).config("yolov2-608"))
    model, layers = _graph_and_layers(cfg)
    mine, theirs = _conv_lists(model, layers)
    assert mine == theirs and len(mine) == 23
    (sink,) = model.graph.sinks()
    assert model.full_sizes[sink] == (1, 1)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("chipbench"))


@pytest.mark.parametrize("cell", ["yolov2-608.offline32",
                                  "resnet34-224.offline32"])
def test_sound_run_is_correct(root, capsys, cell):
    res = run_cell(root, cell, SEED, capsys)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"frames_per_s", "setup_s"}
    assert res["checks"]["logit_err"]["value"] < 1e-4
    assert res["attempted"] > 0 and res["failed"] == 0
