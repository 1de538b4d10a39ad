"""FLOP and byte counts of the yardstick, against a hand count and the
program's graph."""

from __future__ import annotations

import pytest
from chipbench_tiny import ROOT

from chipbench import counts
from chipbench.bench import Bench

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_one_conv_by_hand():
    conv = dict(kind="conv", k=3, stride=1, pad=1, cin=64, cout=128,
                h=112, w=112, ho=112, wo=112, pool=2)
    assert counts.layer_flops(conv) == 2 * 112 * 112 * 9 * 64 * 128
    elems = 112 * 112 * 64 + 9 * 64 * 128 + 128 + 56 * 56 * 128
    assert counts.layer_bytes(conv) == 2 * elems
    assert counts.conv_min_s([conv], PEAK) == pytest.approx(
        2 * 112 * 112 * 9 * 64 * 128 / 197e12)
    fc = dict(kind="fc", cin=512, cout=1000)
    assert counts.layer_flops(fc) == 2 * 512 * 1000
    assert counts.conv_min_s([fc], PEAK) == 0


@pytest.mark.parametrize("config,fn,gflop", [
    ("vgg16-224", "vgg16", 30.7), ("resnet34-224", "resnet34", 7.3)])
def test_frame_flops_are_twice_the_graph_macs(config, fn, gflop):
    from repro.models.cnn import zoo
    b = Bench(ROOT)
    cfg = b.config(config)
    layers = b.module("references", cfg["family"]).layers(cfg)
    model = getattr(zoo, fn)(input_size=tuple(cfg["input_size"]))
    g = model.graph
    sizes = model.full_sizes
    macs = sum(g.layers[n].flops(sizes[n]) for n in g.layers
               if g.layers[n].kind in ("conv", "fc"))
    assert counts.frame_flops(layers) == pytest.approx(2 * macs, rel=1e-12)
    assert counts.frame_flops(layers) / 1e9 == pytest.approx(gflop, abs=0.1)
    convs = [s for s in g.layers.values() if s.kind == "conv"]
    mine = [x for x in layers if x["kind"] == "conv"]
    assert [(s.kernel[0], s.stride[0], s.in_channels, s.out_channels)
            for s in convs] == [(x["k"], x["stride"], x["cin"], x["cout"])
                                for x in mine]
