"""Unit + property tests for the graph IR and receptive-field math."""

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core.graph import (Graph, LayerSpec, tile_widths,
                              proportional_widths)


def chain_graph(specs):
    g = Graph()
    prev = None
    for s in specs:
        g.add(s, [prev] if prev else [])
        prev = s.name
    return g


def test_out_in_maps_roundtrip():
    spec = LayerSpec("c", "conv", (3, 3), (2, 2), (0, 0), 4, 8)
    out = spec.out_size((31, 17))
    assert out == ((31 - 3) // 2 + 1, (17 - 3) // 2 + 1)
    needed = spec.in_size_for(out, (31, 17))
    assert needed[0] <= 31 and needed[1] <= 17
    # exact inverse when stride divides
    spec1 = LayerSpec("c1", "conv", (3, 3), (1, 1), (0, 0), 4, 8)
    assert spec1.in_size_for(spec1.out_size((30, 30)), (30, 30)) == (30, 30)


def test_padded_out_size():
    spec = LayerSpec("c", "conv", (3, 3), (1, 1), (1, 1), 4, 8)
    assert spec.out_size((32, 32)) == (32, 32)  # SAME


def test_global_rf():
    spec = LayerSpec("f", "fc", in_channels=10, out_channels=5)
    assert spec.global_rf
    assert spec.in_size_for((1, 1), (17, 13)) == (17, 13)


def test_forward_sizes_and_width():
    g = Graph()
    g.add(LayerSpec("a", "conv", (3, 3), (1, 1), (0, 0), 3, 8))
    g.add(LayerSpec("b1", "conv", (1, 1), (1, 1), (0, 0), 8, 8), ["a"])
    g.add(LayerSpec("b2", "conv", (3, 3), (1, 1), (1, 1), 8, 8), ["a"])
    g.add(LayerSpec("cat", "concat", in_channels=16, out_channels=16),
          ["b1", "b2"])
    fs = g.forward_sizes((16, 16))
    assert fs["a"] == (14, 14)
    assert fs["b1"] == (14, 14) and fs["b2"] == (14, 14)
    assert fs["cat"] == (14, 14)
    assert g.width() == 2
    assert g.sources() == ["a"]
    assert g.sinks() == ["cat"]


def test_sinks_definition3():
    # mid-segment vertex with an outside consumer is a sink (Def. 3)
    g = Graph()
    g.add(LayerSpec("a", "conv", (1, 1), (1, 1), (0, 0), 3, 4))
    g.add(LayerSpec("b", "conv", (1, 1), (1, 1), (0, 0), 4, 4), ["a"])
    g.add(LayerSpec("c", "add", in_channels=4, out_channels=4), ["a", "b"])
    assert set(g.sinks({"a", "b"})) == {"a", "b"}


def test_required_ranges_exactness_chain():
    g = chain_graph([
        LayerSpec("c1", "conv", (3, 3), (1, 1), (1, 1), 3, 4),
        LayerSpec("p1", "pool", (2, 2), (2, 2), (0, 0), 4, 4),
        LayerSpec("c2", "conv", (5, 5), (1, 1), (2, 2), 4, 8),
    ])
    fs = g.forward_sizes((32, 32))
    ro, ri = g.required_ranges(set(g.layers), {"c2": (4, 10)}, fs, (32, 32))
    assert ro["c2"] == (4, 10)
    # c2 input (padded coords): [4*1-2, 9*1+5-2) = [2, 12)
    assert ri["c2"] == (2, 12)
    assert ro["p1"] == (2, 12)
    assert ri["p1"] == (4, 24)
    assert ro["c1"] == (4, 24)


def test_tile_widths():
    assert tile_widths(10, 3) == [4, 3, 3]
    assert tile_widths(2, 5) == [1, 1]
    assert sum(tile_widths(224, 7)) == 224


def test_proportional_widths():
    w = proportional_widths(100, [3, 1])
    assert sum(w) == 100 and w[0] > w[1]
    assert proportional_widths(2, [1.0, 1.0, 1.0]).count(1) == 2


@settings(max_examples=30, deadline=None)
@given(
    st.lists(st.tuples(st.integers(1, 5), st.integers(1, 2),
                       st.integers(0, 2)), min_size=1, max_size=6),
    st.integers(20, 60),
    st.integers(1, 4),
)
def test_ranges_cover_demand_property(layers, width, parts):
    """Property: for any chain and tile split, per-tile required ranges
    are within bounds and the union of assigned sink tiles covers the
    sink output exactly."""
    specs = []
    cin = 3
    for i, (k, s, p) in enumerate(layers):
        specs.append(LayerSpec(f"l{i}", "conv", (k, k), (s, s), (p, p),
                               cin, 4))
        cin = 4
    g = chain_graph(specs)
    fs = g.forward_sizes((width, width))
    sink = g.sinks()[0]
    W = fs[sink][0]
    if W < parts:
        return
    widths = tile_widths(W, parts)
    start = 0
    covered = []
    for w in widths:
        ro, ri = g.required_ranges(set(g.layers),
                                   {sink: (start, start + w)}, fs,
                                   (width, width))
        assert ro[sink] == (start, start + w)
        for n, (a, b) in ri.items():
            assert 0 <= a <= b
        covered.append((start, start + w))
        start += w
    assert covered[0][0] == 0 and covered[-1][1] == W
