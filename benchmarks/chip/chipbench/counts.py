"""Operations and bytes of one frame, counted from a reference family's
layer shapes (its ``layers(cfg)``), the same whatever lowering runs.

* FLOPs: 2 x HO x WO x KH x KW x Cin x Cout per conv, 2 x in x out per
  dense layer.  Halo recomputation and channel padding are not counted.
* Bytes: one read of the input, the weights and the bias, and one write
  of the output, at 2 bytes per element: the width of the operands that
  the stated precision feeds the matrix unit.  Where a non-overlapping
  max-pool alone consumes a conv, the pooled output is counted, which a
  fused kernel writes in place of the conv's.
"""

from __future__ import annotations

BYTES_PER_ELEMENT = 2


def layer_flops(layer: dict) -> float:
    if layer["kind"] == "conv":
        return 2.0 * layer["ho"] * layer["wo"] * layer["k"] ** 2 \
            * layer["cin"] * layer["cout"]
    return 2.0 * layer["cin"] * layer["cout"]


def layer_bytes(layer: dict) -> float:
    if layer["kind"] == "conv":
        pool = layer.get("pool") or 1
        elems = (layer["h"] * layer["w"] * layer["cin"]
                 + layer["k"] ** 2 * layer["cin"] * layer["cout"]
                 + layer["cout"]
                 + (layer["ho"] // pool) * (layer["wo"] // pool)
                 * layer["cout"])
    else:
        elems = (layer["cin"] + layer["cin"] * layer["cout"]
                 + 2 * layer["cout"])
    return float(BYTES_PER_ELEMENT * elems)


def frame_flops(layers) -> float:
    """Model FLOPs of one frame: every conv and dense layer."""
    return sum(layer_flops(x) for x in layers)


def conv_min_s(layers, peak: dict) -> float:
    """Least device seconds the convs of one frame can take: per conv
    the larger of FLOPs over peak FLOP/s and bytes over HBM bytes/s."""
    return sum(max(layer_flops(x) / peak["flops_per_s"],
                   layer_bytes(x) / peak["hbm_bytes_per_s"])
               for x in layers if x["kind"] == "conv")
