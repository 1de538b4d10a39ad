"""Plain VGG (Simonyan & Zisserman 2015, arXiv:1409.1556), as a
configuration file gives it: ``stages`` of ``[repeats, channels]`` 3x3
SAME convs, each conv followed by bias and ReLU, a 2x2 max-pool after
each stage, then the ``head``: a global mean over space and the dense
layers of ``head.dense`` and ``classes`` (no ReLU after a dense layer).
Written with ``lax.conv_general_dilated`` and ``jnp.dot``; it never uses
the program's layer code.
"""

from __future__ import annotations

import jax.numpy as jnp

from chipbench import refops


def layers(cfg) -> list[dict]:
    """The weighted layers in graph order, with their shapes.  A conv's
    ``pool`` is the non-overlapping max-pool that alone consumes it."""
    w, h = cfg["input_size"]
    c = cfg["in_channels"]
    k, pk = cfg["conv_kernel"], cfg["pool_kernel"]
    out = []
    for reps, ch in cfg["stages"]:
        for i in range(reps):
            last = i == reps - 1
            out.append(dict(kind="conv", k=k, stride=1, pad=k // 2,
                            cin=c, cout=ch, h=h, w=w, ho=h, wo=w,
                            pool=pk if last else None))
            c = ch
        h, w = h // pk, w // pk
    for n in list(cfg["head"]["dense"]) + [cfg["classes"]]:
        out.append(dict(kind="fc", cin=c, cout=n))
        c = n
    return out


def init(cfg, key):
    return refops.init(layers(cfg), key)


def forward(cfg, params, x, mode: str = "f32", fold=None):
    """Logits ``(N, classes)`` of NHWC frames ``x`` (``fold``: see
    ``refops.Fold``)."""
    for layer, (w, b) in zip(layers(cfg), params):
        if layer["kind"] == "conv":
            x = refops.conv(x, w, b, layer["stride"], layer["pad"], mode,
                            fold=fold)
            if layer["pool"]:
                x = refops.max_pool(x, layer["pool"], layer["pool"])
        else:
            if x.ndim == 4:
                x = jnp.mean(x, axis=(1, 2))
            x = refops.dense(x, w, b, mode, fold)
    return x.astype(jnp.float32)
