"""Plain ResNet with basic blocks (He et al. 2015, arXiv:1512.03385,
Table 1), as a configuration file gives it: a ``stem`` conv and
``stem_pool`` max-pool, then ``stages`` of ``[blocks, channels,
stride]``.  A block is conv3x3(stride) -> conv3x3, plus the input, or a
1x1 projection of it where stride or width changes.  As the program's
graph defines it: BatchNorm is folded away (conv + bias), ReLU follows
every conv (the second one and the projection too) and none follows the
add.  The head is a global mean and one dense layer.  Written with
``lax.conv_general_dilated`` and ``jnp.dot``; it never uses the
program's layer code.
"""

from __future__ import annotations

import jax.numpy as jnp

from chipbench import refops


def layers(cfg) -> list[dict]:
    """The weighted layers in graph order (conv1, conv2, then the
    projection, per block), with their shapes.  ``block`` numbers the
    residual block a conv belongs to (``None`` for the stem)."""
    w, h = cfg["input_size"]
    c = cfg["in_channels"]
    st = cfg["stem"]
    ho = refops.out_size(h, st["kernel"], st["stride"], st["padding"])
    wo = refops.out_size(w, st["kernel"], st["stride"], st["padding"])
    out = [dict(kind="conv", k=st["kernel"], stride=st["stride"],
                pad=st["padding"], cin=c, cout=st["out"], h=h, w=w,
                ho=ho, wo=wo, pool=None, block=None, role="stem")]
    c = st["out"]
    sp = cfg["stem_pool"]
    h = refops.out_size(ho, sp["kernel"], sp["stride"], sp["padding"])
    w = refops.out_size(wo, sp["kernel"], sp["stride"], sp["padding"])
    blk = 0
    for reps, ch, s0 in cfg["stages"]:
        for i in range(reps):
            s = s0 if i == 0 else 1
            ho = refops.out_size(h, 3, s, 1)
            wo = refops.out_size(w, 3, s, 1)
            common = dict(kind="conv", pool=None, block=blk)
            out.append(dict(common, k=3, stride=s, pad=1, cin=c, cout=ch,
                            h=h, w=w, ho=ho, wo=wo, role="conv1"))
            out.append(dict(common, k=3, stride=1, pad=1, cin=ch, cout=ch,
                            h=ho, w=wo, ho=ho, wo=wo, role="conv2"))
            if s != 1 or ch != c:
                out.append(dict(common, k=1, stride=s, pad=0, cin=c,
                                cout=ch, h=h, w=w, ho=ho, wo=wo,
                                role="proj"))
            c, h, w = ch, ho, wo
            blk += 1
    out.append(dict(kind="fc", cin=c, cout=cfg["classes"]))
    return out


def init(cfg, key):
    return refops.init(layers(cfg), key)


def forward(cfg, params, x, mode: str = "f32", fold=None):
    """Logits ``(N, classes)`` of NHWC frames ``x`` (``fold``: see
    ``refops.Fold``)."""
    ls = layers(cfg)

    def apply(layer, p, inp):
        return refops.conv(inp, *p, layer["stride"], layer["pad"], mode,
                           fold=fold)

    sp = cfg["stem_pool"]
    x = apply(ls[0], params[0], x)
    x = refops.max_pool(x, sp["kernel"], sp["stride"], sp["padding"])
    blocks: dict[int, dict] = {}
    for layer, p in zip(ls[1:-1], params[1:-1]):
        blocks.setdefault(layer["block"], {})[layer["role"]] = (layer, p)
    for blk in sorted(blocks):
        d = blocks[blk]
        y = apply(*d["conv2"], apply(*d["conv1"], x))
        x = y + (apply(*d["proj"], x) if "proj" in d else x)
    w, b = params[-1]
    return refops.dense(jnp.mean(x, axis=(1, 2)), w, b, mode, fold) \
        .astype(jnp.float32)
