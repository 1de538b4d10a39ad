"""Plain YOLOv2 (Redmon & Farhadi 2016, arXiv:1612.08242; the layer list
of darknet's ``cfg/yolov2.cfg``), as a configuration file gives it: a
``stem`` conv, then ``stages`` of ``[convs, width, pool]``, each a
max-pool of kernel and stride ``pool`` followed by ``convs`` convs that
alternate 3x3 at ``width`` and 1x1 at ``width // 2``.  Then the
``detection`` block: ``convs`` 3x3 convs at the last width (the main
path); the passthrough, a 1x1 conv at ``width // route_divisor`` on the
last conv of stage ``route_stage``, reorganised space-to-depth by
``reorg_stride``; the two concatenated as ``concat`` orders them; one
3x3 conv at the last width and the 1x1 detection conv of ``outputs``
channels.  BatchNorm is folded away (conv + bias); every conv but the
last ends in leaky ReLU of ``activation.slope``, the last is linear.
The output is the detection map, before the region layer's logistic and
softmax.  Written with ``lax.conv_general_dilated`` (``refops``), a
plain reshape and transpose; it never uses the program's layer code.
"""

from __future__ import annotations

import jax.numpy as jnp

from chipbench import refops


def _one_by_one(width: int) -> int:
    return max(1, width // 2)


def _route_width(cfg) -> int:
    det = cfg["detection"]
    return max(1, cfg["stages"][det["route_stage"]][1]
               // det["route_divisor"])


def layers(cfg) -> list[dict]:
    """The convs in graph order (stem, stages, the main path's convs,
    the route conv, the conv after the concat, the detection conv),
    with their shapes.  A conv's ``pool`` is the max-pool that alone
    consumes it: not the route stage's last conv, which feeds the
    route too."""
    w, h = cfg["input_size"]
    det = cfg["detection"]
    out = []

    def conv(k, cin, cout, h, w, role, stride=1, pad=None):
        pad = k // 2 if pad is None else pad
        out.append(dict(kind="conv", k=k, stride=stride, pad=pad, cin=cin,
                        cout=cout, h=h, w=w,
                        ho=refops.out_size(h, k, stride, pad),
                        wo=refops.out_size(w, k, stride, pad), pool=None,
                        role=role))
        return out[-1]

    st = cfg["stem"]
    x = conv(st["kernel"], cfg["in_channels"], st["out"], h, w, "stem",
             st["stride"], st["padding"])
    r = None
    for i, (reps, width, pool) in enumerate(cfg["stages"]):
        if x is not r:
            x["pool"] = pool
        h, w = x["ho"] // pool, x["wo"] // pool
        for j in range(reps):
            k, cout = (3, width) if j % 2 == 0 else (1, _one_by_one(width))
            x = conv(k, x["cout"], cout, h, w, "stage")
        if i == det["route_stage"]:
            r = x
    width = cfg["stages"][-1][1]
    for _ in range(det["convs"]):
        x = conv(3, x["cout"], width, x["ho"], x["wo"], "main")
    s = det["reorg_stride"]
    route = conv(1, r["cout"], _route_width(cfg), r["ho"], r["wo"], "route")
    x = conv(3, route["cout"] * s * s + x["cout"], width, x["ho"], x["wo"],
             "head")
    conv(1, width, det["outputs"], x["ho"], x["wo"], "detect")
    return out


def init(cfg, key):
    return refops.init(layers(cfg), key)


def leaky(y, slope: float):
    return jnp.where(y >= 0, y, slope * y)


def reorg(x, s: int):
    """Space-to-depth by ``s``: output ``(i, j)`` holds input
    ``(i*s + dy, j*s + dx)`` at channel ``(dy*s + dx)*C + c``."""
    n, h, w, c = x.shape
    x = x.reshape(n, h // s, s, w // s, s, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // s, w // s, s * s * c)


def forward(cfg, params, x, mode: str = "f32", fold=None):
    """Detection maps of NHWC frames ``x``, each flattened in its
    ``(H/32, W/32, outputs)`` order: ``(N, H/32 * W/32 * outputs)``
    (``fold``: see ``refops.Fold``).  The convs run in ``layers``'
    order, which is the order the folded biases are made in."""
    ps = iter(zip(layers(cfg), params))
    act = cfg["activation"]

    def conv(inp, last=False):
        layer, (w, b) = next(ps)
        y = refops.conv(inp, w, b, layer["stride"], layer["pad"], mode,
                        relu=False, fold=fold)
        return y if last else leaky(y, act["slope"])

    det = cfg["detection"]
    x = conv(x)
    for i, (reps, _, pool) in enumerate(cfg["stages"]):
        x = refops.max_pool(x, pool, pool)
        for _ in range(reps):
            x = conv(x)
        if i == det["route_stage"]:
            r = x
    for _ in range(det["convs"]):
        x = conv(x)
    parts = {"reorg": reorg(conv(r), det["reorg_stride"]), "main": x}
    x = conv(jnp.concatenate([parts[k] for k in det["concat"]], axis=-1))
    y = conv(x, last=True)
    return y.reshape(y.shape[0], -1).astype(jnp.float32)
