"""Plain reference of MobileNetV2 (Sandler et al., arXiv:1801.04381) at
the sizes of `mobilenetv2.json`, in straightforward jax.numpy, and the
benchmark's own analytic FLOP count.

Follows the published network: a 3x3 stride-2 stem, inverted residual
blocks (1x1 expansion, 3x3 depthwise, linear 1x1 projection, identity
shortcut where the stride is 1 and the widths agree), a 1x1 conv to
1280 channels, global average pooling and a dense classifier. ReLU6
after every convolution but the projection. Departures, each the
program's stated semantics: "SAME" padding (as the TF-Slim release), and
batch normalization with the statistics of the batch at hand, in
training and in serving, with no running averages.

The parameter tree has the program's layout (one entry per freeze unit
under "units", then "head"), so that the benchmark can hand its weights
to the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def unit_specs(doc):
    """Per-unit structure at the configuration's widths."""
    wm = doc["width_mult"]

    def c(ch):
        return max(8, int(ch * wm + 4) // 8 * 8)

    specs = [{"kind": "stem", "cin": 3, "cout": c(doc["stem_channels"]),
              "stride": 2}]
    cin = specs[0]["cout"]
    for t, ch, n, s in doc["blocks"]:
        cout = c(ch)
        for i in range(n):
            specs.append({"kind": "invres", "expand": t, "cin": cin,
                          "hid": cin * t, "cout": cout,
                          "stride": s if i == 0 else 1})
            cin = cout
    specs.append({"kind": "last", "cin": cin,
                  "cout": c(doc["last_channels"]), "stride": 1})
    return specs


def _he(key, shape):
    kh, kw, cin, _ = shape
    return jax.random.normal(key, shape, jnp.float32) * math.sqrt(
        2.0 / (kh * kw * cin))


def _bn(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def init(doc, key):
    """Random weights (He-normal convolutions, 1/sqrt(fan_in) head)."""
    specs = unit_specs(doc)
    keys = iter(jax.random.split(key, 4 * len(specs) + 1))
    units = []
    for sp in specs:
        if sp["kind"] in ("stem", "last"):
            k = 3 if sp["kind"] == "stem" else 1
            units.append({"conv": _he(next(keys), (k, k, sp["cin"], sp["cout"])),
                          "bn": _bn(sp["cout"])})
            continue
        u = {"dw": _he(next(keys), (3, 3, 1, sp["hid"])), "dw_bn": _bn(sp["hid"]),
             "pw": _he(next(keys), (1, 1, sp["hid"], sp["cout"])),
             "pw_bn": _bn(sp["cout"])}
        if sp["expand"] != 1:
            u["exp"] = _he(next(keys), (1, 1, sp["cin"], sp["hid"]))
            u["exp_bn"] = _bn(sp["hid"])
        units.append(u)
    cl = specs[-1]["cout"]
    head = {"w": jax.random.normal(next(keys), (cl, doc["num_classes"]),
                                   jnp.float32) / math.sqrt(cl),
            "b": jnp.zeros((doc["num_classes"],), jnp.float32)}
    return {"units": units, "head": head}


def _conv(x, w, stride=1, groups=1):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def _norm(x, p, eps=1e-5):
    mean = x.mean(axis=(0, 1, 2), keepdims=True)
    var = ((x - mean) ** 2).mean(axis=(0, 1, 2), keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps) * p["scale"].astype(x.dtype)
            + p["bias"].astype(x.dtype))


def _unit(sp, u, x):
    if sp["kind"] in ("stem", "last"):
        return jnp.clip(_norm(_conv(x, u["conv"], sp["stride"]), u["bn"]), 0, 6)
    h = x
    if "exp" in u:
        h = jnp.clip(_norm(_conv(h, u["exp"]), u["exp_bn"]), 0, 6)
    h = jnp.clip(_norm(_conv(h, u["dw"], sp["stride"], groups=h.shape[-1]),
                       u["dw_bn"]), 0, 6)
    h = _norm(_conv(h, u["pw"]), u["pw_bn"])
    if sp["stride"] == 1 and sp["cin"] == sp["cout"]:
        h = h + x
    return h


def forward(doc, params, images, collect=False):
    """(logits, per-unit outputs if `collect`), computed in the dtype of
    `images`."""
    feats = []
    x = images
    for sp, u in zip(unit_specs(doc), params["units"]):
        x = _unit(sp, u, x)
        if collect:
            feats.append(x)
    x = x.mean(axis=(1, 2))
    head = params["head"]
    return x @ head["w"].astype(x.dtype) + head["b"].astype(x.dtype), feats


def unit_forward_flops(doc):
    """Forward FLOPs per image of each freeze unit, the head last: two
    per multiply-accumulate of every convolution and of the classifier.
    Normalization, activations and pooling are not counted."""
    size = doc["image_size"]
    out = []
    for sp in unit_specs(doc):
        in_hw = size * size
        size = -(-size // sp["stride"])
        hw = size * size
        if sp["kind"] in ("stem", "last"):
            k = 3 if sp["kind"] == "stem" else 1
            out.append(2.0 * k * k * sp["cin"] * sp["cout"] * hw)
            continue
        f = 2.0 * 9 * sp["hid"] * hw + 2.0 * sp["hid"] * sp["cout"] * hw
        if sp["expand"] != 1:
            f += 2.0 * sp["cin"] * sp["hid"] * in_hw
        out.append(f)
    out.append(2.0 * unit_specs(doc)[-1]["cout"] * doc["num_classes"])
    return out


def unit_feature_sizes(doc):
    """Features per example of each unit's output (what the CKA probe
    compares)."""
    size = doc["image_size"]
    out = []
    for sp in unit_specs(doc):
        size = -(-size // sp["stride"])
        out.append(size * size * sp["cout"])
    return out
