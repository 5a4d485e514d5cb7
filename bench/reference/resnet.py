"""Plain ResNet-50 forward (arXiv:1512.03385, v1.5 strides) with epitome
weights (EPIM, arXiv:2311.07620).

Float32, convolutions and the classifier at ``precision="highest"`` unless
a lower precision is asked for (``conv``/``dot``).  Sizes come from the
configuration file's layer list and weights are drawn from the seed along
its key tree, so nothing is shared with the program but the seed.

As the configuration states: BatchNorm normalises with the batch's own
statistics (gain 1, shift 0, eps 1e-5), not running statistics; every
layer's weights carry 3-bit codes (epitome layers through the epitome
quantizer, dense layers per 256 x 256 tile); the classifier has no bias.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Callable, List

import jax
import jax.numpy as jnp

from .epitome import epitome_weight, quantize, tables


def highest_conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def highest_dot(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def layer_weight(key, lay: dict, quant: dict, tab=None):
    """HWIO conv weight (or (rows, cols) classifier weight), float32;
    ``tab`` is ``tables(spec)`` of an epitome layer."""
    rows, cols = lay["kh"] * lay["kw"] * lay["cin"], lay["cout"]
    if lay["spec"] is not None:
        W = epitome_weight(key, lay["spec"], quant, tab)
    else:
        shape = ((rows, cols) if lay["kind"] == "fc"
                 else (lay["kh"], lay["kw"], lay["cin"], cols))
        W = jax.random.normal(key, shape) / math.sqrt(rows)
        W = quantize(W.reshape(rows, cols), None, quant)
    if lay["kind"] == "fc":
        return W
    return W.reshape(lay["kh"], lay["kw"], lay["cin"], cols)


def _bn(y):
    mean = y.mean(axis=(0, 1, 2))
    var = y.var(axis=(0, 1, 2))
    return (y - mean) * jax.lax.rsqrt(var + 1e-5)


@functools.partial(jax.jit, static_argnames=("stride", "act", "conv"))
def _conv_bn(x, w, *, stride, act, conv):
    y = _bn(conv(x, w, stride))
    return jax.nn.relu(y) if act else y


@jax.jit
def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 3, 3, 1), (1, 2, 2, 1), "SAME")


@jax.jit
def _add_relu(h, x):
    return jax.nn.relu(h + x)


@functools.partial(jax.jit, static_argnames=("dot",))
def _head(x, w, *, dot):
    return dot(x.mean(axis=(1, 2)), w)


def logits(init_key, layers: List[dict], quant: dict, images,
           conv: Callable = highest_conv, dot: Callable = highest_dot):
    """(N, classes) logits of ``images`` (N, H, W, 3), one layer's weights
    made at a time (one program per distinct layer shape)."""
    keys = jax.random.split(init_key, len(layers))
    makers = {}

    def weight(i):
        lay = layers[i]
        shape = json.dumps({k: v for k, v in lay.items() if k != "name"})
        if shape not in makers:
            tab = jax.device_put(tables(lay["spec"])) if lay["spec"] else None
            fn = jax.jit(lambda k, t, lay=lay: layer_weight(k, lay, quant, t))
            makers[shape] = (fn, tab)
        fn, tab = makers[shape]
        return fn(keys[i], tab)

    index = {lay["name"]: i for i, lay in enumerate(layers)}

    def conv_bn(x, name, act=True):
        i = index[name]
        return _conv_bn(x, weight(i), stride=layers[i]["stride"], act=act,
                        conv=conv)

    x = _pool(conv_bn(images, "conv1"))
    blocks = []
    for lay in layers:
        b = lay["name"].rsplit(".", 1)[0]
        if lay["name"].endswith(".conv1") and b not in blocks:
            blocks.append(b)
    for b in blocks:
        h = conv_bn(x, f"{b}.conv1")
        h = conv_bn(h, f"{b}.conv2")
        h = conv_bn(h, f"{b}.conv3", act=False)
        if f"{b}.down" in index:
            x = conv_bn(x, f"{b}.down", act=False)
        x = _add_relu(h, x)
    return _head(x, weight(index["fc"]), dot=dot)
