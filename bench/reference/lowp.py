"""The controls: the references computed one precision step down.

The configurations state bfloat16 products (the LM's compute dtype; the
TPU's default precision for ResNet's float32 convolutions).  One step
below is int8 or fp8; the controls round each product's operands to that
format and take the product of the rounded values exactly:

* ``fp8``: float8 e4m3, element by element (the control that sets the
  upper readings of the limits);
* ``int8``: symmetric int8, activations per row (per tensor for a
  convolution's input), weights per output column: the step a later
  change on a v5e (int8 MXU, no fp8) would take, recorded beside it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _int8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def int8_dot(a, b):
    return jnp.matmul(_int8(a, -1), _int8(b, -2), precision=HIGHEST)


def fp8_dot(a, b):
    return jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST)


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def int8_conv(x, w, stride):
    return _conv(_int8(x, None), _int8(w, (0, 1, 2)), stride)


def fp8_conv(x, w, stride):
    return _conv(_fp8(x), _fp8(w), stride)


CONTROLS = {"fp8": (fp8_dot, fp8_conv), "int8": (int8_dot, int8_conv)}
