"""Operations and bytes from shapes: the yardstick for utilisation and
roofline shares.

What is counted is the work the algorithm needs, the same whatever
implements it, taken from the configuration file alone:

* An epitome layer's product sums over the epitome's rows: the input
  is folded into the m epitome rows first, so a call on T rows is
  2 T m N operations (N the layer's output columns), not the dense
  2 T M N.  A dense layer is 2 T M N.  A convolution is the product of its
  im2col matrix: T = images x H' x W' rows, M = kh kw cin.
* An epitome call moves its folded input (T x m) and its output (T x N)
  at the configuration's compute dtype, the codes at their bit width
  (3 bits, not the byte they are stored in), and one float32 (scale,
  zero) pair per crossbar tile.
"""
from __future__ import annotations

import math
from typing import Optional

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def epitome_ops(T: int, spec: dict) -> int:
    return 2 * T * spec["m"] * spec["N"]


def epitome_bytes(T: int, spec: dict, quant: dict, dtype: str) -> int:
    m, n, N = spec["m"], spec["n"], spec["N"]
    act = DTYPE_BYTES[dtype]
    tiles = -(-m // quant["tile"]) * -(-n // quant["tile"])
    return (T * m * act + T * N * act + math.ceil(m * n * quant["bits"] / 8)
            + 2 * 4 * tiles)


def least_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def bound(ops: float, nbytes: float, peaks: dict) -> str:
    return ("compute" if ops / peaks["bf16_flops"]
            >= nbytes / peaks["hbm_bytes_per_s"] else "memory")


# -- language models (RWKV-6) -------------------------------------------------
def lm_token_ops(cfg: dict, with_head: bool = True) -> int:
    """Useful operations of one token through the whole model."""
    d, H = cfg["d_model"], cfg["n_heads"]
    K = d // H
    per_layer = sum(epitome_ops(1, lay["spec"]) if lay["spec"]
                    else 2 * lay["M"] * lay["N"] for lay in cfg["layers"])
    per_layer += 5 * 4 * d * cfg["rwkv_lora_mix"]     # ddlerp LoRAs
    per_layer += 4 * d * cfg["rwkv_lora_decay"]       # decay LoRA
    per_layer += 6 * H * K * K                        # WKV state update
    head = 2 * d * cfg["vocab"] if with_head else 0
    return cfg["n_layers"] * per_layer + head


def lm_prefill_ops(cfg: dict, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` tokens; logits at its last token only."""
    return (prompt_len * lm_token_ops(cfg, with_head=False)
            + 2 * cfg["d_model"] * cfg["vocab"])


def lm_epitome_calls(cfg: dict, T: int):
    """(ops, bytes) of each epitome call of one pass of T rows."""
    for _ in range(cfg["n_layers"]):
        for lay in cfg["layers"]:
            if lay["spec"]:
                yield (epitome_ops(T, lay["spec"]),
                       epitome_bytes(T, lay["spec"], cfg["quant"],
                                     cfg["compute_dtype"]))


# -- ResNet --------------------------------------------------------------------
def conv_rows(lay: dict, images: int) -> int:
    return images * (lay["out_hw"] ** 2 if lay["kind"] == "conv" else 1)


def resnet_image_ops(cfg: dict) -> int:
    total = 0
    for lay in cfg["layers"]:
        T = conv_rows(lay, 1)
        if lay["spec"]:
            total += epitome_ops(T, lay["spec"])
        else:
            total += 2 * T * lay["kh"] * lay["kw"] * lay["cin"] * lay["cout"]
    return total


def resnet_epitome_calls(cfg: dict, images: int):
    for lay in cfg["layers"]:
        if lay["spec"]:
            T = conv_rows(lay, images)
            yield (epitome_ops(T, lay["spec"]),
                   epitome_bytes(T, lay["spec"], cfg["quant"],
                                 cfg["compute_dtype"]))


def calls_least_seconds(calls, peaks: dict) -> Optional[float]:
    calls = list(calls)
    if not calls:
        return None
    return sum(least_seconds(o, b, peaks) for o, b in calls)
