"""Operation and byte counts against shapes worked out by hand."""
import json
import os

from bench import work

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_rwkv_epitome_call_at_decode_width():
    cfg = _cfg("rwkv6-7b-q3")
    wr = next(l for l in cfg["layers"] if l["name"] == "L0/mixer/wr")
    assert wr["spec"] == {"M": 4096, "N": 4096, "m": 1024, "n": 4096,
                          "bm": 256, "bn": 256}
    # 32 rows folded into 1024 epitome rows, 4096 output columns
    assert work.epitome_ops(32, wr["spec"]) == 2 * 32 * 1024 * 4096
    # bf16 in (32x1024) and out (32x4096), 3-bit codes, 4x16 tile pairs
    assert work.epitome_bytes(32, wr["spec"], cfg["quant"], "bfloat16") == (
        65_536 + 262_144 + 1_572_864 + 512)
    # at T = 32 the codes dominate: memory-bound on a v5e
    assert work.bound(268_435_456, 1_901_056, V5E) == "memory"


def test_rwkv_token_ops():
    cfg = _cfg("rwkv6-7b-q3")
    per_layer = (5 * 8_388_608 + 29_360_128 + 29_360_128 + 8_388_608
                 + 2_621_440 + 1_048_576 + 1_572_864)
    head = 2 * 4096 * 65536
    assert work.lm_token_ops(cfg) == 32 * per_layer + head == 4_194_304_000
    assert work.lm_prefill_ops(cfg, 10) == 10 * 32 * per_layer + head
    calls = list(work.lm_epitome_calls(cfg, 32))
    assert len(calls) == 32 * 8


def test_resnet_conv_counts():
    cfg = _cfg("resnet50-q3")
    by = {l["name"]: l for l in cfg["layers"]}
    c2 = by["layer1.0.conv2"]
    assert c2["spec"]["m"] == 256 and c2["spec"]["N"] == 64
    T = 56 * 56
    assert work.conv_rows(c2, 1) == T
    assert work.epitome_ops(T, c2["spec"]) == 2 * 3136 * 256 * 64
    assert work.epitome_bytes(T, c2["spec"], cfg["quant"], "float32") == (
        3136 * 256 * 4 + 3136 * 64 * 4 + 256 * 64 * 3 // 8 + 8)
    # the stem is dense: 2 T M N over its 7x7x3 im2col matrix
    assert by["conv1"]["spec"] is None
    ops = work.resnet_image_ops(cfg)
    assert ops > 2 * 112 * 112 * 147 * 64
    assert len(list(work.resnet_epitome_calls(cfg, 2))) == sum(
        1 for l in cfg["layers"] if l["spec"])


def test_least_seconds_is_the_larger_bound():
    assert work.least_seconds(197e12, 0, V5E) == 1.0
    assert work.least_seconds(0, 819e9, V5E) == 1.0
    assert work.least_seconds(197e12, 2 * 819e9, V5E) == 2.0
