"""The control (the reference one precision step down, int8 products)
fails where the configuration's own precision passes, at sizes a test run
can hold.  The chip readings at the cells' own sizes, from which the
limits were set, are in PERF.md (``bench/control.py`` makes them)."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench import trace
from bench.reference import lowp, rwkv6

HERE = os.path.dirname(os.path.abspath(__file__))


def _runner(name):
    path = os.path.join(os.path.dirname(HERE), "runners", name + ".py")
    spec = importlib.util.spec_from_file_location("runner_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_resnet_control_fails_the_limit():
    with open(os.path.join(HERE, "configs", "tiny-resnet-q3.json")) as f:
        cfg = json.load(f)
    cell = _runner("image_batch").Cell(
        cfg, {"kind": "images", "batch": 4, "pool": 2}, 2**33 + 9)
    cell.setup()
    cell.run_window(1.0, trace.Slice(None, 1.0))
    cell.release()
    got = {n: v for n, v, _ in cell.check(control=True)}
    limit = cfg["check"]["mean_rel_l2"]
    assert got["mean_rel_l2"] < limit < min(got["fp8_mean_rel_l2"],
                                            got["int8_mean_rel_l2"])


def _bf16_dot(a, b):
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def test_lm_int8_control_separates_from_bf16():
    """RWKV-6 at d_model 256 over 16 layers: greedy tokens of bf16 products
    (the configuration's precision) against those of int8 products, each
    scored by its mean gap below the float32 reference's best logit."""
    d, ff, V = 256, 896, 4096
    spec = lambda M, N: {"M": M, "N": N, "m": M // 4, "n": N, "bm": 64,
                         "bn": 64}
    layers = ([{"name": f"L0/mixer/{w}", "spec": spec(d, d)}
               for w in ("wr", "wk", "wv", "wg", "wo")]
              + [{"name": "L0/ffn/wk", "spec": spec(d, ff)},
                 {"name": "L0/ffn/wv", "spec": spec(ff, d)},
                 {"name": "L0/ffn/wr", "spec": spec(d, d)}])
    model = dict(n_layers=16, d_model=d, n_heads=4, vocab=V,
                 rwkv_lora_mix=32, rwkv_lora_decay=64, norm_eps=1e-6)
    quant = dict(bits=3, tile=256, w1=0.7, w2=0.3)
    for seed in (1, 2):
        key = jax.random.PRNGKey(seed)
        toks = np.random.default_rng(seed).integers(0, V, (2, 128))
        ref = rwkv6.logits(key, model, layers, quant, toks)

        def mean_gap(dot):
            pick = jnp.argmax(rwkv6.logits(key, model, layers, quant, toks,
                                           dot=dot), -1)
            chosen = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
            return float(jnp.mean(jnp.max(ref, -1) - chosen))
        assert mean_gap(lowp.int8_dot) > 4 * mean_gap(_bf16_dot)
