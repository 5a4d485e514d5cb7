"""A whole run of each kind of cell on the CPU at a test size, past the
harness's look for a chip: correct when the program is sound, and not
correct when the timed path is broken underneath it.

The test sizes are the program's smoke LM (2 layers, d_model 64) and its
tiny ResNet, with configuration files and limits of their own in
``bench/tests/configs`` (set from CPU readings at that size: the sound
program reads a widest gap near 1e-3 and a ResNet rel L2 near 3e-7)."""
import contextlib
import io
import json
import os

import numpy as np
import pytest

from bench import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}

LM_TRAFFIC = {"kind": "requests",
              "arrival": {"process": "closed", "clients": 4},
              "prompt_len": {"dist": "uniform", "lo": 4, "hi": 12},
              "output_len": {"dist": "uniform", "lo": 4, "hi": 16},
              "temperature": 0.0}
IMG_TRAFFIC = {"kind": "images", "batch": 4, "pool": 2}
E2E = [{"name": "ttft_p90_ms", "unit": "ms"},
       {"name": "tpot_p50_ms", "unit": "ms"},
       {"name": "images_per_s", "unit": "images/s"}]


def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _run(monkeypatch, config, traffic, seed=2**33 + 3, trace=0, layer=()):
    import jax
    cell = {"name": "test-cell", "config": config["name"], "chips": 1}
    monkeypatch.setattr(bench_run, "resolve", lambda w: (
        cell, config, traffic, E2E, list(layer)))
    monkeypatch.setattr(bench_run, "devices_or_refuse",
                        lambda chips: (jax.devices(), V5E))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", "test-cell", "--seed", str(seed),
                             "--seconds", "2", "--trace", str(trace)])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_lm_cell_sound(monkeypatch):
    line = _run(monkeypatch, _config("rwkv6-smoke-q3"), LM_TRAFFIC)
    assert line["correct"], line["checks"]
    assert set(line["checks"]) == {"mean_gap"}
    assert list(line)[-1] == "checks"
    assert line["attempted"] > 4 and line["failed"] == 0
    assert {"setup_s", "ttft_p90_ms", "tpot_p50_ms"} <= set(line["metrics"])


def test_lm_cell_open_loop(monkeypatch):
    """The open-loop branch (Poisson arrivals), as later prefill and bursty
    mixes use it: requests are sent on their schedule and all due ones
    are served."""
    traffic = dict(LM_TRAFFIC, arrival={"process": "poisson", "rate": 20.0},
                   round=16)
    line = _run(monkeypatch, _config("rwkv6-smoke-q3"), traffic)
    assert line["correct"], line["checks"]
    assert 15 <= line["attempted"] <= 80 and line["failed"] == 0


def test_lm_cell_traced(monkeypatch):
    """The traced run's line: per-layer metrics, device busy and window
    seconds, the breakdown.  The CPU has no device plane, so busy is 0 and
    the trace-read metrics stay out of the line."""
    layer = [{"name": n, "unit": u} for n, u in (
        ("tokens_per_dispatch.decode", "tokens"), ("mfu.decode", "%"),
        ("qmm_roofline.decode", "%"), ("peak_hbm_gb.decode", "GB"))]
    line = _run(monkeypatch, _config("rwkv6-smoke-q3"), LM_TRAFFIC,
                trace=1, layer=layer)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"tokens_per_dispatch.decode",
                                    "mfu.decode"}
    assert 1 <= line["metrics"]["tokens_per_dispatch.decode"]["value"] <= 4
    assert line["device"]["window_s"] > 0.5
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_resnet_cell_sound(monkeypatch):
    line = _run(monkeypatch, _config("tiny-resnet-q3"), IMG_TRAFFIC)
    assert line["correct"], line["checks"]
    assert {"setup_s", "images_per_s"} <= set(line["metrics"])


def _alter_tokens(monkeypatch):
    """A token altered where it is produced: every decode dispatch's
    sampled tokens shifted by one id."""
    from repro.launch import engine
    real = engine._decode_multi

    def broken(*a, **kw):
        toks, live, pool, tok, keys = real(*a, **kw)
        return (toks + 1) % kw["cfg"].vocab, live, pool, tok, keys
    monkeypatch.setattr(engine, "_decode_multi", broken)


def _state_unchanged(monkeypatch):
    """A step that returns its state unchanged: decode hands back the
    pool it was given."""
    from repro.launch import engine
    real = engine._decode_multi

    def broken(params, pool, *a, **kw):
        import jax.numpy as jnp
        kept = {k: {kk: jnp.array(vv) for kk, vv in v.items()}
                for k, v in pool.items()}
        toks, live, _, tok, keys = real(params, pool, *a, **kw)
        return toks, live, kept, tok, keys
    monkeypatch.setattr(engine, "_decode_multi", broken)


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged])
def test_lm_cell_fault(monkeypatch, fault):
    fault(monkeypatch)
    line = _run(monkeypatch, _config("rwkv6-smoke-q3"), LM_TRAFFIC)
    assert not line["correct"], line["checks"]


def _alter_answer(monkeypatch):
    """An answer altered where it is produced: one image's logits
    perturbed."""
    from repro.models import resnet
    real = resnet.ResNetModel.apply

    def broken(self, params, x):
        y = real(self, params, x)
        return y.at[0].add(1.0)
    monkeypatch.setattr(resnet.ResNetModel, "apply", broken)


def _half_batch(monkeypatch):
    """Half of the batch left out: the first half computed (its batch
    statistics taken over that half) and repeated."""
    from repro.models import resnet
    real = resnet.ResNetModel.apply

    def broken(self, params, x):
        import jax.numpy as jnp
        h = real(self, params, x[: x.shape[0] // 2])
        return jnp.concatenate([h, h])
    monkeypatch.setattr(resnet.ResNetModel, "apply", broken)


@pytest.mark.parametrize("fault", [_alter_answer, _half_batch])
def test_resnet_cell_fault(monkeypatch, fault):
    fault(monkeypatch)
    line = _run(monkeypatch, _config("tiny-resnet-q3"), IMG_TRAFFIC)
    assert not line["correct"], line["checks"]
