"""The hybrid (Jamba) cell on the CPU at a test size: a whole run through
``bench/run.py``, correct when the program is sound and not correct when
the served token is altered; and the work ``lm_hybrid.slice_work`` counts
for one Mamba, one attention and one MoE layer, against numbers worked
out by hand.

The test size is the program's smoke share of Jamba2-Mini (2 groups of
8 layers, d_model 64, experts 0-1 of 4 held), with a configuration file
and a limit of its own in ``bench/tests/configs`` (set from CPU readings
at that size: the sound program reads a mean gap of 0.09-0.26, its bf16
Mamba with dt/B/C norms over 4 channels being touchy at d_model 64; a
served token shifted by one id reads 2.5)."""
import contextlib
import io
import json
import os

import pytest

from bench import run as bench_run
from bench import work
from bench.runners import lm_hybrid

HERE = os.path.dirname(os.path.abspath(__file__))
V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
TRAFFIC = {"kind": "requests",
           "arrival": {"process": "closed", "clients": 4},
           "prompt_len": {"dist": "uniform", "lo": 4, "hi": 40},
           "output_len": {"dist": "uniform", "lo": 4, "hi": 12},
           "temperature": 0.0}
E2E = [{"name": "ttft_p90_ms", "unit": "ms"},
       {"name": "tpot_p50_ms", "unit": "ms"}]


def _config():
    with open(os.path.join(HERE, "configs", "jamba2-smoke-q3.json")) as f:
        return json.load(f)


def _run(monkeypatch, trace=0, layer=()):
    import jax
    config = _config()
    cell = {"name": "test-cell", "config": config["name"], "chips": 1}
    monkeypatch.setattr(bench_run, "resolve", lambda w: (
        cell, config, TRAFFIC, E2E, list(layer)))
    monkeypatch.setattr(bench_run, "devices_or_refuse",
                        lambda chips: (jax.devices(), V5E))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", "test-cell", "--seed",
                             str(2**33 + 11), "--seconds", "3",
                             "--trace", str(trace)])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_hybrid_cell_sound(monkeypatch):
    """Prompts above the 128-token chunk are not in this traffic, so the
    run serves bucketed one-shot prefills through Mamba, paged attention
    and the held experts, then decode; the traced run's line carries the
    engine's metrics and leaves the trace-read ones out (no device plane
    on the CPU)."""
    layer = [{"name": n, "unit": u} for n, u in (
        ("moe_share.doc-decode", "%"), ("mamba_share.doc-decode", "%"),
        ("expert_roofline.doc-decode", "%"), ("prefill_ms.doc-decode", "ms"))]
    line = _run(monkeypatch, trace=1, layer=layer)
    assert line["correct"], line["checks"]
    assert set(line["checks"]) == {"mean_gap"}
    assert line["attempted"] > 4 and line["failed"] == 0
    assert "prefill_ms.doc-decode" in line["metrics"]


def test_hybrid_cell_served_token_shifted(monkeypatch):
    from repro.launch import engine
    real = engine._decode_multi

    def broken(*a, **kw):
        toks, live, pool, tok, keys = real(*a, **kw)
        return (toks + 1) % kw["cfg"].vocab, live, pool, tok, keys
    monkeypatch.setattr(engine, "_decode_multi", broken)
    line = _run(monkeypatch)
    assert not line["correct"], line["checks"]


def _one_of_each():
    """Jamba2-Mini's widths cut to two layers: a Mamba mixer with a dense
    FFN, then attention with an MoE FFN (8 experts held of 16)."""
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "jamba2-mini-q3.json")) as f:
        cfg = json.load(f)
    layers = []
    for lay in cfg["layers"]:
        group, block = lay["name"].split("/")[:2]
        if (group, block) in (("L0", "mixer"), ("L0", "ffn"), ("L1", "ffn")):
            layers.append(lay)
        elif (group, block) == ("L4", "mixer"):
            layers.append(dict(lay, name="L1" + lay["name"][2:]))
    return dict(cfg, n_layers=2, pattern=["mamba", "attn"],
                ffn_pattern=["dense", "moe"], layers=layers)


def test_slice_work_by_hand():
    """One decode micro-step of 4 live rows at context 1000, 6 (token,
    held expert) pairs routed."""
    cfg = _one_of_each()
    cell = lm_hybrid.Cell(cfg, TRAFFIC, 0)
    cell.counters = {"slice_start": {"decode_micro_steps": 10,
                                     "expert_rows": 100},
                     "slice_end": {"decode_micro_steps": 11,
                                   "expert_rows": 106}}

    class Req:
        prompt = (0,) * 999

    class Sent:
        req = Req()

        def all_times(self):
            return [0.5, 2.0]           # first token, then one decode token
    cell.sent = [Sent() for _ in range(4)]
    got = cell.slice_work(1.0, 3.0, V5E)
    assert got["decode_tokens"] == 4 and got["decode_micro_steps"] == 1
    assert got["expert_rows"] == 6

    # Mamba (L0): in_proj 4096x16384 folded to 1024 rows, out_proj
    # 8192x4096 to 2048; x_proj 8192x288 and dt_proj 256x8192 dense f32;
    # conv 2*4*8192 and scan 6*8192*16 a token; h read and written (f32)
    assert got["ops"]["mamba"] == 4 * (
        2 * 1024 * 16384 + 2 * 8192 * 288 + 2 * 256 * 8192
        + 2 * 2048 * 4096 + 2 * 4 * 8192 + 6 * 8192 * 16)
    assert got["bytes"]["mamba"] == (
        1024 * 16384 * 3 // 8 + 2048 * 4096 * 3 // 8      # codes
        + (4 * 64 + 8 * 16) * 8                           # tile pairs
        + 4 * 2 * (1024 + 16384 + 8192 + 288 + 256 + 8192 + 2048 + 4096)
        + (8192 * 288 + 256 * 8192) * 4                   # dense f32
        + 4 * 2 * 8192 * 16 * 4)                          # state

    # attention (L1): wq, wo 4096x4096 folded to 1024 rows, wk, wv
    # 4096x1024 at 4096x256; scores and values 4*1000*32*128 a token,
    # bf16 K and V of 8 heads x 128 for 1000 positions
    assert got["ops"]["attn"] == 4 * (2 * 2 * 1024 * 4096
                                      + 2 * 2 * 4096 * 1024
                                      + 4 * 1000 * 32 * 128)
    assert got["bytes"]["attn"] == (
        4 * 2 * (1024 + 4096 + 4096 + 1024 + 4096 + 1024 + 1024 + 4096)
        + 2 * 1024 * 4096 * 3 // 8 + 2 * 4096 * 256 * 3 // 8
        + (2 * 4 * 16 + 2 * 16 * 1) * 8
        + 4 * 1000 * 2 * 8 * 128 * 2)

    # MoE (L1): router 2*4096*16 a token; 6 routed pairs through gate, up
    # (1024x14336 epitomes) and down (3584x4096); every held expert's
    # codes (8 of them) read once
    e_ops = 2 * 1024 * 14336 * 2 + 2 * 3584 * 4096
    e_codes = (2 * 1024 * 14336 * 3 // 8 + 3584 * 4096 * 3 // 8
               + (2 * 4 * 56 + 14 * 16) * 8)
    e_act = 2 * (1024 + 14336) * 2 + (3584 + 4096) * 2
    assert got["ops"]["moe"] == 4 * 2 * 4096 * 16 + 6 * e_ops
    assert got["bytes"]["moe"] == (4 * 4096 * 2 + 4096 * 16 * 4
                                   + 6 * e_act + 8 * e_codes)

    # least time: 8 held experts' three calls at 6/8 rows each, memory
    # bound at these rows
    per_call = [work.least_seconds(o, b, V5E)
                for o, b in lm_hybrid.expert_calls(cfg, 1, 6 / 8)]
    assert got["expert_least_s"] == pytest.approx(8 * sum(per_call))
    assert got["expert_least_s"] == pytest.approx(8 * (
        e_codes + 6 / 8 * e_act) / 819e9)
