"""The program's own trace marks: the engine's host spans and counters,
the layer dispatch's op_name scopes, and the Pallas kernels' names.

A profile reduction attributes device time and idle gaps to a layer only
through these marks (``bench/spans.py`` reads them), so their names and
nesting are part of the program's interface.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.epitome import EpitomeSpec
from repro.core.layers import (EpLayerConfig, apply_linear, init_linear,
                               prepack_linear)
from repro.core.quant import QuantConfig
from repro.kernels import ops
from repro.kernels.quant_matmul import quant_matmul
from repro.kernels.wkv6 import wkv6_chunked
from repro.launch.engine import EngineConfig, Request

MAX_LEN = 48
SPEC = EpitomeSpec(M=512, N=256, m=128, n=256, bm=128, bn=128)
QCFG = QuantConfig(bits=3)


@pytest.fixture(scope="module")
def engine_factory():
    def make(**kw):
        kw.setdefault("arch", "rwkv6-7b")
        kw.setdefault("epitome", "kernel-q3")
        return EngineConfig(smoke=True, mesh=None, capacity=2,
                            max_len=MAX_LEN, **kw).build()
    return make


def _prompt(eng, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, eng.cfg.vocab, n).tolist()


def _epim_events(trace_dir):
    """(name, start_ns, end_ns, stats) of the host's ``epim.*`` events."""
    [path] = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
             {k: v for k, v in e.stats})
            for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name.startswith("epim.")]


def _inside(inner, outers):
    return any(s <= inner[1] and inner[2] <= e for _, s, e, _ in outers)


def test_engine_spans_nest_on_the_host_plane(engine_factory, tmp_path):
    eng = engine_factory()
    eng.submit(Request(prompt=_prompt(eng, 5), max_new_tokens=3))
    eng.drain()                                  # compile outside the trace
    steps = eng.stats["decode_steps"]
    with jax.profiler.trace(str(tmp_path)):
        h = eng.submit(Request(prompt=_prompt(eng, 6, 1), max_new_tokens=3))
        eng.drain()
    ev = _epim_events(tmp_path)
    by = {}
    for e in ev:
        by.setdefault(e[0], []).append(e)
    assert {"epim.step", "epim.submit", "epim.admit", "epim.prefill",
            "epim.prefill.wait", "epim.activate", "epim.retire",
            "epim.retire.wait", "epim.dispatch"} <= set(by)
    [prefill] = by["epim.prefill"]
    assert prefill[3]["rid"] == h.request_id and prefill[3]["bucket"] == 8
    assert by["epim.admit"][0][3] == {"rid": h.request_id, "slot": 0}
    # numbered by the dispatches before it; the last step only retires
    assert sorted(e[3]["step_num"] for e in by["epim.step"]) == list(
        range(steps, eng.stats["decode_steps"] + 1))
    assert all(e[3] == {"k": 1, "live": 1} for e in by["epim.dispatch"])
    # the request's spans nest submit > admit > prefill > (activate, then
    # prefill.wait): the activation is launched before the host waits
    assert _inside(prefill, by["epim.admit"])
    assert _inside(by["epim.admit"][0], by["epim.submit"])
    [activate], [wait] = by["epim.activate"], by["epim.prefill.wait"]
    assert _inside(activate, [prefill]) and _inside(wait, [prefill])
    assert activate[2] <= wait[1]
    for name, outer in (("epim.retire.wait", "epim.retire"),
                        ("epim.retire", "epim.step"),
                        ("epim.dispatch", "epim.step")):
        assert all(_inside(e, by[outer]) for e in by[name]), name
    assert not any(_inside(e, by["epim.step"]) for e in by["epim.submit"])


@pytest.mark.parametrize("arch,chunk,prompt_len",
                         [("rwkv6-7b", 0, 12), ("qwen2-72b", 8, 20)],
                         ids=["one-shot", "chunked"])
def test_prefill_seconds_grow_with_each_admission(engine_factory, arch,
                                                  chunk, prompt_len):
    """Each prefill adds its host time; a chunked one (an attention arch:
    the rwkv smoke recurrence window exceeds max_len) counts each chunk."""
    eng = engine_factory(arch=arch, epitome="off", prefill_chunk=chunk)
    assert "slot_hwm" not in eng.stats
    seen = [eng.stats["prefill_s"]]
    assert seen == [0.0]
    for i in range(3):
        eng.submit(Request(prompt=_prompt(eng, prompt_len, i),
                           max_new_tokens=2))
        eng.drain()
        assert eng.stats["admitted"] == i + 1
        seen.append(eng.stats["prefill_s"])
    assert all(b > a for a, b in zip(seen, seen[1:])), seen
    assert eng.stats["prefill_chunks"] == (3 * -(-prompt_len // eng.chunk)
                                           if chunk else 0)


def test_handle_token_times_finished_and_not(engine_factory):
    eng = engine_factory()
    h = eng.submit(Request(prompt=_prompt(eng, 4), max_new_tokens=4))
    first = h.token_times
    assert len(first) == 1 and not h.done()     # the prefill's token
    eng.step()
    eng.step()
    mid = h.token_times
    assert len(mid) == 2 and mid[0] == first[0] and mid[1] >= mid[0]
    eng.drain()
    assert h.token_times == h.result().token_times
    assert len(h.token_times) == 4


def _kernel_calls():
    """One call of each Pallas kernel at a small legal shape, compiled
    for Mosaic (not interpret mode)."""
    bk, bn = ops.pack_blocks(SPEC, QCFG)
    grid = (-(-SPEC.m // bk), SPEC.n // bn)
    sds = jax.ShapeDtypeStruct
    q = (sds((SPEC.m, SPEC.n), jnp.int8), sds(grid, jnp.float32),
         sds(grid, jnp.float32))

    def qmm(x, *arrays, fused_fold=False):
        packed = ops.PackedEpitome(*arrays, bk, bn)
        return ops.quant_epitome_matmul(x, None, SPEC, packed=packed,
                                        fused_fold=fused_fold,
                                        interpret=False)
    x = sds((8, SPEC.M), jnp.bfloat16)
    return {
        "epim_qmm": (qmm, (x,) + q),
        "epim_qmm_fused_fold": (lambda *a: qmm(*a, fused_fold=True),
                                (x,) + q),
        "epim_epitome_matmul": (
            lambda x, E: ops.epitome_matmul(x, E, SPEC, interpret=False),
            (x, sds((SPEC.m, SPEC.n), jnp.float32))),
        "epim_quant_matmul": (
            lambda x, w, s, z: quant_matmul(x, w, s, z, bt=8,
                                            interpret=False),
            (sds((8, 256), jnp.bfloat16), sds((256, 256), jnp.int8),
             sds((1, 1), jnp.float32), sds((1, 1), jnp.float32))),
    }


@pytest.mark.parametrize("name", ["epim_qmm", "epim_qmm_fused_fold",
                                  "epim_epitome_matmul", "epim_quant_matmul"])
def test_kernel_carries_its_name_in_the_tpu_lowering(name):
    fn, args = _kernel_calls()[name]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    [call] = [l for l in text.splitlines() if "tpu_custom_call" in l]
    assert f'kernel_name = "{name}"' in call
    assert f'\\22epim_kernel\\22:\\22{name}\\22' in call


def test_layer_ops_carry_the_dispatch_and_fold_scopes():
    cfg = EpLayerConfig(spec=SPEC, mode="kernel", quant=QCFG)
    params = prepack_linear(
        init_linear(jax.random.PRNGKey(0), SPEC.M, SPEC.N, cfg), cfg)
    x = jnp.ones((8, SPEC.M), jnp.bfloat16)
    text = jax.jit(lambda p, x: apply_linear(p, x, cfg)).lower(
        params, x).as_text(debug_info=True)
    locs = [l for l in text.splitlines() if l.startswith("#loc")]
    assert any("/epim.epitome_matmul/" in l for l in locs)
    assert any("epim.fold/scatter-add" in l for l in locs)


def test_wkv6_kernel_ops_carry_its_name():
    """The wkv6 kernel has no Mosaic lowering (its cumsum); in interpret
    mode its ops carry the kernel's name in their op_name."""
    rkv = jnp.zeros((1, 64, 64), jnp.float32)
    text = jax.jit(lambda *a: wkv6_chunked(*a, chunk=64, interpret=True)).lower(
        rkv, rkv, rkv, rkv, jnp.zeros((1, 64), jnp.float32)).as_text(
            debug_info=True)
    assert any(l.startswith("#loc") and "/epim_wkv6/" in l
               for l in text.splitlines())
