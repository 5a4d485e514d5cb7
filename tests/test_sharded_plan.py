"""Sharded weight-stationary serving from placement-aware plans.

The acceptance contract of the placement pipeline: a searched-then-
legalized LM plan (with placement annotations) serves on a forced
8-host-device CPU mesh with prefill logits and decoded tokens
bit-identical to the single-device prepacked path — the role-based
placement defaults are column-parallel exactly so no cross-device
partial-sum reordering can occur.

Multi-device tests boot jax in fresh subprocesses (jax locks the device
count at first init), like tests/test_system.py; they run in the nightly
slow lane and in CI's sharded smoke lane.  The warning-behaviour tests at
the bottom are in-process and fast.
"""
import os
import subprocess
import sys
import textwrap
import warnings

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_sharded_prepacked_decode_bit_identical():
    """search -> legalize -> mesh_for_plan -> sharded prepack -> decode:
    logits and tokens bit-identical to single-device, packed codes
    actually laid out across the (2, 4) mesh."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.mesh import mesh_for_plan
        from repro.launch.serve import _prefill, generate
        from repro.models import lm
        from repro.models.common import set_mesh
        from repro.pim.evo import EvoConfig
        from repro.pim.plan import legalize_plan, search_plan

        plan = legalize_plan(search_plan(
            "rwkv6-7b-smoke", objective="latency", weight_bits=3, act_bits=9,
            evo=EvoConfig(population=6, iterations=3, seed=0)))
        assert all(lp.placement is not None for lp in plan.layers)
        cfg = get_smoke_config("rwkv6-7b", plan=plan)
        key = jax.random.PRNGKey(0)
        params = lm.init_params(key, cfg)
        prompts = jax.random.randint(key, (2, 8), 0, cfg.vocab)

        set_mesh(None)
        packed = lm.prepack_params(params, cfg)
        state = lm.init_decode_state(cfg, 2, 17)
        logits_ref, _ = _prefill(packed, prompts, state, cfg)
        toks_ref, _ = generate(packed, cfg, prompts, 17, 8)

        mesh = mesh_for_plan(plan, data=2, model=4)
        assert dict(mesh.shape) == {"data": 2, "model": 4}
        set_mesh(mesh)
        sharded = lm.prepack_params(params, cfg, mesh=mesh)
        # the int8 codes really live sharded: at least one leaf is not
        # fully replicated across the 8 devices
        leaves = jax.tree_util.tree_leaves_with_path(sharded["groups"])
        placed = [l for p, l in leaves
                  if getattr(l.sharding, "is_fully_replicated", True) is False]
        assert placed, "no leaf ended up sharded"
        state = lm.init_decode_state(cfg, 2, 17)
        logits_sh, _ = _prefill(sharded, prompts, state, cfg)
        toks_sh, _ = generate(sharded, cfg, prompts, 17, 8)
        np.testing.assert_array_equal(np.asarray(logits_ref),
                                      np.asarray(logits_sh))
        np.testing.assert_array_equal(np.asarray(toks_ref),
                                      np.asarray(toks_sh))
        print("SHARDED PLAN OK")
    """)


@pytest.mark.slow
def test_sharded_attention_prefill_batchN_exact():
    """The former known edge, now closed: batch-N one-shot prefill on an
    attention arch is bit-identical between single-device and a 2x4
    data-sharded mesh — whole logits, not just per-request rows.  Three
    fixes make it exact: the stack-form rope (GSPMD re-reduced the
    concat form 2x when n_kv_heads doesn't divide the model axis),
    contraction-dim replication before wo / w_down (row-parallel
    partial sums reorder additions), and ``core.layers.exact_dot``
    (XLA CPU emits a *different* bf16 dot kernel for partitioned vs
    unpartitioned modules; rounding the operands behind an optimization
    barrier and accumulating in f32 pins one kernel per geometry)."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_host_mesh
        from repro.launch.serve import _prefill, generate
        from repro.models import lm
        from repro.models.common import set_mesh

        cfg = get_smoke_config("qwen2-72b")
        key = jax.random.PRNGKey(0)
        params = lm.init_params(key, cfg)
        prompts = jax.random.randint(key, (2, 8), 0, cfg.vocab)

        def run(p, pr):
            state = lm.init_decode_state(cfg, pr.shape[0], 17)
            lg, _ = _prefill(p, pr, state, cfg)
            toks, _ = generate(p, cfg, pr, 17, 8)
            return np.asarray(jnp.float32(lg)), np.asarray(toks)

        set_mesh(None)
        lg2, tk2 = run(params, prompts)
        lg1, tk1 = run(params, prompts[:1])

        mesh = make_host_mesh(data=2, model=4)
        set_mesh(mesh)
        sp = lm.shard_params(params, cfg, mesh)
        slg2, stk2 = run(sp, prompts)
        slg1, stk1 = run(sp, prompts[:1])

        # batch-2 cross-geometry: the comparison that used to drift
        np.testing.assert_array_equal(slg2, lg2)
        np.testing.assert_array_equal(stk2, tk2)
        # batch-1 parity (the old contract) still holds
        np.testing.assert_array_equal(slg1, lg1)
        np.testing.assert_array_equal(stk1, tk1)
        # and batch composition doesn't perturb a row under the mesh
        np.testing.assert_array_equal(slg2[:1], slg1)
        print("ATTN BATCH-N CROSS-GEOMETRY OK")
    """)


@pytest.mark.slow
def test_plan_run_mesh_cli():
    """launch/plan.py run --mesh asserts sharded-vs-single-device
    bit-identity itself — the CLI form of the acceptance criterion."""
    import tempfile
    code = """
        import subprocess, sys, os, tempfile
        d = tempfile.mkdtemp()
        env = dict(os.environ)
        def run(*args):
            out = subprocess.run([sys.executable, "-m", "repro.launch.plan",
                                  *args], capture_output=True, text=True,
                                 env=env)
            assert out.returncode == 0, out.stdout + out.stderr
            return out.stdout
        p = os.path.join(d, "p.json"); q = os.path.join(d, "q.json")
        run("search", "--arch", "rwkv6-7b-smoke", "--objective", "latency",
            "--weight-bits", "3", "--act-bits", "9", "--population", "6",
            "--iterations", "3", "--out", p)
        run("legalize", "--plan", p, "--mesh", "2,4", "--out", q)
        out = run("run", "--plan", q, "--mesh", "2,4")
        assert "logits bit-identical=True" in out, out
        assert "tokens bit-identical=True" in out, out
        print("PLAN RUN MESH OK")
    """
    run_py(code)


# ---------------------------------------------------------------------------
# In-process (fast): oversized mesh refusal + placement fallback warnings
# ---------------------------------------------------------------------------
def test_make_host_mesh_warns_on_clamp():
    """An explicit mesh larger than the device count is refused (it used
    to be clamped with a warning, so a run could report numbers for a mesh
    nobody asked for); one that fits builds silently."""
    import jax
    from repro.launch.mesh import make_host_mesh
    n = len(jax.devices())
    for data, model in ((n + 1, 1), (1, n + 1), (n, 2)):
        with pytest.raises(ValueError, match="needs"):
            make_host_mesh(data=data, model=model)

    with warnings.catch_warnings():
        warnings.simplefilter("error")          # no warning when it fits
        mesh = make_host_mesh(data=1, model=1)
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_engine_explicit_mesh_too_large_raises():
    """EngineConfig(mesh='D,M') goes through make_host_mesh: an explicit
    sharded layout that the devices cannot hold fails before any param is
    built, while the automatic data-parallel default still fits."""
    import jax
    from repro.launch.engine import EngineConfig
    from repro.models.common import set_mesh
    n = len(jax.devices())
    try:
        with pytest.raises(ValueError, match="needs"):
            EngineConfig(arch="rwkv6-7b", smoke=True,
                         mesh=f"1,{n + 1}").build()
    finally:
        set_mesh(None)


def test_mesh_for_plan_warns_on_placement_fallback():
    import dataclasses
    from repro.core.placement import LayerPlacement
    from repro.launch.mesh import mesh_for_plan
    from repro.pim.plan import auto_plan
    plan = auto_plan("rwkv6-7b-smoke", target_cr=2.0, weight_bits=3,
                     mode="kernel")
    # 'pod' is a legal axis name but absent from the (data, model) host
    # mesh -> the placement legalizer must report the fallback
    plan = dataclasses.replace(plan, layers=[dataclasses.replace(
        plan.layers[0],
        placement=LayerPlacement(row_axis=None, col_axis="pod"))]
        + list(plan.layers[1:]))
    with pytest.warns(UserWarning, match="absent from mesh"):
        mesh_for_plan(plan, data=1, model=1)


def test_parse_mesh():
    from repro.launch.mesh import parse_mesh
    assert parse_mesh("2,4") == (2, 4)
    for bad in ("", "2", "2x4", "0,4", "a,b"):
        with pytest.raises(ValueError):
            parse_mesh(bad)
