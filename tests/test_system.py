"""System behaviour: distributed correctness on multi-device (fake) meshes.

Multi-device tests run in subprocesses because jax locks the device count at
first init (the main pytest process stays single-device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

# every test here boots jax in a fresh multi-device subprocess — minutes of
# wall-time on CPU, so the whole module runs in the nightly -m slow lane
pytestmark = pytest.mark.slow

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


def test_moe_dispatch_matches_dense():
    """shard_map all_to_all expert dispatch == dense-masked reference, on a
    4x2 (data, model) mesh with 4 experts (capacity ample => no drops)."""
    run_py("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models import moe
        from repro.models.common import set_mesh
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4, 2), ("data", "model"),
            axis_types=(AxisType.Auto,) * 2)
        set_mesh(mesh)
        cfg = dataclasses.replace(get_smoke_config("phi3.5-moe-42b-a6.6b"),
                                  capacity_factor=8.0,
                                  compute_dtype="float32",
                                  param_dtype="float32")
        key = jax.random.PRNGKey(0)
        params = moe.init_moe(key, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model))
        y_dense = moe.moe_dense(params, x, cfg)
        y_disp = jax.jit(lambda p, x: moe.moe_dispatch(p, x, cfg))(params, x)
        err = float(jnp.abs(y_dense - y_disp).max())
        rel = err / float(jnp.abs(y_dense).max())
        assert rel < 1e-4, (err, rel)
        print("MOE OK", rel)
    """)


def test_moe_grok_replicated_experts():
    """8 data shards, 4 experts -> 2 replica slots per expert; gradients on
    the true (E, d, ff) weights stay consistent (the broadcast's transpose
    sums replica contributions)."""
    run_py("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import moe
        from repro.models.common import set_mesh
        from jax.sharding import AxisType
        mesh = jax.make_mesh((8, 1), ("data", "model"),
            axis_types=(AxisType.Auto,) * 2)
        set_mesh(mesh)
        cfg = dataclasses.replace(get_smoke_config("grok-1-314b"),
                                  capacity_factor=8.0,
                                  compute_dtype="float32",
                                  param_dtype="float32")
        params = moe.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 8, cfg.d_model))
        def loss_disp(p):
            return (moe.moe_dispatch(p, x, cfg) ** 2).sum()
        def loss_dense(p):
            return (moe.moe_dense(p, x, cfg) ** 2).sum()
        g1 = jax.jit(jax.grad(loss_disp))(params)
        g2 = jax.jit(jax.grad(loss_dense))(params)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            d = float(jnp.abs(a - b).max())
            s = float(jnp.abs(b).max()) + 1e-9
            assert d / s < 1e-3, (d, s)
        print("GROK MOE GRAD OK")
    """)


def test_sharded_train_step_runs():
    """Real sharded training step on a 4x2 mesh: loss finite, params move."""
    run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models.common import set_mesh
        from repro.train.loop import TrainConfig, init_state, make_train_step
        from repro.train.optimizer import AdamWConfig
        from repro.train.data import SyntheticData
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4, 2), ("data", "model"),
            axis_types=(AxisType.Auto,) * 2)
        set_mesh(mesh)
        cfg = get_smoke_config("qwen2-72b")
        opt = AdamWConfig(lr=1e-3, total_steps=5)
        tc = TrainConfig(grad_accum=2)
        state = init_state(jax.random.PRNGKey(0), cfg, opt, tc)
        data = SyntheticData(vocab=cfg.vocab, seq_len=16, global_batch=8)
        step = jax.jit(make_train_step(cfg, opt, tc), donate_argnums=(0,))
        w0 = float(jnp.abs(state["params"]["embed"]).sum())
        for i in range(3):
            state, m = step(state, data.batch(i))
        assert bool(jnp.isfinite(m["loss"]))
        w1 = float(jnp.abs(state["params"]["embed"]).sum())
        assert w0 != w1
        print("SHARDED TRAIN OK", float(m["loss"]))
    """)


def test_mini_multipod_dryrun():
    """(pod, data, model) = (2, 2, 2) miniature of the production multi-pod
    mesh: train + decode lower&compile with the same spec machinery."""
    run_py("""
        import dataclasses, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.configs import get_smoke_config
        from repro.models import lm
        from repro.models.common import set_mesh, clean_spec
        from repro.train.loop import TrainConfig, init_state, make_train_step
        from repro.train.optimizer import AdamWConfig
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
            axis_types=(AxisType.Auto,) * 3)
        set_mesh(mesh)
        cfg = get_smoke_config("jamba-1.5-large-398b")
        opt = AdamWConfig(moments_dtype="int8")
        tc = TrainConfig(grad_accum=2)
        from repro.train.loop import shaped, state_sharding_tree
        state_shape, specs = state_sharding_tree(cfg, opt, tc)
        state_in = shaped(state_shape, specs, mesh)
        B, S = 8, 32
        batch = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32,
                sharding=NamedSharding(mesh, P(("pod", "data"), None))),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32,
                sharding=NamedSharding(mesh, P(("pod", "data"), None))),
            "mask": jax.ShapeDtypeStruct((B, S), jnp.float32,
                sharding=NamedSharding(mesh, P(("pod", "data"), None))),
        }
        step = make_train_step(cfg, opt, tc)
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            state_in, batch).compile()
        ma = compiled.memory_analysis()
        assert ma.temp_size_in_bytes >= 0
        print("MULTIPOD MINI DRYRUN OK")
    """)


def test_elastic_restart_new_mesh():
    """Checkpoint on a (4,2) mesh restores onto (2,4) — elastic re-shard."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.configs import get_smoke_config
        from repro.models.common import set_mesh
        from repro.models import lm
        from repro.train.checkpoint import CheckpointManager
        from jax.sharding import NamedSharding
        cfg = get_smoke_config("deepseek-67b")
        d = tempfile.mkdtemp()
        from jax.sharding import AxisType
        mesh1 = jax.make_mesh((4, 2), ("data", "model"),
            axis_types=(AxisType.Auto,) * 2)
        set_mesh(mesh1)
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        mgr = CheckpointManager(d, async_write=False)
        mgr.save(1, params, blocking=True)
        # "restart" on a different layout
        mesh2 = jax.make_mesh((2, 4), ("data", "model"),
            axis_types=(AxisType.Auto,) * 2)
        set_mesh(mesh2)
        specs = lm.param_specs(cfg, jax.eval_shape(lambda: params))
        from repro.models.common import clean_spec
        shardings = jax.tree.map(
            lambda sp: NamedSharding(mesh2, clean_spec(*sp)), specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        step, restored = mgr.restore(params, shardings=shardings)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("ELASTIC RESTORE OK")
    """)


def test_compile_cache_placed_from_outside_or_in_checkout(monkeypatch,
                                                          tmp_path):
    """enable_compile_cache leaves an outside JAX_COMPILATION_CACHE_DIR to
    JAX and otherwise points the cache at the fixed in-checkout path."""
    import jax
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable_compile_cache() == \
            compile_cache.CACHE_DIR == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            compile_cache.CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_files_land_in_outside_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, an entry point's compiles are
    written there (JAX reads the variable itself)."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(REPO, "src"))
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr
    assert os.listdir(tmp_path), "no cache entry written"
