"""The LM half of the plan -> legalize -> execute pipeline (PR 4):

per-layer LM plans through ``get_config(plan=...)`` (JSON round-trip,
per-layer bits parity, legality gating), the vmapped scan-over-groups tree
prepack (bit-identical logits, stacked int8 leaves, sharding specs), the
module-level fused-path jit (no-retrace regression), and the
EpitomeSettings.layer_config kernel-mode legalization.  All fast-lane:
smoke-dim configs only.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.archs import BUILDERS
from repro.core.layers import EpLayerConfig
from repro.models import lm
from repro.models.config import EpitomeSettings
from repro.pim.plan import (
    EpitomePlan, INVENTORIES, LM_SMOKE_SUFFIX, auto_plan, inventory_for,
    is_kernel_exact, legalize_plan, search_plan,
)
from repro.pim.workloads import lm_layers

KEY = jax.random.PRNGKey(0)
ARCH = "rwkv6-7b"
SMOKE = ARCH + LM_SMOKE_SUFFIX


def _tree_get(tree, path):
    for k in path.split("/"):
        tree = tree[k]
    return tree


# ---------------------------------------------------------------------------
# Inventory <-> param tree contract
# ---------------------------------------------------------------------------
class TestLMInventory:
    def test_registry_covers_all_lm_archs(self):
        """INVENTORIES' static LM arch list must track configs/archs.py."""
        for arch in BUILDERS:
            assert arch in INVENTORIES, arch
            assert arch + LM_SMOKE_SUFFIX in INVENTORIES, arch

    @pytest.mark.parametrize("arch", ["rwkv6-7b", "gemma2-2b",
                                      "jamba-1.5-large-398b",
                                      "jamba2-mini-ep2"])
    def test_names_and_shapes_match_param_tree(self, arch):
        """Every inventory row names a real param-tree path whose dense
        weight has exactly the inventoried (rows, cols) — stacked over the
        leading group axis, and an MoE layer's expert sites over the held
        experts after it."""
        cfg = get_smoke_config(arch)
        inv = lm_layers(cfg)
        assert inv, arch
        shapes = jax.eval_shape(lambda: lm.init_params(KEY, cfg))
        lo, hi = cfg.held_experts
        for l in inv:
            leaf = _tree_get(shapes["groups"], l.name)
            i = int(l.name.split("/")[0][1:])
            held = ((hi - lo,) if "/ffn/" in l.name
                    and cfg.full_pattern[i][1] == "moe" else ())
            assert leaf["W"].shape == (cfg.n_groups, *held, l.rows,
                                       l.cols), l.name

    def test_smoke_inventory_builder(self):
        names = [l.name for l in inventory_for(SMOKE)()]
        assert names[0].startswith("L0/mixer/")
        assert any(n.startswith("L0/ffn/") for n in names)


# ---------------------------------------------------------------------------
# Scanned-LM tree prepack
# ---------------------------------------------------------------------------
class TestScannedPrepack:
    def _setup(self, epitome="kernel-q3", plan=None):
        cfg = get_smoke_config(ARCH, epitome, plan=plan)
        params = lm.init_params(KEY, cfg)
        return cfg, params

    def test_forward_bit_identical(self):
        """Prepacked vs on-the-fly logits, kernel x q3, smoke LM config:
        the pack runs once (vmapped over groups) instead of per forward,
        changing nothing about the math."""
        cfg, params = self._setup()
        assert lm.needs_prepack(cfg)
        packed = lm.prepack_params(params, cfg)
        toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
        y = lm.forward(params, toks, cfg, remat=False)
        yp = lm.forward(packed, toks, cfg, remat=False)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(yp))

    def test_decode_bit_identical(self):
        """Scan-over-groups decode feeds the fused kernel pure prepacked
        codes and emits the same tokens/logits as the re-quantizing path."""
        from repro.launch.serve import generate
        cfg, params = self._setup()
        packed = lm.prepack_params(params, cfg)
        prompts = jax.random.randint(KEY, (2, 4), 0, cfg.vocab)
        toks, _ = generate(params, cfg, prompts, 12, 4)
        toks_p, _ = generate(packed, cfg, prompts, 12, 4)
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(toks_p))

    def test_packed_leaves_stacked_int8(self):
        cfg, params = self._setup()
        packed = lm.prepack_params(params, cfg)
        for l in lm_layers(cfg):
            leaf = _tree_get(packed["groups"], l.name)
            assert leaf["Eq"].dtype == jnp.int8, l.name
            assert leaf["Eq"].shape[0] == cfg.n_groups, l.name
            assert leaf["Eq"].shape[1:] == leaf["E"].shape[1:], l.name
            assert leaf["Es"].shape[0] == cfg.n_groups, l.name

    def test_noop_without_kernel_quant(self):
        cfg, params = self._setup("folded-q3")
        assert not lm.needs_prepack(cfg)
        packed = lm.prepack_params(params, cfg)
        assert jax.tree.structure(packed) == jax.tree.structure(params)

    def test_param_specs_cover_packed_tree(self):
        """_leaf_spec extends to Eq/Es/Ez: codes shard like E, the tiny
        scale grids replicate."""
        from jax.sharding import PartitionSpec as P
        cfg, params = self._setup()
        packed = lm.prepack_params(params, cfg)
        specs = lm.param_specs(cfg, jax.eval_shape(lambda: packed))
        sample = _tree_get(specs["groups"], "L0/mixer/wr")
        assert sample["Eq"] == sample["E"]
        assert sample["Es"] == P(None, None, None)
        assert sample["Ez"] == P(None, None, None)


# ---------------------------------------------------------------------------
# LM plans through get_config(plan=...)
# ---------------------------------------------------------------------------
class TestLMPlanConfig:
    def test_json_roundtrip_builds_identical_config(self):
        plan = auto_plan(SMOKE, target_cr=2.0, weight_bits=3, mode="kernel")
        rt = EpitomePlan.from_json(plan.to_json())
        cfg = get_smoke_config(ARCH, plan=plan)
        cfg_rt = get_smoke_config(ARCH, plan=rt)
        assert cfg.layer_config == cfg_rt.layer_config
        assert [n for n, _ in cfg.layer_config] == [lp.name
                                                    for lp in plan.layers]
        assert cfg == cfg_rt and hash(cfg) == hash(cfg_rt)

    def test_per_layer_bits_parity(self):
        """A plan's per-layer weight_bits sequence lands 1:1 in the built
        config — per-layer selection, not one global quant."""
        base = auto_plan(SMOKE, target_cr=2.0, mode="kernel")
        bits = [3, 8, None, 4, 3, None, 8, 3][:len(base.layers)]
        plan = dataclasses.replace(
            base, layers=[dataclasses.replace(lp, weight_bits=b)
                          for lp, b in zip(base.layers, bits)])
        assert plan.bits() == bits
        cfg = get_smoke_config(ARCH, plan=plan)
        got = [None if lc.quant is None else lc.quant.bits
               for _, lc in cfg.layer_config]
        assert got == bits

    def test_plan_drives_param_shapes(self):
        plan = auto_plan(SMOKE, target_cr=2.0, weight_bits=3, mode="kernel")
        cfg = get_smoke_config(ARCH, plan=plan)
        params = lm.init_params(KEY, cfg)
        for lp in plan.layers:
            leaf = _tree_get(params["groups"], lp.name)
            if lp.spec is None:
                assert "W" in leaf
            else:
                assert leaf["E"].shape == (cfg.n_groups, lp.spec.m, lp.spec.n)

    def test_arch_mismatch_rejected(self):
        plan = auto_plan(SMOKE, target_cr=2.0, weight_bits=3)
        with pytest.raises(ValueError, match="plan is for"):
            get_smoke_config("gemma2-2b", plan=plan)

    def test_unlegalized_kernel_plan_rejected(self):
        """Searched specs are generally not kernel-exact; building a
        kernel-mode model from one must fail loudly, not silently sample
        snapped geometry."""
        base = auto_plan(SMOKE, target_cr=2.0, weight_bits=3, mode="kernel")
        spec = base.layers[0].spec
        bad_spec = dataclasses.replace(spec, n=min(spec.N, spec.n + spec.bn),
                                       m=max(spec.bm, spec.m // 2))
        if is_kernel_exact(bad_spec):    # force unaligned spread offsets
            bad_spec = dataclasses.replace(spec, n=48, bm=32, bn=32)
        assert not is_kernel_exact(bad_spec)
        bad = dataclasses.replace(
            base, layers=[dataclasses.replace(base.layers[0], spec=bad_spec)]
            + list(base.layers[1:]))
        with pytest.raises(ValueError, match="not kernel-exact"):
            get_smoke_config(ARCH, plan=bad)

    def test_searched_legalized_plan_serves(self):
        """search -> legalize -> config -> prepacked forward: the LM half
        of the plan->legalize->execute loop, end to end."""
        from repro.pim.evo import EvoConfig
        plan = search_plan(SMOKE, objective="latency", weight_bits=3,
                           act_bits=9,
                           evo=EvoConfig(population=6, iterations=3, seed=0))
        legal = legalize_plan(plan)
        assert all(lp.spec is None or is_kernel_exact(lp.spec)
                   for lp in legal.layers)
        cfg = get_smoke_config(ARCH, plan=legal)
        params = lm.init_params(KEY, cfg)
        packed = lm.prepack_params(params, cfg)
        toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
        y = lm.forward(params, toks, cfg, remat=False)
        yp = lm.forward(packed, toks, cfg, remat=False)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(yp))
        assert bool(jnp.all(jnp.isfinite(y)))


# ---------------------------------------------------------------------------
# Placement threading: plan -> layer_config -> param_specs
# ---------------------------------------------------------------------------
class TestPlacementThreading:
    def test_placement_lands_in_layer_config(self):
        """Plan placements survive into the hashable ModelConfig, and the
        JSON round-trip builds an identical config — placement included."""
        plan = auto_plan(SMOKE, target_cr=2.0, weight_bits=3, mode="kernel")
        assert all(lp.placement is not None for lp in plan.layers)
        cfg = get_smoke_config(ARCH, plan=plan)
        for (name, lc), lp in zip(cfg.layer_config, plan.layers):
            assert lc.placement == lp.placement, name
        rt = EpitomePlan.from_json(plan.to_json())
        cfg_rt = get_smoke_config(ARCH, plan=rt)
        assert cfg == cfg_rt and hash(cfg) == hash(cfg_rt)

    def test_param_specs_driven_by_plan_placement(self):
        """An explicit placement annotation overrides the hard-coded path
        rules in param_specs — including for the prepacked Eq/Es leaves
        when scales='shard'."""
        from jax.sharding import PartitionSpec as P
        from repro.core.placement import LayerPlacement
        plan = auto_plan(SMOKE, target_cr=2.0, weight_bits=3, mode="kernel")
        name0 = plan.layers[0].name
        plan = dataclasses.replace(plan, layers=[dataclasses.replace(
            plan.layers[0],
            placement=LayerPlacement(row_axis="data", col_axis="model",
                                     scales="shard"))] + list(plan.layers[1:]))
        cfg = get_smoke_config(ARCH, plan=plan)
        params = lm.init_params(KEY, cfg)
        packed = lm.prepack_params(params, cfg)
        specs = lm.param_specs(cfg, jax.eval_shape(lambda: packed))
        leaf = _tree_get(specs["groups"], name0)
        assert leaf["E"] == P(None, "data", "model")
        assert leaf["Eq"] == P(None, "data", "model")
        assert leaf["Es"] == P(None, "data", "model")      # scales='shard'
        # a default-placement layer: column-parallel, scales replicated
        name1 = plan.layers[1].name
        leaf1 = _tree_get(specs["groups"], name1)
        col = plan.layers[1].placement.col_axis
        assert leaf1["E"] == P(None, None, col)
        assert leaf1["Es"] == P(None, None, None)

    def test_serving_fallback_specs_are_column_parallel(self):
        """Without a plan, serving=True uses the role-based bit-exact
        defaults: output dims shard, contraction dims never do."""
        from jax.sharding import PartitionSpec as P
        cfg = get_smoke_config(ARCH, "kernel-q3")
        params_shape = jax.eval_shape(lambda: lm.init_params(KEY, cfg))
        specs = lm.param_specs(cfg, params_shape, serving=True)
        wq = _tree_get(specs["groups"], "L0/mixer/wr")
        assert wq["E"] == P(None, None, "model")
        wv = _tree_get(specs["groups"], "L0/ffn/wv")       # (ff, d) fan-in
        assert wv["E"] == P(None, None, "data")
        assert specs["embed"] == P("model", None)
        # the training default is untouched: FSDP rows over 'data'
        train = lm.param_specs(cfg, params_shape)
        assert _tree_get(train["groups"], "L0/mixer/wr")["E"] == \
            P(None, "data", "model")


# ---------------------------------------------------------------------------
# Module-level fused path: no retrace across repeated applies
# ---------------------------------------------------------------------------
class TestNoRetrace:
    def test_repeat_apply_hits_cache(self, monkeypatch):
        """_quant_kernel_inference_only used to define a fresh custom_vjp
        closure per call, rebuilding and re-tracing the wrapper every
        apply.  Now both the pack and the fused matmul are module-level
        jits: after the first apply, poisoning the trace-time entry points
        must not matter — a second same-shape apply is a pure cache hit."""
        from repro.core import layers as core_layers
        from repro.core.epitome import EpitomeSpec, init_epitome
        from repro.core.quant import QuantConfig
        from repro.kernels import ops

        spec = EpitomeSpec(M=64, N=64, m=32, n=32, bm=32, bn=32)
        cfg = EpLayerConfig(spec=spec, mode="kernel", quant=QuantConfig(bits=3))
        params = {"E": init_epitome(KEY, spec)}
        x = jax.random.normal(KEY, (4, 64))
        y1 = core_layers.apply_linear(params, x, cfg)

        def boom(*a, **kw):
            raise AssertionError("fused path re-traced on a repeated apply")

        monkeypatch.setattr(ops, "quant_epitome_matmul", boom)
        monkeypatch.setattr(ops, "pack_epitome", boom)
        y2 = core_layers.apply_linear(params, x, cfg)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


# ---------------------------------------------------------------------------
# EpitomeSettings.layer_config legalizes kernel-mode auto specs
# ---------------------------------------------------------------------------
class TestSettingsLegalize:
    # (512, 512) at CR 2 with a (128, 128) patch auto-plans a 512x256
    # epitome whose spread column offsets are NOT bn-aligned
    SHAPE = (512, 512)
    SETTINGS = dict(enabled=True, target_cr=2.0, min_params=0,
                    patch=(128, 128))

    def test_kernel_mode_snaps_and_warns(self):
        s = EpitomeSettings(mode="kernel", **self.SETTINGS)
        with pytest.warns(UserWarning, match="not kernel-exact"):
            lc = s.layer_config(*self.SHAPE)
        assert lc.spec is not None and is_kernel_exact(lc.spec)

    def test_fake_quant_modes_untouched(self):
        from repro.core.epitome import plan_epitome
        raw = plan_epitome(*self.SHAPE, 2.0, patch=(128, 128))
        assert not is_kernel_exact(raw)      # the case under test
        s = EpitomeSettings(mode="folded", **self.SETTINGS)
        assert s.layer_config(*self.SHAPE).spec == raw
