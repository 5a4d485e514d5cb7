"""Kernel autotuner: cache round-trips, deterministic winners, graceful
fallbacks, bit-identity of tuned blocks, and plan-provenance integration."""
import json
import os

import jax
import numpy as np
import pytest

from repro.core.epitome import EpitomeSpec
from repro.core.quant import QuantConfig
from repro.kernels import autotune, ops

SPEC = EpitomeSpec(M=512, N=512, m=256, n=512, bm=128, bn=256)   # aligned
KEY = jax.random.PRNGKey(0)


class CountingTimer:
    """Deterministic fake timer: latency is a fixed function of the call
    index, so winners don't depend on wall-clock noise and tests can assert
    how many timings ran."""

    def __init__(self, best_idx=None):
        self.calls = 0
        self.best_idx = best_idx

    def __call__(self, fn, iters):
        us = 100.0 + self.calls
        if self.best_idx is not None and self.calls == self.best_idx:
            us = 1.0
        self.calls += 1
        return us


class TestKeys:
    def test_t_bucket(self):
        assert autotune.t_bucket(1) == 8
        assert autotune.t_bucket(8) == 8
        assert autotune.t_bucket(49) == 64
        assert autotune.t_bucket(196) == 256
        assert autotune.t_bucket(256) == 256

    def test_tune_key_buckets_T(self):
        assert autotune.tune_key(SPEC, 3, 196) == autotune.tune_key(
            SPEC, 3, 256)
        assert autotune.tune_key(SPEC, 3, 196) != autotune.tune_key(
            SPEC, 4, 196)

    def test_candidates_heuristic_first(self):
        cands = autotune.candidate_blocks(SPEC, 8, bits=3, grid="tiny")
        assert cands[0] == (ops._pick_bt(8), ops._pick_bk_quant(SPEC.m, 256),
                            SPEC.bn)
        assert len(set(cands)) == len(cands)


class TestCache:
    def test_round_trip_hits_cache(self, tmp_path):
        timer = CountingTimer()
        r1 = autotune.tune(SPEC, 3, 8, grid="tiny", timer=timer,
                           cache_dir=str(tmp_path))
        assert r1.source == "timed"
        n = timer.calls
        assert n > 0
        r2 = autotune.tune(SPEC, 3, 8, grid="tiny", timer=timer,
                           cache_dir=str(tmp_path))
        assert r2.source == "cache"
        assert timer.calls == n                  # no re-timing
        assert r2.blocks == r1.blocks and r2.fused_fold == r1.fused_fold
        assert r2.tuned_us == r1.tuned_us

    def test_force_retunes(self, tmp_path):
        timer = CountingTimer()
        autotune.tune(SPEC, 3, 8, grid="tiny", timer=timer,
                      cache_dir=str(tmp_path))
        n = timer.calls
        r = autotune.tune(SPEC, 3, 8, grid="tiny", timer=timer,
                          cache_dir=str(tmp_path), force=True)
        assert r.source == "timed" and timer.calls > n

    def test_stale_signature_invalidates(self, tmp_path):
        timer = CountingTimer()
        autotune.tune(SPEC, 3, 8, grid="tiny", timer=timer,
                      cache_dir=str(tmp_path))
        path = autotune._cache_path(str(tmp_path), jax.default_backend())
        with open(path) as f:
            d = json.load(f)
        d["jax"] = "0.0.0-stale"
        with open(path, "w") as f:
            json.dump(d, f)
        assert autotune._load_cache(str(tmp_path),
                                    jax.default_backend()) == {}
        r = autotune.tune(SPEC, 3, 8, grid="tiny", timer=timer,
                          cache_dir=str(tmp_path))
        assert r.source == "timed"               # re-tuned, no crash

    def test_corrupt_cache_falls_back(self, tmp_path):
        path = autotune._cache_path(str(tmp_path), jax.default_backend())
        os.makedirs(str(tmp_path), exist_ok=True)
        with open(path, "w") as f:
            f.write("not json{")
        r = autotune.tune(SPEC, 3, 8, grid="tiny", timer=CountingTimer(),
                          cache_dir=str(tmp_path))
        assert r.source == "timed"


class TestTune:
    def test_deterministic_winner(self, tmp_path):
        r1 = autotune.tune(SPEC, 3, 8, grid="tiny", timer=CountingTimer(5),
                           cache_dir=str(tmp_path / "a"))
        r2 = autotune.tune(SPEC, 3, 8, grid="tiny", timer=CountingTimer(5),
                           cache_dir=str(tmp_path / "b"))
        assert (r1.blocks, r1.fused_fold) == (r2.blocks, r2.fused_fold)
        assert r1.tuned_us == r2.tuned_us

    def test_tuned_never_slower_than_heuristic(self, tmp_path):
        for best in (0, 3, 7):
            r = autotune.tune(SPEC, 3, 8, grid="tiny",
                              timer=CountingTimer(best),
                              cache_dir=str(tmp_path / str(best)),
                              force=True)
            assert r.tuned_us <= r.heuristic_us

    def test_timer_failure_degrades_to_heuristic(self, tmp_path):
        def broken(fn, iters):
            raise RuntimeError("no clock")
        r = autotune.tune(SPEC, 3, 8, grid="tiny", timer=broken,
                          cache_dir=str(tmp_path))
        assert r.source == "heuristic"
        assert r.blocks == autotune.candidate_blocks(
            SPEC, autotune.t_bucket(8), bits=3, grid="tiny")[0]
        # nothing cached: a later run with a working timer re-tunes
        r2 = autotune.tune(SPEC, 3, 8, grid="tiny", timer=CountingTimer(),
                           cache_dir=str(tmp_path))
        assert r2.source == "timed"

    def test_failing_baseline_raises(self, tmp_path, monkeypatch):
        """A heuristic-blocks kernel that fails to build or run is a fault
        to surface, not a tuning result: tune raises instead of returning
        source='heuristic'."""
        def broken(*a, **k):
            raise RuntimeError("kernel refused")
        monkeypatch.setattr(ops, "quant_epitome_matmul", broken)
        with pytest.raises(RuntimeError, match="kernel refused"):
            autotune.tune(SPEC, 3, 8, grid="tiny", timer=CountingTimer(),
                          cache_dir=str(tmp_path))
        assert not os.listdir(tmp_path)          # nothing cached

    def test_winner_bit_identical_and_accurate(self, tmp_path):
        """The default contract: the winning blocks produce output
        bit-identical to the heuristic blocks, within 1e-4 of the
        reconstruct oracle."""
        r = autotune.tune(SPEC, 3, 8, grid="tiny", timer=CountingTimer(7),
                          cache_dir=str(tmp_path))
        assert r.bit_identical and r.max_err <= 1e-4
        x, E = autotune._synthetic_case(SPEC, autotune.t_bucket(8))
        qcfg = QuantConfig(bits=3)
        p_h = ops.pack_epitome(E, SPEC, qcfg)
        y_h = ops.quant_epitome_matmul(x, None, SPEC, packed=p_h,
                                       interpret=True)
        p_t = ops.pack_epitome(E, SPEC, qcfg, blocks=r.blocks)
        y_t = ops.quant_epitome_matmul(x, None, SPEC, packed=p_t,
                                       bt=r.blocks[0],
                                       fused_fold=r.fused_fold,
                                       interpret=True)
        np.testing.assert_array_equal(np.asarray(y_h), np.asarray(y_t))

    def test_fp_kernel_tunes_too(self, tmp_path):
        r = autotune.tune(SPEC, 0, 16, grid="tiny", timer=CountingTimer(),
                          cache_dir=str(tmp_path))
        assert r.source == "timed" and not r.fused_fold
        assert r.max_err <= 1e-4

    def test_record_is_json_native(self, tmp_path):
        r = autotune.tune(SPEC, 3, 8, grid="tiny", timer=CountingTimer(),
                          cache_dir=str(tmp_path))
        rec = r.record()
        assert json.loads(json.dumps(rec)) == rec
        assert all(type(v) in (int, float, bool, str)
                   for v in rec.values())


class TestTunePlan:
    @pytest.fixture(scope="class")
    def tuned_plan(self, tmp_path_factory):
        from repro.pim.plan import auto_plan
        plan = auto_plan("tiny-resnet", target_cr=2.0, weight_bits=3,
                         mode="kernel")
        cache = str(tmp_path_factory.mktemp("tuned"))
        return autotune.tune_plan(plan, t=1, grid="tiny",
                                  timer=CountingTimer(), cache_dir=cache)

    def test_provenance_stamped(self, tuned_plan):
        rec = tuned_plan.provenance["tuned_blocks"]
        assert rec                                 # every kernel layer tuned
        for name, r in rec.items():
            assert r["tuned_us"] <= r["heuristic_us"]
            assert r["bit_identical"] is True
            assert {"bt", "bk", "bn", "fused_fold", "T",
                    "source"} <= set(r)

    def test_json_round_trip_byte_identical(self, tuned_plan, tmp_path):
        from repro.pim.plan import EpitomePlan
        p1 = str(tmp_path / "a.json")
        p2 = str(tmp_path / "b.json")
        tuned_plan.save(p1)
        EpitomePlan.load(p1).save(p2)
        with open(p1, "rb") as a, open(p2, "rb") as b:
            assert a.read() == b.read()

    def test_layer_configs_carry_tuned_blocks(self, tuned_plan):
        tuned = tuned_plan.tuned_blocks()
        cfgs = dict(tuned_plan.layer_configs())
        for name, (blocks, fused) in tuned.items():
            assert cfgs[name].blocks == blocks
            assert cfgs[name].fused_fold == fused
        assert any(c.blocks is not None for c in cfgs.values())

    def test_untouched_plan_has_no_tuned_blocks(self):
        from repro.pim.plan import auto_plan
        plan = auto_plan("tiny-resnet", target_cr=2.0, weight_bits=3,
                         mode="kernel")
        assert plan.tuned_blocks() == {}
        assert all(c.blocks is None for _, c in plan.layer_configs())
