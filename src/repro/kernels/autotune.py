"""Kernel autotuner: measured block shapes for the fused epitome kernels.

The fused int8-epitome matmul is the hot loop of every flagship config,
but its block shapes were heuristic one-shots (``_pick_bt`` / ``_pick_bk``
in ops.py).  PIMCOMP-style, this module closes the loop with *measured*
latency: time ``quant_epitome_matmul`` (and the unquantized
``epitome_matmul``) over a candidate (bt, bk, bn) grid — plus the
fused-fold kernel variant — for a given (legalized spec, quant bits,
T bucket), and stamp the winner into plan provenance.

Correctness contract.  Changing bt or splitting bn (when the column
offsets stay aligned) leaves the per-element contraction order untouched
and is bit-identical; changing bk *reassociates* the fp32 accumulation and
drifts in the last ulps.  The repo's serving contract is bit-exactness, so
every candidate's output is compared against the heuristic-blocks baseline
and — by default — only bit-identical candidates are eligible winners
(``require_bit_identical=False`` opens the full grid and reports max_err
instead).  The heuristic candidate always rides in the same timing sweep,
so the winner satisfies ``tuned_us <= heuristic_us`` by construction.

Results persist in a JSON cache under ``benchmarks/tuned/<backend>.json``
keyed by (spec signature, bits, T bucket); the file records the backend
and jax version, and a mismatching signature invalidates the whole file
(graceful fallback: re-tune, never crash).  If timing is unavailable the
tuner degrades to the heuristic blocks with ``source='heuristic'``; a
heuristic-blocks kernel that fails to build or run raises.

    from repro.kernels.autotune import tune, tune_plan
    res = tune(spec, bits=3, T=196)             # TuneResult
    plan = tune_plan(legal_plan, t=1)           # provenance['tuned_blocks']

``launch/plan.py legalize --tune`` drives ``tune_plan`` from the CLI; the
stamped blocks flow through ``EpitomePlan.layer_configs()`` into
``EpLayerConfig.blocks`` and are honored byte-identically by ``plan run``
and serving.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.epitome import EpitomeSpec, reconstruct
from ..core.quant import QuantConfig, dequantize_packed
from . import ops

Blocks = Tuple[int, int, int]                  # (bt, bk, bn)

_CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "..", "..", "..", "benchmarks", "tuned")


def default_cache_dir() -> str:
    return os.path.normpath(_CACHE_DIR)


@dataclasses.dataclass(frozen=True)
class TuneResult:
    """One tuning decision, ready for plan provenance (JSON-native)."""
    blocks: Blocks                 # winning (bt, bk, bn)
    fused_fold: bool               # winner uses the in-kernel fold variant
    tuned_us: float                # winner's measured latency
    heuristic_us: float            # the heuristic candidate's latency
    bit_identical: bool            # winner output == heuristic-blocks output
    max_err: float                 # winner vs the reconstruct oracle
    source: str                    # 'timed' | 'cache' | 'heuristic'
    backend: str
    key: str

    def record(self) -> Dict[str, Any]:
        """Provenance/cache form — plain JSON types only, so plans carrying
        it round-trip byte-identically."""
        return {"bt": int(self.blocks[0]), "bk": int(self.blocks[1]),
                "bn": int(self.blocks[2]), "fused_fold": bool(self.fused_fold),
                "tuned_us": float(self.tuned_us),
                "heuristic_us": float(self.heuristic_us),
                "bit_identical": bool(self.bit_identical),
                "max_err": float(self.max_err), "backend": self.backend,
                "key": self.key}


def t_bucket(T: int) -> int:
    """Power-of-two T bucket (min 8): one tuning entry serves every row
    count padding up to the same grid."""
    return max(8, 1 << (max(1, int(T)) - 1).bit_length())


def spec_signature(spec: EpitomeSpec) -> str:
    return (f"M{spec.M}-N{spec.N}-m{spec.m}-n{spec.n}"
            f"-bm{spec.bm}-bn{spec.bn}")


def tune_key(spec: EpitomeSpec, bits: int, T: int) -> str:
    return f"{spec_signature(spec)}/b{int(bits)}/T{t_bucket(T)}"


# ---------------------------------------------------------------------------
# Candidate grids
# ---------------------------------------------------------------------------
def candidate_blocks(spec: EpitomeSpec, T: int, *, bits: int = 0,
                     tile: int = 256, grid: str = "default") -> List[Blocks]:
    """Candidate (bt, bk, bn) triples, heuristic first.

    ``grid='tiny'`` keeps the sweep to the heuristic plus one neighbor per
    axis (the CI smoke lane); 'default' spans the standard block menus; bn
    candidates are gated by ``col_blocks_splittable`` so every triple
    samples exactly the planned W."""
    Tb = t_bucket(T)
    quant = bits > 0
    h_bk = (ops._pick_bk_quant(spec.m, tile) if quant
            else ops._pick_bk(spec.m))
    heur: Blocks = (ops._pick_bt(Tb), h_bk, spec.bn)

    bts = [b for b in ops._BT_BLOCKS if b <= Tb]
    bk_cap = min(tile, spec.m) if quant else spec.m
    bks = [b for b in ops._BK_LANE_BLOCKS if b <= bk_cap]
    bns = [b for b in (512, 256, 128) if b < spec.bn
           and ops.col_blocks_splittable(spec, b)]
    bns = [spec.bn] + bns
    if grid == "tiny":
        bts = bts[:2]
        bks = bks[:2]
        bns = bns[:1]
    cands = [heur]
    for bt in bts or [heur[0]]:
        for bk in bks or [heur[1]]:
            for bn in bns:
                c = (bt, bk, bn)
                if c not in cands:
                    cands.append(c)
    return cands


# ---------------------------------------------------------------------------
# Timing
# ---------------------------------------------------------------------------
def wall_timer(fn: Callable[[], Any], iters: int) -> float:
    """Default timer: best-of-iters wall time in us (first call compiles)."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------
def _cache_path(cache_dir: str, backend: str) -> str:
    return os.path.join(cache_dir, f"{backend}.json")


def _load_cache(cache_dir: str, backend: str) -> Dict[str, Any]:
    """Entries of the per-backend cache file; {} when missing or when the
    (backend, jax version) signature mismatches — a stale cache degrades
    to re-tuning, never to serving mistimed blocks."""
    path = _cache_path(cache_dir, backend)
    try:
        with open(path) as f:
            d = json.load(f)
    except (OSError, ValueError):
        return {}
    if d.get("backend") != backend or d.get("jax") != jax.__version__:
        return {}
    entries = d.get("entries")
    return entries if isinstance(entries, dict) else {}


def _save_cache(cache_dir: str, backend: str,
                entries: Dict[str, Any]) -> None:
    os.makedirs(cache_dir, exist_ok=True)
    payload = {"backend": backend, "jax": jax.__version__,
               "entries": entries}
    fd, tmp = tempfile.mkstemp(dir=cache_dir, suffix=".tmp")
    with os.fdopen(fd, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, _cache_path(cache_dir, backend))


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------
def _synthetic_case(spec: EpitomeSpec, T: int):
    """Deterministic (x, E) for a spec — same data for every tuning run, so
    winners are comparable across processes.  Activations are fan-in
    scaled (1/sqrt(M), like a normalized net's) so the absolute fp32
    accumulation error vs the reconstruct oracle stays O(1e-5) regardless
    of contraction depth."""
    key = jax.random.PRNGKey(spec.M * 1000003 + spec.N * 1009 + spec.m)
    kx, ke = jax.random.split(key)
    x = jax.random.normal(kx, (T, spec.M), jnp.float32) * (spec.M ** -0.5)
    E = jax.random.normal(ke, (spec.m, spec.n), jnp.float32)
    return x, E


def tune(spec: EpitomeSpec, bits: int, T: int, *,
         qcfg: Optional[QuantConfig] = None,
         candidates: Optional[Sequence[Blocks]] = None,
         grid: str = "default", iters: int = 2,
         timer: Optional[Callable[[Callable[[], Any], int], float]] = None,
         require_bit_identical: bool = True,
         include_fused_fold: bool = True,
         cache_dir: Optional[str] = None, force: bool = False,
         interpret: Optional[bool] = None) -> TuneResult:
    """Measure the candidate grid for (spec, bits, T bucket) and return the
    winner; a prior winner in the JSON cache short-circuits the sweep
    (``source='cache'``), and a failing timer degrades to the heuristic
    blocks (``source='heuristic'``).  A candidate that fails to build or
    run is skipped; the heuristic baseline failing raises.  ``bits=0`` tunes the unquantized fp
    kernel; otherwise the fused int8 kernel at that weight width."""
    backend = jax.default_backend()
    cache_dir = default_cache_dir() if cache_dir is None else cache_dir
    key = tune_key(spec, bits, T)
    entries = _load_cache(cache_dir, backend)
    hit = entries.get(key)
    if hit is not None and not force:
        # a corrupt/partial entry (hand-edited file, interrupted writer,
        # or a measure/-namespaced record that leaked here) is a cache
        # MISS — re-time rather than crash or serve garbage blocks
        try:
            return TuneResult(blocks=(int(hit["bt"]), int(hit["bk"]),
                                      int(hit["bn"])),
                              fused_fold=bool(hit["fused_fold"]),
                              tuned_us=float(hit["tuned_us"]),
                              heuristic_us=float(hit["heuristic_us"]),
                              bit_identical=bool(hit["bit_identical"]),
                              max_err=float(hit["max_err"]), source="cache",
                              backend=backend, key=key)
        except (KeyError, TypeError, ValueError):
            pass

    quant = bits > 0
    qcfg = qcfg if qcfg is not None else (QuantConfig(bits=bits) if quant
                                          else None)
    tile = qcfg.tile if qcfg is not None else 256
    Tb = t_bucket(T)
    cands = list(candidates) if candidates is not None else \
        candidate_blocks(spec, Tb, bits=bits, tile=tile, grid=grid)
    heur = cands[0]
    timer = wall_timer if timer is None else timer

    x, E = _synthetic_case(spec, Tb)

    def runner(blocks: Blocks, fused: bool) -> Callable[[], jax.Array]:
        bt, bk, bn = blocks
        if quant:
            packed = ops.pack_epitome(E, spec, qcfg, blocks=blocks)
            return jax.jit(lambda: ops.quant_epitome_matmul(
                x, None, spec, packed=packed, bt=bt, fused_fold=fused,
                interpret=interpret))
        return jax.jit(lambda: ops.epitome_matmul(
            x, E, spec, bt=bt, bk=bk, bn=bn, interpret=interpret))

    # the reconstruct oracle: what the kernel's output must approximate
    if quant:
        p0 = ops.pack_epitome(E, spec, qcfg)
        W = reconstruct(dequantize_packed(p0.q, p0.scales, p0.zeros,
                                          (p0.bk, p0.bn)), spec)
    else:
        W = reconstruct(E, spec)
    oracle = np.asarray(x @ W)

    def fallback(reason: str) -> TuneResult:
        res = TuneResult(blocks=heur, fused_fold=False, tuned_us=float("nan"),
                         heuristic_us=float("nan"), bit_identical=True,
                         max_err=float("nan"), source="heuristic",
                         backend=backend, key=key)
        return res

    # the heuristic blocks are what serving runs untuned: if they do not
    # build or run, that is a kernel fault to surface, not a tuning result
    baseline = np.asarray(runner(heur, False)())

    variants: List[Tuple[Blocks, bool]] = [(c, False) for c in cands]
    if include_fused_fold and quant:
        variants += [(c, True) for c in cands]

    sweep = []                           # (us, idx, blocks, fused, ident, err)
    for idx, (blocks, fused) in enumerate(variants):
        try:
            fn = runner(blocks, fused)
            out = np.asarray(fn())
        except Exception:
            continue                     # candidate doesn't build/run: skip
        ident = bool(np.array_equal(out, baseline))
        err = float(np.abs(out - oracle).max())
        try:
            us = float(timer(fn, iters))
        except Exception:
            return fallback("timer unavailable")
        if not np.isfinite(us):
            return fallback("timer unavailable")
        sweep.append((us, idx, blocks, fused, ident, err))

    if not sweep:
        return fallback("no candidate ran")
    heuristic_us = next(s[0] for s in sweep if s[2] == heur and not s[3])
    eligible = [s for s in sweep if s[4]] if require_bit_identical else sweep
    if not eligible:
        eligible = [s for s in sweep if s[2] == heur and not s[3]]
    us, _, blocks, fused, ident, err = min(eligible,
                                           key=lambda s: (s[0], s[1]))
    res = TuneResult(blocks=blocks, fused_fold=fused, tuned_us=us,
                     heuristic_us=heuristic_us, bit_identical=ident,
                     max_err=err, source="timed", backend=backend, key=key)
    entries = _load_cache(cache_dir, backend)   # re-read: concurrent tuners
    entries[key] = res.record()
    try:
        _save_cache(cache_dir, backend, entries)
    except OSError:
        pass                                    # read-only FS: still usable
    return res


# ---------------------------------------------------------------------------
# Plan integration
# ---------------------------------------------------------------------------
def tune_plan(plan, *, t: int = 1, grid: str = "tiny", iters: int = 2,
              timer=None, include_fused_fold: bool = True,
              cache_dir: Optional[str] = None,
              require_bit_identical: bool = True):
    """Tune every kernel-mode epitomized layer of a legalized plan and
    stamp the winners into ``provenance['tuned_blocks']`` (schema-additive:
    provenance is free-form, no version bump).  ``t`` is the activation
    batch: conv layers tune at T = t * out_hw^2 rows (their im2col row
    count), fc/LM projections at T = t (the decode batch).  Repeated
    (spec, bits, T-bucket) keys hit the JSON cache, so duplicate layer
    geometries tune once."""
    import dataclasses as _dc
    from ..pim.plan import inventory_for
    layers = inventory_for(plan.arch)()
    records: Dict[str, Any] = {}
    for l, lp in zip(layers, plan.layers):
        if lp.spec is None or lp.mode != "kernel":
            continue
        T = t * l.rounds if l.kind == "conv" else max(1, t)
        res = tune(lp.spec, lp.weight_bits or 0, T, grid=grid, iters=iters,
                   timer=timer, include_fused_fold=include_fused_fold,
                   cache_dir=cache_dir,
                   require_bit_identical=require_bit_identical)
        records[lp.name] = {**res.record(), "T": int(T),
                            "source": res.source}
    return _dc.replace(plan, provenance={**plan.provenance,
                                         "tuned_blocks": records})
