"""Epitome-backed layers (EpLinear / EpConv) and their dense twins.

Functional style: ``init_*`` returns a param pytree, ``apply_*`` consumes it.
The EpitomeSpec is *static* configuration (it defines trace-time index maps —
the TPU analogue of IFAT/IFRT/OFAT), never part of the pytree.

Execution modes for an epitomized weight, in increasing optimization order:
  'reconstruct' — materialize W then matmul (paper-faithful baseline; the
                  epitome only saves *storage*, like PIM crossbar area).
  'wrapped'     — channel wrapping (§5.3): compute unique output-column
                  blocks only, expand with a static gather (saves FLOPs and
                  output-buffer writes — the paper's optimization).
  'folded'      — epitome-space matmul: fold activations into epitome rows,
                  multiply in the compressed space, expand by static gather
                  (FLOPs and bytes fall by ~CR; beyond-paper, pure jnp).
  'kernel'      — Pallas epitome_matmul: never materializes W in HBM; the
                  epitome stays VMEM-resident across all virtual tiles
                  (beyond-paper TPU optimization; see kernels/epitome_matmul).

Linear layers and convolutions share one dispatcher
(``_dispatch_epitome_matmul``): a conv lowers to its im2col patch matrix
(rows = output positions, cols = kh*kw*cin — the crossbar word lines) and
then runs the identical ladder, so every mode/quant combination below is
available to both layer kinds.

Each mode composes with ``quant`` (epitome-aware quantization, §4.2).  The
first three apply *fake* quantization — E is quantized+dequantized in fp
before the matmul, so accuracy effects are modeled but storage/bandwidth is
not.  'kernel' + quant is the real thing, and the paper's flagship
configuration (e.g. 3-bit EPIM-ResNet50): the epitome is packed to int8
codes with per-crossbar-tile (scale, zero) and the fused
kernels/quant_epitome_matmul dequantizes in registers — the kernel reads
only int8, once, for all virtual tiles.  By default the pack step runs
per forward (its own cached jit program); weight-stationary serving
should `prepack_linear` the params once — or `prepack_tree` for a whole
scan-over-groups LM param stack (vmapped over the leading group axis) —
so forwards skip re-quantizing entirely.  The fused path is
inference-only (codes are rounded, no STE); training under quantization
uses the fake-quant modes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .epitome import (
    EpitomeSpec,
    folded_matmul,
    init_epitome,
    reconstruct,
    wrapped_matmul,
)
from .placement import LayerPlacement
from .quant import QuantConfig, dequantize_packed, fake_quant

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class EpLayerConfig:
    """Static config attached to each (potentially) epitomized layer."""
    spec: Optional[EpitomeSpec] = None       # None -> dense layer
    mode: str = "wrapped"                    # reconstruct | wrapped | kernel
    quant: Optional[QuantConfig] = None      # None -> fp weights
    placement: Optional[LayerPlacement] = None   # None -> role-based default
    # autotuned kernel block shapes (bt, bk, bn) from plan provenance
    # (kernels/autotune.py); None -> the ops.py heuristics.  fused_fold
    # selects the in-kernel fold variant the tuner picked.
    blocks: Optional[Tuple[int, int, int]] = None
    fused_fold: bool = False

    @property
    def is_epitome(self) -> bool:
        return self.spec is not None


# ---------------------------------------------------------------------------
# Linear
# ---------------------------------------------------------------------------
def init_linear(key: Array, M: int, N: int, cfg: EpLayerConfig,
                *, bias: bool = False, dtype=jnp.float32) -> dict:
    p = {}
    if cfg.is_epitome:
        p["E"] = init_epitome(key, cfg.spec, dtype=dtype)
    else:
        p["W"] = (jax.random.normal(key, (M, N)) / np.sqrt(M)).astype(dtype)
    if bias:
        p["b"] = jnp.zeros((N,), dtype)
    return p


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _quant_kernel_call(cfg: EpLayerConfig, x: Array, packed_arrays) -> Array:
    """The fused quantized-epitome kernel, opaque to autodiff.

    Module-level (not a per-call closure) so its identity — and therefore
    every jit cache keyed on it — is stable across applies.  The custom_vjp
    makes AD call our bwd instead of differentiating through the Pallas
    call; bwd raises a targeted error because the packed int8 codes go
    through a hard round with no straight-through estimator —
    differentiating would silently train nothing.  ``packed_arrays`` is the
    (q, scales, zeros) triple; the static block sizes are rebuilt from
    (spec, quant)."""
    from repro.kernels.ops import (PackedEpitome, pack_blocks,
                                   quant_epitome_matmul)
    bk, bn = pack_blocks(cfg.spec, cfg.quant, cfg.blocks)
    packed = PackedEpitome(*packed_arrays, bk, bn)
    bt = cfg.blocks[0] if cfg.blocks is not None else None
    return quant_epitome_matmul(x, None, cfg.spec, cfg.quant, packed=packed,
                                bt=bt, fused_fold=cfg.fused_fold)


def _quant_kernel_fwd(cfg, x, packed_arrays):
    return _quant_kernel_call(cfg, x, packed_arrays), None


def _quant_kernel_bwd(cfg, res, g):
    raise NotImplementedError(
        "mode='kernel' with quant is inference-only: the packed int8 "
        "codes have no straight-through estimator. Train under "
        "quantization with a fake-quant mode (e.g. 'folded'/folded-q3) "
        "and switch to the fused kernel for serving.")


_quant_kernel_call.defvjp(_quant_kernel_fwd, _quant_kernel_bwd)

# Jitted entry points (cfg static): eager repeated applies of the same
# layer hit the compile cache instead of rebuilding and re-tracing a fresh
# custom_vjp wrapper per call; under an outer jit they simply inline.  The
# pack is its OWN program — shared by prepack_linear and the on-the-fly
# path — so a prepacked layer carries bit-identical codes AND scales to
# what a non-prepacked forward would compute (one compiled pack, two call
# sites; were the pack fused into the matmul program instead, FMA
# contraction could shift the fp scales by an ulp between the paths).
_quant_kernel_apply = jax.jit(_quant_kernel_call, static_argnums=(0,))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _pack_arrays(E: Array, *, cfg: EpLayerConfig):
    from repro.kernels.ops import pack_epitome
    p = pack_epitome(E, cfg.spec, cfg.quant, blocks=cfg.blocks)
    return p.q, p.scales, p.zeros


def _quant_kernel_inference_only(x: Array, E: Array, cfg: EpLayerConfig,
                                 packed) -> Array:
    arrays = ((packed.q, packed.scales, packed.zeros)
              if packed is not None else _pack_arrays(E, cfg=cfg))
    return _whole_on_each_device(functools.partial(_quant_kernel_apply, cfg),
                                 x, arrays)


def _whole_on_each_device(kernel_fn, *args):
    """Run a fused-kernel call under the installed serving mesh.

    GSPMD cannot partition a Mosaic kernel (the chip's compiler refuses
    one with sharded operands), so on a multi-device mesh the call runs
    inside a shard_map with every operand replicated: each device gathers
    the layer's operands and computes the whole output.  Weights stay
    sharded at rest; only the compute is replicated.  Without a mesh, or
    on one device, the call is made as is."""
    from jax.sharding import PartitionSpec as P
    from ..models.common import get_mesh
    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        return kernel_fn(*args)
    return jax.shard_map(kernel_fn, mesh=mesh,
                         in_specs=jax.tree.map(lambda _: P(), args),
                         out_specs=P(), check_vma=False)(*args)


def prepack_linear(params: dict, cfg: EpLayerConfig) -> dict:
    """Inference-time prepack for the fused quantized-epitome path.

    For a mode='kernel' x quant epitome layer, quantizes the epitome ONCE
    (int8 codes + per-block scale/zero) and stores it alongside E, so every
    subsequent apply skips re-quantizing and feeds the kernel pure int8.
    Conv epitome params carry the same {"E": ...} structure, so this packs
    them too (ResNetModel.prepack routes both).  A no-op for every other
    layer kind.  Pure jnp on E, so it also works under vmap over stacked
    param groups."""
    if not (cfg.is_epitome and cfg.quant is not None and cfg.mode == "kernel"):
        return params
    out = dict(params)
    # same jitted pack program the on-the-fly path runs -> bit-identical
    out["Eq"], out["Es"], out["Ez"] = _pack_arrays(params["E"], cfg=cfg)
    return out


def placement_pspec(placement: Optional[LayerPlacement], leaf: str,
                    ndim: int):
    """PartitionSpec of one layer-subdict leaf under a LayerPlacement.

    The last two dims of E / W / Eq are (rows, cols) — they map to
    (row_axis, col_axis); leading dims (the scan-over-groups stack axis)
    replicate.  The per-crossbar-tile Es/Ez scale grids follow the codes
    only when ``scales == 'shard'``; a bias shards its single (cols) dim.
    Anything else (norm vectors, LoRAs, ...) replicates."""
    from jax.sharding import PartitionSpec as P
    if placement is None:
        return P(*([None] * ndim))
    row, col = placement.row_axis, placement.col_axis
    if leaf in ("E", "W", "Eq") and ndim >= 2:
        return P(*([None] * (ndim - 2)), row, col)
    if leaf in ("Es", "Ez") and ndim >= 2 and placement.scales == "shard":
        return P(*([None] * (ndim - 2)), row, col)
    if leaf == "b" and ndim >= 1:
        return P(*([None] * (ndim - 1)), col)
    return P(*([None] * ndim))


def constrained_sharding(mesh, pspec, shape):
    """NamedSharding with axes dropped when absent from the mesh or when
    they do not divide the corresponding dim (the divisibility snap the
    placement legalizer applies to plan artifacts, enforced again at the
    array layer so a stale annotation degrades to replicated instead of
    crashing device_put)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    names = set(mesh.axis_names)
    fixed = []
    for i, s in enumerate(pspec):
        if s is None:
            fixed.append(None)
            continue
        axes = s if isinstance(s, (tuple, list)) else (s,)
        axes = tuple(a for a in axes if a in names)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        ok = axes and shape[i] % size == 0
        fixed.append((axes if len(axes) > 1 else axes[0]) if ok else None)
    return NamedSharding(mesh, P(*fixed))


def _place_layer(leaves: dict, cfg: EpLayerConfig, mesh) -> dict:
    """Lay one (possibly prepacked) layer subdict out on ``mesh`` per the
    config's placement record.  Layers without a placement pass through
    untouched (forcing them replicated here would be a pure memory tax —
    the caller's fallback spec machinery owns them)."""
    if cfg.placement is None:
        return leaves
    return {k: jax.device_put(
                v, constrained_sharding(
                    mesh, placement_pspec(cfg.placement, k, v.ndim), v.shape))
            for k, v in leaves.items()}


def prepack_tree(params, layer_configs: Mapping[str, EpLayerConfig],
                 *, stacked: bool = True, mesh=None):
    """Tree variant of ``prepack_linear`` for scan-over-groups params.

    Walks a param pytree (e.g. the LM's ``params["groups"]``) and, for
    every linear-layer subdict whose '/'-joined path names a kernel x quant
    epitome entry of ``layer_configs``, packs the int8 codes once.  The
    scanned LM stacks every leaf with a leading group axis, and an MoE
    expert site its held experts on the next, so the pack runs under one
    ``jax.vmap`` per leading axis of E (``stacked=True``); the new
    Eq/Es/Ez leaves then carry the same leading axes and slice per group
    (and per expert) inside ``lax.scan`` exactly like E does.  Everything
    else — dense layers, norms, paths the mapping does not name — passes
    through untouched.

    With ``mesh``, every layer subdict named by ``layer_configs`` is
    additionally laid out with a NamedSharding from its placement record
    (plan-driven sharded weight-stationary serving): the packed int8 codes
    land sharded over the annotated mesh axes instead of being packed
    replicated and re-laid-out afterwards."""
    def walk(tree, path):
        if not isinstance(tree, dict):
            return tree
        if "E" in tree or "W" in tree:       # a linear/conv layer subdict
            cfg = layer_configs.get(path)
            if cfg is None:
                return tree
            out = tree
            if (cfg.is_epitome and cfg.quant is not None
                    and cfg.mode == "kernel"):
                pack = lambda p: prepack_linear(p, cfg)
                # one vmap per stacked axis: the group axis, and an MoE
                # site's expert axis after it
                for _ in range(tree["E"].ndim - 2 if stacked else 0):
                    pack = jax.vmap(pack)
                out = pack(tree)
            if mesh is not None:
                out = _place_layer(out, cfg, mesh)
            return out
        return {k: walk(v, f"{path}/{k}" if path else k)
                for k, v in tree.items()}
    return walk(params, "")


def _packed_of(params: dict, cfg: EpLayerConfig):
    """Rebuild the PackedEpitome from prepacked param entries (block sizes
    are deterministic from spec + qcfg, so only the arrays are stored)."""
    from repro.kernels.ops import PackedEpitome, pack_blocks
    bk, bn = pack_blocks(cfg.spec, cfg.quant, cfg.blocks)
    return PackedEpitome(params["Eq"], params["Es"], params["Ez"], bk, bn)


def effective_weight(params: dict, cfg: EpLayerConfig) -> Array:
    """The (possibly fake-quantized) weight a layer multiplies by.

    Used by 'reconstruct' mode, by the quantization parity tests, and as
    the reference the kernel modes are compared against on aligned specs;
    'wrapped'/'kernel' modes never materialize the full W at runtime.
    Prepacked params (``prepack_linear``) dequantize their stored int8
    codes, so a reference forward over a served param tree multiplies by
    exactly the weights the fused kernel reads."""
    if cfg.is_epitome:
        E = params["E"]
        if cfg.quant is not None and "Eq" in params:
            p = _packed_of(params, cfg)
            E = dequantize_packed(p.q, p.scales, p.zeros,
                                  (p.bk, p.bn)).astype(E.dtype)
        elif cfg.quant is not None:
            if cfg.mode == "kernel":
                # mirror the fused path's packed (int8, per-block s/z) quant
                from repro.kernels.ops import pack_epitome
                p = pack_epitome(E, cfg.spec, cfg.quant, blocks=cfg.blocks)
                E = dequantize_packed(p.q, p.scales, p.zeros,
                                      (p.bk, p.bn)).astype(E.dtype)
            else:
                E = fake_quant(E, cfg.spec, cfg.quant)
        return reconstruct(E, cfg.spec)
    W = params["W"]
    if cfg.quant is not None:
        W = fake_quant(W, None, cfg.quant)
    return W


@jax.named_scope("epim.epitome_matmul")
def _dispatch_epitome_matmul(params: dict, x: Array, cfg: EpLayerConfig) -> Array:
    """(…, M) @ W(E) -> (…, N) through the full mode x quant matrix.

    The single execution ladder shared by linear layers and (via their
    im2col patch matrix) convolutions: reconstruct | wrapped | folded |
    kernel | kernel x quant, each composed with fake or packed-int8
    quantization as documented in the module docstring.  Every device op
    it emits carries ``epim.epitome_matmul`` in its op_name, which is how
    a profile's reduction finds the epitome layers' device time."""
    E = params["E"]
    if cfg.mode == "kernel":
        # import here to keep layers importable without pallas
        if cfg.quant is not None:
            # fused path: int8 codes + per-tile dequant in the kernel;
            # prepacked params (prepack_linear) skip the quantize step
            packed = _packed_of(params, cfg) if "Eq" in params else None
            return _quant_kernel_inference_only(x, E, cfg, packed)
        from repro.kernels.ops import epitome_matmul
        return _whole_on_each_device(
            functools.partial(epitome_matmul, spec=cfg.spec), x, E)
    if cfg.mode == "reconstruct":
        return x @ effective_weight(params, cfg).astype(x.dtype)
    if cfg.quant is not None:
        E = fake_quant(E, cfg.spec, cfg.quant)
    if cfg.mode == "wrapped":
        return wrapped_matmul(x, E, cfg.spec)
    if cfg.mode == "folded":
        return folded_matmul(x, E, cfg.spec)
    raise ValueError(f"unknown mode {cfg.mode}")


def exact_dot(x: Array, W: Array) -> Array:
    """``x @ W.astype(x.dtype)`` with geometry-independent bits.

    For low-precision compute dtypes the obvious formulation is NOT
    reproducible across SPMD geometries on the CPU backend: XLA emits a
    different bf16-dot kernel (and elides the f32->bf16 operand rounding
    under allow-excess-precision) depending on whether the module is
    partitioned, so the same dot drifts ~1e-2 between a single-device and
    a meshed compile even with every operand and result pinned replicated.
    Rounding both operands to the compute dtype behind an
    optimization_barrier (so the rounding can't be folded away) and then
    accumulating in float32 picks one kernel in every geometry — measured
    bit-exact single-device vs 2x4 mesh, which the serving cross-geometry
    contract depends on.  float32 (and wider) inputs take the plain dot,
    which is already deterministic across geometries."""
    if jnp.issubdtype(x.dtype, jnp.floating) and \
            jnp.finfo(x.dtype).bits < jnp.finfo(jnp.float32).bits:
        xb = jax.lax.optimization_barrier(x)
        Wb = jax.lax.optimization_barrier(W.astype(x.dtype))
        return (xb.astype(jnp.float32) @ Wb.astype(jnp.float32)).astype(x.dtype)
    return x @ W.astype(x.dtype)


def apply_linear(params: dict, x: Array, cfg: EpLayerConfig) -> Array:
    """y = x @ W (+ b), with W possibly epitome-backed and quantized."""
    if not cfg.is_epitome:
        W = params["W"]
        if cfg.quant is not None:
            W = fake_quant(W, None, cfg.quant)
        y = exact_dot(x, W)
    else:
        y = _dispatch_epitome_matmul(params, x, cfg)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Conv2D (NHWC) — for the paper's own ResNet-50/101 evaluation
# ---------------------------------------------------------------------------
def init_conv(key: Array, kh: int, kw: int, cin: int, cout: int,
              cfg: EpLayerConfig, dtype=jnp.float32) -> dict:
    M = kh * kw * cin
    if cfg.is_epitome:
        return {"E": init_epitome(key, cfg.spec, dtype=dtype)}
    fan = M
    W = (jax.random.normal(key, (kh, kw, cin, cout)) / np.sqrt(fan)).astype(dtype)
    return {"W": W}


def im2col(x: Array, kh: int, kw: int, *, stride: int = 1,
           padding: str = "SAME") -> Array:
    """Extract conv patches as matmul rows: (N, H, W, cin) ->
    (N, H', W', kh*kw*cin), the im2col matrix of the PIM mapping [13].

    Feature columns are ordered (kh, kw, cin) to match an HWIO weight
    flattened to (kh*kw*cin, cout) — and to match EpitomeSpec.M row order —
    so ``im2col(x) @ W.reshape(-1, cout)`` is bit-identical to the lax
    convolution."""
    cin = x.shape[-1]
    p = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    # conv_general_dilated_patches emits features channel-major (cin, kh, kw)
    p = jnp.moveaxis(p.reshape(*p.shape[:-1], cin, kh, kw), -3, -1)
    return p.reshape(*p.shape[:-3], kh * kw * cin)


def apply_conv(params: dict, x: Array, kh: int, kw: int, cin: int, cout: int,
               cfg: EpLayerConfig, *, stride: int = 1, padding: str = "SAME") -> Array:
    """Conv in crossbar space: the epitome stands for the im2col matrix
    (kh*kw*cin, cout) — exactly the PIM mapping [13] of rows/cols.

    Epitomized convs run the SAME execution ladder as apply_linear: the
    input lowers to its im2col patch matrix (N, H', W', kh*kw*cin) and the
    matmul dispatches through _dispatch_epitome_matmul, so every mode
    (reconstruct | wrapped | folded | kernel, x fake/packed quant — incl.
    the fused int8 kernel and prepacked serving) is available to convs.
    The folded/kernel paths realize the paper's feature-map reuse: each
    patch row is folded into epitome-row space once (fold_rows on the patch
    matrix — the IFRT reuse), instead of paying the gather kh*kw times per
    overlapping window.  mode='reconstruct' keeps the fused lax convolution
    (bit-identical to im2col @ W, without materializing the kh*kw-times-
    larger patch tensor — it is the paper-faithful baseline, not an
    epitome-space path)."""
    if cfg.is_epitome and cfg.mode != "reconstruct":
        patches = im2col(x, kh, kw, stride=stride, padding=padding)
        return _dispatch_epitome_matmul(params, patches, cfg)
    if cfg.is_epitome:
        W = effective_weight(params, cfg).reshape(kh, kw, cin, cout)
    else:
        W = params["W"]
        if cfg.quant is not None:
            Wm = fake_quant(W.reshape(-1, cout), None, cfg.quant)
            W = Wm.reshape(kh, kw, cin, cout)
    return jax.lax.conv_general_dilated(
        x, W.astype(x.dtype),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
