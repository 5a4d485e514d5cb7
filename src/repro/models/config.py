"""Unified model configuration covering all assigned architectures.

A model is ``n_layers`` layers; layers cycle through ``pattern`` (the
smallest repeating "super-block", e.g. jamba's 1-attention-per-8 or gemma2's
local/global alternation).  Each pattern position names a sequence mixer and
an FFN kind.  Layer parameters are stacked over super-block repeats and the
forward pass scans over them (compile-time O(len(pattern)), not O(layers)).
"""
from __future__ import annotations

import dataclasses
import enum
import functools
import math
import warnings
from typing import Optional, Tuple

import jax.numpy as jnp

from ..core.epitome import EpitomeSpec, plan_epitome
from ..core.layers import EpLayerConfig
from ..core.quant import QuantConfig


class LayerKind(str, enum.Enum):
    ATTN = "attn"                 # global causal attention
    ATTN_LOCAL = "attn_local"     # sliding-window attention
    MAMBA = "mamba"
    RWKV = "rwkv"


@dataclasses.dataclass(frozen=True)
class EpitomeSettings:
    """How the paper's operator is applied across a model's weights."""
    enabled: bool = False
    target_cr: float = 4.0            # weight-matrix compression rate
    mode: str = "folded"              # reconstruct | wrapped | folded | kernel
    min_params: int = 1 << 22         # don't epitomize small matrices (4M)
    patch: Tuple[int, int] = (256, 256)
    quant_bits: int = 0               # 0 = fp; else epitome-aware fake quant
    quant_per_crossbar: bool = True
    quant_overlap_weighted: bool = True

    def layer_config(self, M: int, N: int) -> EpLayerConfig:
        if not self.enabled or M * N < self.min_params:
            return EpLayerConfig(spec=None, quant=self._qcfg())
        spec = plan_epitome(M, N, self.target_cr, patch=self.patch)
        if spec is not None and self.mode == "kernel":
            # the fused kernels' OFAT col-block table is exact only for the
            # bn-aligned families; a planned spec with spread (unaligned)
            # column offsets would silently fall back to the kernel's
            # snapped — inexact — sampling.  Route through the legalizer so
            # what runs is bn-aligned/kernel-exact, and surface the snap.
            legal, err = _legalized(spec, M, N, self.patch)
            if legal != spec:
                warnings.warn(
                    f"epitome spec for ({M}, {N}) is not kernel-exact; "
                    f"snapped {spec.m}x{spec.n} -> "
                    f"{'dense' if legal is None else f'{legal.m}x{legal.n}'} "
                    f"(snap error {err:.3f})", stacklevel=2)
            spec = legal
        return EpLayerConfig(spec=spec, mode=self.mode, quant=self._qcfg())

    def _qcfg(self) -> Optional[QuantConfig]:
        if self.quant_bits <= 0:
            return None
        return QuantConfig(bits=self.quant_bits,
                           per_crossbar=self.quant_per_crossbar,
                           overlap_weighted=self.quant_overlap_weighted)


@functools.lru_cache(maxsize=None)
def _legalized(spec: EpitomeSpec, M: int, N: int, patch: Tuple[int, int]):
    """Snap an auto-planned spec to the kernel-exact families, returning
    (legal spec, snap error).  Cached — the same (M, N) site is planned at
    every traced apply — and warning-free so the caller decides per call
    whether to surface the snap."""
    from ..pim.plan import is_kernel_exact, legalize_spec
    from ..pim.workloads import LayerShape
    if is_kernel_exact(spec):
        return spec, 0.0
    layer = LayerShape(f"{M}x{N}", 1, 1, M, N, 1, kind="fc")
    return legalize_spec(layer, spec, patch)


def layer_name(prefix: str, w: str) -> Optional[str]:
    """Param-tree path of projection ``w`` under ``prefix`` — the naming
    contract shared by pim.workloads.lm_layers, ModelConfig.layer_config,
    and the tree prepack.  None without a prefix: the caller then resolves
    per-layer config by shape alone."""
    return f"{prefix}/{w}" if prefix else None


@functools.lru_cache(maxsize=None)
def _layer_config_map(layer_config: Tuple[Tuple[str, EpLayerConfig], ...]):
    """Dict view of the per-layer tuple (cached: ep() runs per traced op)."""
    return dict(layer_config)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                      # 0 -> d_model // n_heads

    # super-block structure
    pattern: Tuple[str, ...] = ("attn",)   # LayerKind values, cycled
    ffn_pattern: Tuple[str, ...] = ("dense",)  # dense | moe | none, cycled

    # attention details
    qkv_bias: bool = False                 # qwen
    window: int = 4096                     # sliding window for ATTN_LOCAL
    rope_theta: float = 10000.0
    rope: bool = True                      # False: no positional encoding
    attn_softcap: float = 0.0              # gemma2: 50.0
    logit_softcap: float = 0.0             # gemma2: 30.0

    # MoE
    n_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    # experts [lo, hi) held by this device (expert parallelism: the layer
    # routes over all n_experts and computes its own experts' part); ()
    # holds them all
    experts_held: Tuple[int, ...] = ()
    # top-k weights renormalised over the k chosen (softmax of the top-k
    # logits); False keeps the softmax over all n_experts (jamba)
    moe_renormalize: bool = True

    # Mamba (jamba)
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dtbc_norm: bool = False          # RMSNorm dt, B, C after x_proj

    # RWKV6
    rwkv_lora_decay: int = 64
    rwkv_lora_mix: int = 32

    # chunking (memory/perf trade-offs; the dry-run cost probes override
    # these so inner scans can be fully unrolled for FLOP counting)
    attn_kv_chunk: int = 512
    rwkv_chunk: int = 64
    mamba_chunk: int = 128

    # distribution/perf knobs (§Perf hillclimb levers)
    seq_shard_residual: bool = True    # Megatron-SP residual (False = pure TP)
    remat_policy: str = "nothing"      # nothing | dots (save matmul outputs)
    kv_cache_bits: int = 16            # 8 = int8 KV cache w/ per-tile scales

    # misc
    act: str = "silu"                      # silu | gelu
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    # the paper's operator
    epitome: EpitomeSettings = EpitomeSettings()

    # per-layer epitome deployment, keyed by param-tree path ("L0/mixer/wq",
    # "L0/ffn/w_gate", ... — the names pim.workloads.lm_layers emits): an
    # EpitomePlan's layer_configs() lands here via get_config(plan=...).
    # Each EpLayerConfig carries {spec, mode, quant, placement} — placement
    # (core.placement.LayerPlacement) says which mesh axes the layer's m/n
    # dims shard over, and drives lm.param_specs / the prepack layout.
    # Entries override the global ``epitome`` settings for their site;
    # unlisted sites fall back.  A tuple of (name, EpLayerConfig) pairs so
    # the config stays hashable (it is a jit static argument).
    layer_config: Tuple[Tuple[str, EpLayerConfig], ...] = ()

    # modality frontend stub ([audio]/[vlm]): inputs are precomputed
    # frame/patch embeddings of this dimension instead of token ids
    embed_inputs: bool = False

    def __post_init__(self):
        if not isinstance(self.layer_config, tuple):
            # accept a dict / list of pairs; normalize to the hashable form
            items = (self.layer_config.items()
                     if isinstance(self.layer_config, dict)
                     else self.layer_config)
            object.__setattr__(self, "layer_config",
                               tuple((str(k), v) for k, v in items))
        if self.n_layers % len(self.pattern) != 0:
            raise ValueError(f"{self.name}: n_layers {self.n_layers} not a "
                             f"multiple of pattern {len(self.pattern)}")
        if len(self.ffn_pattern) not in (1, len(self.pattern)):
            raise ValueError(f"{self.name}: ffn_pattern length mismatch")
        if self.experts_held:
            lo, hi = self.experts_held
            if not 0 <= lo < hi <= self.n_experts:
                raise ValueError(f"{self.name}: experts_held {lo, hi} not "
                                 f"inside [0, {self.n_experts})")

    # -- derived -------------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def n_groups(self) -> int:
        return self.n_layers // len(self.pattern)

    @property
    def full_pattern(self) -> Tuple[Tuple[str, str], ...]:
        fp = self.ffn_pattern * (len(self.pattern) // len(self.ffn_pattern)) \
            if len(self.ffn_pattern) == 1 else self.ffn_pattern
        return tuple(zip(self.pattern, fp))

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return max(1, self.d_model // 16)

    @property
    def held_experts(self) -> Tuple[int, int]:
        """[lo, hi) of the experts this device holds."""
        return self.experts_held or (0, self.n_experts)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def ep(self, M: int, N: int, name: Optional[str] = None) -> EpLayerConfig:
        """EpLayerConfig for a weight of virtual shape (M, N).

        ``name`` is the layer's param-tree path; when it names an entry of
        ``layer_config`` (a plan-driven per-layer design) that entry wins,
        otherwise the global EpitomeSettings plan the site from (M, N)."""
        if name is not None and self.layer_config:
            lc = _layer_config_map(self.layer_config).get(name)
            if lc is not None:
                if lc.spec is not None and (lc.spec.M, lc.spec.N) != (M, N):
                    raise ValueError(
                        f"{self.name}: plan spec for {name} covers "
                        f"({lc.spec.M}, {lc.spec.N}) but the layer is "
                        f"({M}, {N})")
                return lc
        return self.epitome.layer_config(M, N)

    # -- parameter counting (MODEL_FLOPS uses 6*N*D / 6*N_active*D) ----------
    def param_count(self, active_only: bool = False) -> int:
        d, ff, hd = self.d_model, self.d_ff, self.hd
        n = self.vocab * d  # embedding
        if not self.tie_embeddings:
            n += d * self.vocab
        for kind, ffn in self.full_pattern:
            reps = self.n_groups
            if kind in (LayerKind.ATTN.value, LayerKind.ATTN_LOCAL.value):
                n += reps * (d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                             + self.n_heads * hd * d)
            elif kind == LayerKind.MAMBA.value:
                di, ds = self.mamba_d_inner, self.mamba_d_state
                n += reps * (d * 2 * di + di * self.mamba_d_conv
                             + di * (ds * 2 + 2 * self.dt_rank + ds) + di * d)
            elif kind == LayerKind.RWKV.value:
                n += reps * (4 * d * d + d * self.rwkv_lora_decay * 2
                             + 5 * d * self.rwkv_lora_mix * 2)
            if ffn == "moe":
                lo, hi = self.held_experts
                e = hi - lo if not active_only else self.top_k
                n += reps * (e * 3 * d * ff + d * self.n_experts)
            elif ffn == "dense":
                mult = 3 if self.act in ("silu", "gelu") else 2
                n += reps * (mult * d * ff)
            if kind == LayerKind.RWKV.value and ffn == "rwkv_ffn":
                pass
        return n
