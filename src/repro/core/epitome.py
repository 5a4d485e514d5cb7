"""Epitome: the paper's compact neural operator (EPIM §2.2, Eq. 1, Fig. 1).

An epitome ``E`` is a small learnable tensor; a sampler repeatedly samples
(possibly overlapping) patches of ``E`` and concatenates them to reconstruct
a full weight matrix ``W``.  EPIM's hardware mapping [13] views every weight
in *crossbar space*: rows = ``c_in * p * q`` (word lines), cols = ``c_out``
(bit lines) — i.e. a 2-D matrix.  The paper's own notation ("1024x256
epitome" == c_in*p*q x c_out) is already this 2-D view, so the operator here
is defined on 2-D matrices; a convolution is epitomized through its im2col
matrix (see `layers.EpConv`).

TPU adaptation (DESIGN.md §2): patch offsets are *static* (trace time), so
the IFAT/IFRT/OFAT index tables of the PIM datapath become compile-time
index maps — reconstruction is pure gather, differentiable by scatter-add.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# ---------------------------------------------------------------------------
# Spec
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EpitomeSpec:
    """Static description of one epitomized weight matrix.

    W_virtual is (M, N); the epitome parameter is (m, n); the sampler tiles W
    with a grid of (gm x gn) patches of size (bm, bn), patch (i, j) sampled
    from E at (row_off[i], col_off[j]).  Offsets are evenly spread across the
    epitome so every cell of E is used and adjacent patches overlap whenever
    m < gm*bm (parameter sharing with overlaps — the paper's Fig. 1).
    """

    M: int                    # virtual fan-in   (c_in*p*q on PIM word lines)
    N: int                    # virtual fan-out  (c_out on PIM bit lines)
    m: int                    # epitome rows
    n: int                    # epitome cols
    bm: int = 256             # patch rows  (crossbar word-line count / MXU tile)
    bn: int = 256             # patch cols  (crossbar bit-line count / MXU tile)

    def __post_init__(self):
        if not (0 < self.m <= self.M and 0 < self.n <= self.N):
            raise ValueError(f"epitome ({self.m},{self.n}) must fit in ({self.M},{self.N})")
        if self.bm > self.m or self.bn > self.n:
            # patch cannot exceed the epitome; clamp is the caller's job
            raise ValueError(f"patch ({self.bm},{self.bn}) exceeds epitome ({self.m},{self.n})")

    # -- grid ---------------------------------------------------------------
    @property
    def gm(self) -> int:
        return -(-self.M // self.bm)

    @property
    def gn(self) -> int:
        return -(-self.N // self.bn)

    @property
    def compression_rate(self) -> float:
        return (self.M * self.N) / (self.m * self.n)

    # -- offsets (static python ints; these ARE the IFRT/IFAT/OFAT content) --
    def row_offsets(self) -> np.ndarray:
        return _spread_offsets(self.m, self.bm, self.gm)

    def col_offsets(self) -> np.ndarray:
        return _spread_offsets(self.n, self.bn, self.gn)

    # -- index maps: virtual coordinate -> epitome coordinate ----------------
    def row_index_map(self) -> np.ndarray:
        return _index_map(self.M, self.bm, self.row_offsets())

    def col_index_map(self) -> np.ndarray:
        return _index_map(self.N, self.bn, self.col_offsets())

    # -- channel wrapping (paper §5.3) ---------------------------------------
    def unique_col_blocks(self) -> Tuple[np.ndarray, np.ndarray]:
        """(unique_offsets, inverse) — column blocks with equal offset are
        byte-identical in W, so ``y`` needs only the unique ones (Eq. 8-9)."""
        offs = self.col_offsets()
        uniq, inverse = np.unique(offs, return_inverse=True)
        return uniq, inverse

    @property
    def wrap_factor(self) -> float:
        """r: how many x fewer output-column blocks need computing."""
        uniq, _ = self.unique_col_blocks()
        return self.gn / max(1, len(uniq))


def _spread_offsets(m: int, bm: int, g: int) -> np.ndarray:
    """g patch offsets evenly spread over [0, m-bm] (all ints, static)."""
    if g <= 1 or m == bm:
        return np.zeros(g, dtype=np.int64)
    span = m - bm
    # Evenly spread; duplicates appear exactly when span < g-1 — that
    # duplication IS the paper's channel wrapping when it happens on cols.
    return np.round(np.linspace(0, span, g)).astype(np.int64)


def _index_map(M: int, bm: int, offsets: np.ndarray) -> np.ndarray:
    """idx[u] = epitome row for virtual row u (static gather table)."""
    idx = np.empty(M, dtype=np.int64)
    for i, off in enumerate(offsets):
        lo = i * bm
        hi = min(M, lo + bm)
        idx[lo:hi] = off + np.arange(hi - lo)
    return idx


# ---------------------------------------------------------------------------
# Planning
# ---------------------------------------------------------------------------
def plan_epitome(
    M: int,
    N: int,
    target_cr: float,
    *,
    patch: Tuple[int, int] = (256, 256),
    align: int = 128,
    wrap_cols: bool = True,
) -> Optional[EpitomeSpec]:
    """Choose an epitome shape for a (M, N) weight at roughly ``target_cr``.

    PIM-aware shaping (paper §4.1): m is a multiple of the patch row count
    and n a multiple of the patch col count whenever possible, so sampled
    patches fully utilize crossbars / MXU tiles.  ``wrap_cols=True`` prefers
    n == bn (single column patch) which maximizes output channel wrapping.

    Returns None when the layer is too small to compress (epitome would not
    be smaller than the weight) — the layer then stays dense, mirroring the
    paper keeping small ResNet layers un-epitomized.
    """
    if target_cr <= 1.0:
        return None
    bm = min(patch[0], M)
    bn = min(patch[1], N)
    # round patch down to alignment when the dim allows it
    if M >= align:
        bm = max(align, (bm // align) * align)
    if N >= align:
        bn = max(align, (bn // align) * align)
    total = M * N
    budget = total / target_cr

    # candidate n: wrap-first (n = bn), else multiples of bn
    n_candidates = [bn] if wrap_cols else []
    k = 1
    while k * bn <= N:
        n_candidates.append(k * bn)
        k += 1
    n_candidates = sorted(set(n_candidates))

    best = None
    best_err = math.inf
    for n in n_candidates:
        m_f = budget / n
        # m multiples of bm, at least bm, at most M
        for m in {max(bm, int(m_f // bm) * bm), max(bm, -(-int(m_f) // bm) * bm)}:
            m = min(m, M)
            if m * n >= total:      # not actually smaller
                continue
            spec = EpitomeSpec(M=M, N=N, m=m, n=n, bm=bm, bn=bn)
            err = abs(spec.compression_rate - target_cr) / target_cr
            if err < best_err:
                best, best_err = spec, err
    return best


# ---------------------------------------------------------------------------
# Reconstruction & matmul references (pure jnp)
# ---------------------------------------------------------------------------
def reconstruct(E: Array, spec: EpitomeSpec) -> Array:
    """Materialize the virtual weight W (M, N) from the epitome (m, n)."""
    ri = jnp.asarray(spec.row_index_map())
    ci = jnp.asarray(spec.col_index_map())
    return E[ri[:, None], ci[None, :]]


def reconstruct_unique(E: Array, spec: EpitomeSpec) -> Tuple[Array, np.ndarray]:
    """Materialize only the unique column blocks (channel wrapping, §5.3).

    Returns (W_unique of shape (M, n_unique_cols), inverse block index)."""
    uniq, inverse = spec.unique_col_blocks()
    ri = jnp.asarray(spec.row_index_map())
    cols = []
    for off in uniq:
        width = min(spec.bn, spec.N)  # last block may be ragged; keep bn, trim later
        cols.append(jnp.arange(off, off + width))
    ci = jnp.concatenate([jnp.asarray(c) for c in cols])
    W_u = E[ri[:, None], ci[None, :]]
    return W_u, inverse


def epitome_matmul_ref(x: Array, E: Array, spec: EpitomeSpec) -> Array:
    """y = x @ W(E): reference without wrapping (full reconstruction)."""
    W = reconstruct(E, spec)
    return x @ W.astype(x.dtype)


def wrapped_matmul(x: Array, E: Array, spec: EpitomeSpec) -> Array:
    """y = x @ W(E) computing only unique column blocks, then expanding.

    The paper's output channel wrapping (Eq. 9): identical column blocks of W
    produce identical output columns, so compute c cols once and reuse r
    times.  On TPU the expansion is a cheap static `take`; FLOPs fall by r.
    """
    uniq, inverse = spec.unique_col_blocks()
    if len(uniq) == spec.gn:
        return epitome_matmul_ref(x, E, spec)     # nothing wraps
    W_u, inverse = reconstruct_unique(E, spec)
    y_u = x @ W_u.astype(x.dtype)                 # (..., n_unique*bn)
    # expand block-wise: output col block j = unique block inverse[j]
    pieces = []
    for j in range(spec.gn):
        lo = int(inverse[j]) * spec.bn
        width = min(spec.bn, spec.N - j * spec.bn)
        pieces.append(jax.lax.slice_in_dim(y_u, lo, lo + width, axis=-1))
    return jnp.concatenate(pieces, axis=-1)


def fold_rows(x: Array, spec: EpitomeSpec) -> Array:
    """IFRT analogue: sum each virtual input row into its epitome row,
    (..., M) -> (..., m).

    Virtual row block g (rows g*bm up to M) lands on epitome rows
    ``row_offsets()[g]`` on.  The offsets are static, so each block is a
    static slice of x padded with zeros to the epitome's width at its
    offset, and the blocks are added in ascending g: the order of
    ``jax.ops.segment_sum`` and of the fused-fold kernel, with no scatter
    (the zeros the pads add are exact).  On the CPU its bits equal
    ``segment_sum``'s; on a TPU, XLA may keep a fused chain of bf16 adds
    in f32 and round once."""
    lead = [(0, 0)] * (x.ndim - 1)
    out = None
    for g, off in enumerate(spec.row_offsets()):
        block = jax.lax.slice_in_dim(x, g * spec.bm,
                                     min(spec.M, (g + 1) * spec.bm), axis=-1)
        off = int(off)
        block = jnp.pad(block, lead + [(off, spec.m - off - block.shape[-1])])
        out = block if out is None else out + block
    return out


def folded_matmul(x: Array, E: Array, spec: EpitomeSpec) -> Array:
    """Epitome-space matmul (beyond-paper TPU optimization; DESIGN.md §2).

    Because reconstruction is linear weight *sharing*, the matmul factors
    through the compressed space:

        y[t, j] = sum_i x[t, i] * E[rmap[i], cmap[j]]
                = sum_u ( sum_{i in rmap^-1(u)} x[t, i] ) * E[u, cmap[j]]

    i.e.  y = fold(x) @ E, then a static column gather.  Cost falls from
    T*M*N to T*M (fold) + T*m*n (matmul) + T*N (expand): both the FLOP and
    HBM-byte terms shrink by ~the compression rate.  Output channel wrapping
    (§5.3) is subsumed: duplicated column blocks become repeated gather
    indices.  Exact (no approximation); gradients flow by transposition.
    """
    cmap = jnp.asarray(spec.col_index_map())
    y_ep = fold_rows(x, spec) @ E.astype(x.dtype)     # (..., n)
    return jnp.take(y_ep, cmap, axis=-1)              # (..., N)


# ---------------------------------------------------------------------------
# Overlap statistics (drive the quantization range, paper Fig. 2c / Eq. 4-5)
# ---------------------------------------------------------------------------
def overlap_counts(spec: EpitomeSpec) -> np.ndarray:
    """cnt[u, v] = number of sampled patches covering epitome cell (u, v).

    Separable: cnt = row_cnt (x) col_cnt.  Center cells of the epitome are
    covered by more patches — the paper's observation that "center parts are
    repeated more frequently"."""
    def axis_counts(m, bm, offsets, M):
        c = np.zeros(m, dtype=np.int64)
        for i, off in enumerate(offsets):
            # last virtual patch may be ragged: only the used rows count
            used = min(bm, M - i * bm)
            c[off:off + used] += 1
        return c

    rc = axis_counts(spec.m, spec.bm, spec.row_offsets(), spec.M)
    cc = axis_counts(spec.n, spec.bn, spec.col_offsets(), spec.N)
    return rc[:, None] * cc[None, :]


def overlap_mask(spec: EpitomeSpec) -> np.ndarray:
    """Boolean mask of the 'overlap' (high-repetition) region of E.

    Cells whose coverage count exceeds the minimum positive coverage are the
    paper's green "center parts"; the rest are the blue "others"."""
    cnt = overlap_counts(spec)
    pos = cnt[cnt > 0]
    if pos.size == 0:
        return np.zeros_like(cnt, dtype=bool)
    return cnt > pos.min()


# ---------------------------------------------------------------------------
# Conversion from a dense weight (epitome designer, Fig. 2a)
# ---------------------------------------------------------------------------
def epitomize_dense(W: Array, spec: EpitomeSpec) -> Array:
    """Least-squares init of E from a dense W: each epitome cell becomes the
    mean of every virtual cell that samples it (the scatter-add adjoint of
    `reconstruct`, normalized by coverage)."""
    ri = jnp.asarray(spec.row_index_map())
    ci = jnp.asarray(spec.col_index_map())
    flat_idx = ri[:, None] * spec.n + ci[None, :]
    sums = jnp.zeros(spec.m * spec.n, W.dtype).at[flat_idx.reshape(-1)].add(W.reshape(-1))
    cnt = jnp.asarray(np.maximum(overlap_counts(spec), 1), W.dtype)
    return sums.reshape(spec.m, spec.n) / cnt


def init_epitome(key: Array, spec: EpitomeSpec, dtype=jnp.float32, scale: Optional[float] = None) -> Array:
    """Fan-in-scaled init; fan-in is the *virtual* M so the reconstructed W
    has the same statistics a dense layer would have."""
    if scale is None:
        scale = 1.0 / math.sqrt(spec.M)
    return (jax.random.normal(key, (spec.m, spec.n)) * scale).astype(dtype)
