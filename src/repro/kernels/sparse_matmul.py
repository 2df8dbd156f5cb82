"""Pallas TPU block-gather sparse matmul — the WiSparse decode kernel.

TPU adaptation of the paper's TEAL-derived CUDA gather kernels (DESIGN.md
SS3): input channels are grouped into blocks of `blk` (>=128, the lane
width); a scalar-prefetch array lists the kept block ids and the grid
iterates only over those, with ``BlockSpec.index_map`` remapping each grid
step to the kept block's tile of W.  HBM->VMEM DMA traffic and MXU FLOPs
both shrink by (kept blocks / total blocks).  Per-channel WiSparse masks
are applied to x *before* the kernel (elementwise, free on the VPU), so
numerics match the paper's Eq. 5 exactly while skipping stays
block-granular.

Two variants:
  * shared  — one kept-block set for the whole batch (batched serving mode)
  * per_seq — per-sequence block sets (the paper's per-token masks); W tiles
    are re-fetched per sequence, which is exactly the batching cost the
    paper's limitation section describes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLK = 128      # channel-block (lane) size
DEFAULT_MT = 256       # output tile
DEFAULT_BT = 8         # batch tile

# TPU block-shape rule: a block's last two dims must be divisible by
# (SUBLANE, LANE) or equal the array's dims (Mosaic refuses the launch
# otherwise; interpret mode accepts anything)
SUBLANE = 8
LANE = 128

# Per-core VMEM (TPU on-chip vector memory, ~16 MB/core).  Every
# kernel's working set — all live operand/output blocks, double-buffered
# for the DMA pipeline — must fit under this or the launch fails at
# compile time on real hardware (the interpreter hides it on CPU).
VMEM_BYTES = 16 * 1024 * 1024
DOUBLE_BUFFER = 2      # pallas pipelines block DMA against compute

# Each kernel's stable name: its ``pallas_call`` name and the
# ``jax.named_scope`` around the call, so a profiler trace's op carries
# it (in its framework-name stat) and a reader can find the kernel.
SHARED_NAME = "wisparse_sparse_matmul_shared"
PER_SEQ_NAME = "wisparse_sparse_matmul_per_seq"
SCORE_MASK_NAME = "wisparse_score_mask"


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """One operand/output of a kernel launch: its BlockSpec geometry in
    checkable form.  ``index_map`` is the exact callable handed to
    ``pl.BlockSpec`` (block-unit coordinates); ``padded`` is the array
    shape the kernel actually launches over (after any zero-padding).
    A ``None`` block dim is squeezed out of the kernel's ref (one
    element of that dim per grid step)."""
    name: str
    block: Tuple[Optional[int], ...]
    padded: Tuple[int, ...]
    index_map: Callable
    bytes_per_elem: int = 4

    @property
    def dims(self) -> Tuple[int, ...]:
        """Block extent per array dim (a squeezed dim spans 1)."""
        return tuple(1 if d is None else d for d in self.block)

    @property
    def block_bytes(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n * self.bytes_per_elem


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """The launch geometry of one Pallas kernel, built by the same plan
    function the kernel itself consumes — so ``repro.analysis``'s
    pallas passes check exactly what launches, and the two cannot
    drift.  ``tiles`` holds the resolved tile sizes (post ``_fit_tile``)
    keyed by dim name for the divisibility contract checks."""
    kernel: str
    grid: Tuple[int, ...]
    inputs: Tuple[BlockPlan, ...]
    outputs: Tuple[BlockPlan, ...]
    tiles: Tuple[Tuple[str, int, int], ...]   # (dim, tile, padded_size)

    @property
    def blocks(self) -> Tuple[BlockPlan, ...]:
        return self.inputs + self.outputs

    def vmem_bytes(self) -> int:
        """Working-set estimate: every block double-buffered."""
        return DOUBLE_BUFFER * sum(b.block_bytes for b in self.blocks)


def shared_plan(B: int, n: int, m: int, kb: int, *,
                blk: int = DEFAULT_BLK, mt: int = DEFAULT_MT,
                bt: int = DEFAULT_BT, x_bytes: int = 4,
                w_bytes: int = 4) -> KernelPlan:
    """Launch plan for :func:`sparse_matmul_shared` (also its single
    source of geometry truth — the kernel reads tiles/grid from here)."""
    blk = min(blk, n)
    assert n % blk == 0, (n, blk)
    mt = _fit_tile(m, mt, LANE)
    bt = _fit_tile(B, bt, SUBLANE)
    Bp = B + (-B % bt)
    mp = m + (-m % mt)
    grid = (Bp // bt, mp // mt, kb)
    return KernelPlan(
        kernel="sparse_matmul_shared", grid=grid,
        inputs=(
            BlockPlan("x", (bt, blk), (Bp, n),
                      lambda b, j, i, idx: (b, idx[i]), x_bytes),
            BlockPlan("w", (blk, mt), (n, mp),
                      lambda b, j, i, idx: (idx[i], j), w_bytes),
        ),
        outputs=(
            BlockPlan("y", (bt, mt), (Bp, mp),
                      lambda b, j, i, idx: (b, j), 4),
        ),
        tiles=(("B", bt, Bp), ("m", mt, mp), ("n", blk, n)))


def per_seq_plan(B: int, n: int, m: int, kb: int, *,
                 blk: int = DEFAULT_BLK, mt: int = DEFAULT_MT,
                 x_bytes: int = 4, w_bytes: int = 4) -> KernelPlan:
    """Launch plan for :func:`sparse_matmul_per_seq`.  x and y launch
    as ``(B, 1, dim)`` with the batch dim squeezed (``None``) from the
    block, so each sequence's ``(1, blk)`` row block spans a full
    unit dim instead of a 1-row slice of a B-row array."""
    blk = min(blk, n)
    assert n % blk == 0
    mt = _fit_tile(m, mt, LANE)
    mp = m + (-m % mt)
    grid = (B, mp // mt, kb)
    return KernelPlan(
        kernel="sparse_matmul_per_seq", grid=grid,
        inputs=(
            BlockPlan("x", (None, 1, blk), (B, 1, n),
                      lambda b, j, i, idx: (b, 0, idx[b, i]), x_bytes),
            BlockPlan("w", (blk, mt), (n, mp),
                      lambda b, j, i, idx: (idx[b, i], j), w_bytes),
        ),
        outputs=(
            BlockPlan("y", (None, 1, mt), (B, 1, mp),
                      lambda b, j, i, idx: (b, 0, j), 4),
        ),
        tiles=(("m", mt, mp), ("n", blk, n)))


def score_mask_plan(B: int, n: int, *, blk: int = DEFAULT_BLK,
                    x_bytes: int = 4) -> KernelPlan:
    """Launch plan for :func:`score_mask`."""
    blk = min(blk, n)
    assert n % blk == 0
    nb = n // blk
    return KernelPlan(
        kernel="score_mask", grid=(nb,),
        inputs=(
            BlockPlan("x", (B, blk), (B, n),
                      lambda j, ab: (0, j), x_bytes),
            # 2-D: a 1-D operand's XLA tiling (1024) disagrees with
            # Mosaic's (128)
            BlockPlan("g", (1, blk), (1, n), lambda j, ab: (0, j), 4),
            BlockPlan("rw", (B, 1), (B, 1), lambda j, ab: (0, 0), 4),
        ),
        outputs=(
            BlockPlan("xm", (B, blk), (B, n),
                      lambda j, ab: (0, j), x_bytes),
            # one lane-dense (1, 128) row per channel block, the score
            # broadcast across it: a (1, 1) block of an (nb, 1) array
            # breaks the TPU tiling rule
            BlockPlan("bs", (1, LANE), (1, nb * LANE),
                      lambda j, ab: (0, j), 4),
        ),
        tiles=(("n", blk, n),))


@functools.lru_cache(maxsize=1)
def default_interpret() -> bool:
    """Pallas interpret mode unless we are actually on TPU.  Kernel
    callers that pass ``interpret=None`` get this — so forgetting the
    kwarg can no longer silently run the interpreter on real TPUs (or
    crash on CPU with a compiled kernel)."""
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else interpret


def _fit_tile(size: int, want: int, align: int) -> int:
    """Tile for a dim of ``size``: the whole dim when ``size <= want``,
    else the largest multiple of ``align`` in [want/2, want] that
    divides ``size`` (zero padding — e.g. 384 under a 256 lane tile runs
    at 128), else ``want`` with the caller padding up to a multiple.
    ``want`` is a multiple of ``align``; pass ``SUBLANE`` for a block's
    second-to-last dim and ``LANE`` for its last, so every tile meets
    the TPU rule (divisible by (8, 128) or equal to the array dim).
    Never degrades below want/2, so prime dims pad instead of
    collapsing to 1-wide tiles."""
    if size <= want:
        return size
    for t in range(want, max(want // 2, 1) - 1, -align):
        if size % t == 0:
            return t
    return want


def _pad_dim(a, axis: int, tile: int):
    """Pad ``axis`` up to a multiple of ``tile`` (zeros).  Returns the
    padded array and the padded size.  Zero-padding is exact here: extra
    batch rows compute garbage rows that are sliced away, and extra
    output columns only ever multiply against zero weight columns."""
    size = a.shape[axis]
    pad = -size % tile
    if pad:
        pads = [(0, 0)] * a.ndim
        pads[axis] = (0, pad)
        a = jnp.pad(a, pads)
    return a, size + pad


def _acc_kernel(idx_ref, x_ref, w_ref, o_ref):
    """One (batch-tile, out-tile) x kept-block accumulation step."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                          preferred_element_type=jnp.float32)


def sparse_matmul_shared(x, w, block_idx, *, blk: int = DEFAULT_BLK,
                         mt: int = DEFAULT_MT, bt: int = DEFAULT_BT,
                         interpret: Optional[bool] = None):
    """y[b, :] = sum_{kept blocks i} x[b, blk_i] @ w[blk_i, :].

    x: (B, n) already per-channel masked; w: (n, m); block_idx: (kb,) int32
    kept channel-block ids (entries may repeat-pad with 0 iff the padded
    lanes of x were zeroed).  Returns (B, m) float32.

    Tiles shrink only to a clean divisor in [tile/2, tile]; otherwise
    the dim is zero-padded up to a tile multiple and the result sliced
    back — full-width MXU tiles regardless of shape.  (The old fallback
    shrank the tile until it divided, which silently degraded to 1-wide
    tiles on prime dims.)
    """
    interpret = _resolve_interpret(interpret)
    B, n = x.shape
    m = w.shape[1]
    kb = block_idx.shape[0]
    plan = shared_plan(B, n, m, kb, blk=min(blk, n), mt=mt, bt=bt,
                       x_bytes=x.dtype.itemsize, w_bytes=w.dtype.itemsize)
    (_, bt, Bp), (_, mt, mp), (_, blk, _) = plan.tiles
    x, _ = _pad_dim(x, 0, bt)
    w, _ = _pad_dim(w, 1, mt)

    xs, ws = plan.inputs
    (ys,) = plan.outputs
    with jax.named_scope(SHARED_NAME):
        y = pl.pallas_call(
            _acc_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=plan.grid,
                in_specs=[
                    pl.BlockSpec(xs.block, xs.index_map),
                    pl.BlockSpec(ws.block, ws.index_map),
                ],
                out_specs=pl.BlockSpec(ys.block, ys.index_map),
            ),
            out_shape=jax.ShapeDtypeStruct(ys.padded, jnp.float32),
            interpret=interpret,
            name=SHARED_NAME,
        )(block_idx, x, w)
    return y[:B, :m] if (Bp, mp) != (B, m) else y


def sparse_matmul_per_seq(x, w, block_idx, *, blk: int = DEFAULT_BLK,
                          mt: int = DEFAULT_MT,
                          interpret: Optional[bool] = None):
    """Per-sequence kept-block sets (paper's per-token masks).

    x: (B, n) masked; w: (n, m); block_idx: (B, kb) int32.  Returns (B, m).
    Non-divisible output dims shrink to a clean divisor tile or pad
    (see sparse_matmul_shared).
    """
    interpret = _resolve_interpret(interpret)
    B, n = x.shape
    m = w.shape[1]
    kb = block_idx.shape[1]
    plan = per_seq_plan(B, n, m, kb, blk=min(blk, n), mt=mt,
                        x_bytes=x.dtype.itemsize, w_bytes=w.dtype.itemsize)
    (_, mt, mp), (_, blk, _) = plan.tiles
    w, _ = _pad_dim(w, 1, mt)

    xs, ws = plan.inputs
    (ys,) = plan.outputs
    with jax.named_scope(PER_SEQ_NAME):
        y = pl.pallas_call(
            _acc_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=plan.grid,
                in_specs=[
                    pl.BlockSpec(xs.block, xs.index_map),
                    pl.BlockSpec(ws.block, ws.index_map),
                ],
                out_specs=pl.BlockSpec(ys.block, ys.index_map),
            ),
            out_shape=jax.ShapeDtypeStruct(ys.padded, jnp.float32),
            interpret=interpret,
            name=PER_SEQ_NAME,
        )(block_idx, x[:, None], w)[:, 0]
    return y[:, :m] if mp != m else y


def _score_mask_kernel(ab_ref, x_ref, g_ref, w_ref, xm_ref, bs_ref):
    """Fused WiSparse scoring: s=|x|*g^alpha, m=s>=tau, xm=x*m and the
    per-channel-block aggregate score (for block selection).  Each row's
    score contribution is scaled by its weight (serving: 0 for freed
    slots / pad tokens, 1 otherwise; all-ones is bit-identical to the
    unweighted sum).  The mask itself stays per-token (unweighted)."""
    alpha = ab_ref[0]
    tau = ab_ref[1]
    x = x_ref[...]
    g = jnp.maximum(g_ref[...], 1e-12).astype(jnp.float32)
    s = jnp.abs(x.astype(jnp.float32)) * jnp.power(g, alpha)
    keep = s >= tau
    xm_ref[...] = jnp.where(keep, x, jnp.zeros_like(x))
    total = jnp.sum(jnp.where(keep, s, 0.0) * w_ref[...], keepdims=True)
    bs_ref[...] = jnp.broadcast_to(total, bs_ref.shape)


def score_mask(x, g, alpha, tau, *, blk: int = DEFAULT_BLK,
               interpret: Optional[bool] = None, row_weights=None):
    """Returns (x_masked (B,n), block_scores (n//blk,)) — Eq. 4/5 fused.
    row_weights (B,) optionally weights each row's block-score
    contribution (the serving engine's active-slot / real-token mask)."""
    interpret = _resolve_interpret(interpret)
    B, n = x.shape
    plan = score_mask_plan(B, n, blk=min(blk, n),
                           x_bytes=x.dtype.itemsize)
    ((_, blk, _),) = plan.tiles
    nb = n // blk
    ab = jnp.stack([jnp.asarray(alpha, jnp.float32),
                    jnp.asarray(tau, jnp.float32)])
    if row_weights is None:
        rw = jnp.ones((B, 1), jnp.float32)
    else:
        rw = row_weights.reshape(B, 1).astype(jnp.float32)
    xs, gs, rs = plan.inputs
    xo, bo = plan.outputs
    with jax.named_scope(SCORE_MASK_NAME):
        xm, bs = pl.pallas_call(
            _score_mask_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=plan.grid,
                in_specs=[
                    pl.BlockSpec(xs.block, xs.index_map),
                    pl.BlockSpec(gs.block, gs.index_map),
                    pl.BlockSpec(rs.block, rs.index_map),
                ],
                out_specs=[
                    pl.BlockSpec(xo.block, xo.index_map),
                    pl.BlockSpec(bo.block, bo.index_map),
                ],
            ),
            out_shape=[jax.ShapeDtypeStruct(xo.padded, x.dtype),
                       jax.ShapeDtypeStruct(bo.padded, jnp.float32)],
            interpret=interpret,
            name=SCORE_MASK_NAME,
        )(ab, x, g.reshape(1, n), rw)
    return xm, bs.reshape(nb, LANE)[:, 0]
