"""Jit'd end-to-end WiSparse projection built on the Pallas kernels.

This is the ``backend="pallas"`` path of ``repro.core.sparse_linear``:
  1. fused scoring + per-channel threshold mask (Eq. 4/5) + per-block
     aggregate scores (score_mask kernel),
  2. static-budget top-k block selection (k from the policy's k_max_frac;
     ranks beyond the layer's traced keep_frac get their x zeroed, so the
     per-layer allocation still binds),
  3. block-gather matmul over exactly the kept blocks (sparse_matmul).

All execution state arrives as explicit arguments (``k_frac``,
``token_weights``) — typically from the caller's ``SparsityPolicy``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import sparse_matmul as K


def channel_plan(n: int, block: int = 128):
    """Channel-block geometry of :func:`wisparse_project`: resolved block
    width, zero-padded channel count and block count — the PR 5 contract
    (full-width blocks via padding, never 1-wide fallback blocks).  The
    projection consumes this plan and ``repro.analysis``'s pallas pass
    checks it, so the two cannot drift."""
    blk = min(block, n)
    n_padded = n + (-n % blk)
    return blk, n_padded, n_padded // blk


def wisparse_project(x, w, sp, *, layer=None, block: int = 128,
                     k_frac: float = 1.0, interpret=None,
                     per_seq: bool = False, token_weights=None):
    """x: (..., n); w: (n, *out).  Returns x W with WiSparse block sparsity.

    layer: with ``w`` an ``(L, n, m)`` layer stack, the (traced) layer
    to read; the kernel DMAs that layer's kept tiles from the stack, so
    the stack must tile without padding (:func:`K.reads_in_place`).

    interpret: Pallas interpret mode — ``None`` (default) auto-detects
    from the backend (compiled on TPU, interpreted elsewhere), matching
    ``SparsityPolicy.interpret``.

    token_weights: per-row weights for the shared block-score aggregate
    (the serving engine's active-slot / real-token mask, fused into the
    kernel); explicit None disables weighting."""
    interpret = K._resolve_interpret(interpret)
    if layer is None:
        n, out = w.shape[0], w.shape[1:]
        w2 = w.reshape(n, -1)
    else:
        n, out = w.shape[1], w.shape[2:]
        w2 = w
    lead = x.shape[:-1]
    xf = x.reshape(-1, n)
    blk, n_padded, _ = channel_plan(n, block)
    g = sp["g"]
    pad = n_padded - n
    if pad:
        if layer is not None:
            raise ValueError(
                f"stacked weight {w.shape} does not tile its channels by "
                f"{blk}: padding would copy the whole stack; pass the "
                "layer's slice (see sparse_matmul.reads_in_place)")
        # keep full-width channel blocks on non-divisible dims by
        # zero-padding the channel axis (the old `while n % blk: blk -= 1`
        # fallback degraded to 1-wide blocks on prime dims, destroying
        # both MXU tiles and the block-selection granularity).  Exact:
        # padded channels score |0|*g^a = 0 and multiply zero weight
        # rows, so the tail block just aggregates fewer real channels —
        # the same partial-block semantics as the jnp topk_block path.
        xf = jnp.pad(xf, ((0, 0), (0, pad)))
        w2 = jnp.pad(w2, ((0, pad), (0, 0)))
        g = jnp.pad(g, (0, pad))
        n += pad
    nb = n // blk
    kb = max(1, min(nb, round(nb * k_frac)))

    tw = token_weights
    if tw is not None and tw.size != xf.shape[0]:
        raise ValueError(
            f"token_weights has {tw.size} rows but the projection sees "
            f"{xf.shape[0]} token rows; pass token_weights=None for "
            "dispatch-reshaped projections")
    xm, bs = K.score_mask(xf, g, sp["alpha"], sp["tau"], blk=blk,
                          interpret=interpret, row_weights=tw)
    _, idx = jax.lax.top_k(bs, kb)
    # per-layer budget: zero blocks ranked past keep_frac*nb
    kb_l = jnp.round(sp["keep_frac"] * nb).astype(jnp.int32)
    rank_ok = jnp.arange(kb) < kb_l
    keep_blocks = jnp.zeros((nb,), bool).at[idx].set(rank_ok)
    xm = xm * jnp.repeat(keep_blocks, blk)[None].astype(xm.dtype)
    # entries ranked past the budget keep their own (now-zeroed) block ids,
    # so their kernel contribution is exactly zero

    if per_seq:
        y = K.sparse_matmul_per_seq(xm, w2, jnp.tile(idx, (xf.shape[0], 1)),
                                    layer=layer, blk=blk,
                                    interpret=interpret)
    else:
        y = K.sparse_matmul_shared(xm, w2, idx, layer=layer, blk=blk,
                                   interpret=interpret)
    return y.astype(x.dtype).reshape(lead + out)
