"""WiSparse sparse-projection dispatch.

``project(x, w, sp, policy=...)`` is the single choke point through which
every linear layer in the model zoo runs.  ``sp`` carries the per-layer
WiSparse parameters (all traced arrays so they can ride through
``lax.scan`` over a stacked layer group):

    g          (n_in,)  precomputed weight-column L2 norms  (paper Eq. 4)
    alpha      ()       layer exponent alpha_l               (paper Eq. 4)
    tau        ()       inference threshold tau_l            (paper Eq. 5)
    keep_frac  ()       keep ratio 1 - p_l (gather backends)

The *static* execution config is an explicit :class:`SparsityPolicy`
value (``repro.sparsity``): which backend runs where (globally, per
layer-role, per block range), the static top-k bound, the Pallas block
size/interpret flag (``interpret=None`` auto-detects — compiled on TPU,
interpreted elsewhere), and the optional calibration capture hook.  Because
backends differ in lowering, the policy is a hashable static jit argument
— never ambient state — so concurrent engines with different policies can
never share a trace.  ``policy=None`` means dense execution; the
thread-local ``sparsity_mode``/``capture_inputs``/``token_weights``
contexts that used to fill unspecified state are gone (see the README
migration notes).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.sparsity import CaptureSink, SparsityPolicy, VALID_BACKENDS

__all__ = [
    "SparsityPolicy", "CaptureSink", "VALID_BACKENDS", "DENSE", "project",
    "LayerWeight", "scores", "column_norms", "default_sp",
]

# the default execution when no policy is passed: plain dense matmuls
DENSE = SparsityPolicy.dense()


@dataclasses.dataclass(frozen=True)
class LayerWeight:
    """One layer's weight as the scan over a stacked layer group holds
    it: the whole ``(L, n_in, *out)`` stack and the traced layer index.
    The ``pallas`` backend hands both to the kernel, which reads the
    kept tiles of that layer straight from the stack; every other
    consumer takes :meth:`sliced`, the layer's own ``(n_in, *out)``
    weight, as the scan's ``xs`` would have handed it."""
    stack: Any
    index: Any

    def sliced(self):
        return jax.lax.dynamic_index_in_dim(self.stack, self.index, 0,
                                            keepdims=False)


# ---------------------------------------------------------------------------
# Scoring
# ---------------------------------------------------------------------------

def _saliency(xf, sp, tok_w=None):
    """Per-channel shared saliency over all token rows (optionally
    weighted by the serving engine's token weights)."""
    s = scores(xf, sp["g"], sp["alpha"])                 # (rows, n_in)
    if tok_w is None:
        return s.mean(axis=0)
    if tok_w.size != s.shape[0]:
        # a projection whose rows aren't the step's tokens (e.g. an
        # expert-dispatched layout) must opt out via token_weights=None
        # — mis-aligned weights would silently bias the channel set
        raise ValueError(
            f"token_weights has {tok_w.size} rows but the projection sees "
            f"{s.shape[0]} token rows; pass token_weights=None for "
            "dispatch-reshaped projections")
    twf = tok_w.reshape(-1, 1).astype(jnp.float32)
    return (s * twf).sum(axis=0) / jnp.maximum(twf.sum(), 1.0)


def _matmul(x, w):
    """x (..., n_in) @ w (n_in, *out).

    Output dtype == input dtype: a f32 preferred_element_type makes XLA
    hoist the bf16 convert past the row-parallel all-reduce, doubling every
    TP activation psum on the wire (measured on the TP mesh dry-runs; see
    benchmarks/roofline_report.py).  The MXU accumulates in f32 internally
    either way."""
    return jax.lax.dot_general(
        x.reshape(-1, x.shape[-1]), w.reshape(w.shape[0], -1),
        (((1,), (0,)), ((), ())),
        preferred_element_type=x.dtype,
    ).reshape(x.shape[:-1] + w.shape[1:])


def scores(x, g, alpha):
    """Weight-aware importance score  s_i = |x_i| * g_i^alpha  (Eq. 4)."""
    gf = jnp.maximum(g.astype(jnp.float32), 1e-12)
    return jnp.abs(x.astype(jnp.float32)) * jnp.power(gf, alpha)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def project(x, w, sp: Optional[dict] = None, row_parallel: bool = False, *,
            policy: Optional[SparsityPolicy] = None,
            role: Optional[str] = None, token_weights=None):
    """Dispatch one projection under ``policy`` (per-block depth ranges
    are already folded in by the model's scan driver; only role overrides
    remain to resolve here).  ``policy=None`` runs dense.

    ``w`` is an array ``(n_in, *out)`` or a :class:`LayerWeight`, which
    only the ``pallas`` backend reads in place.

    row_parallel statically marks weights whose *input* dim is
    model-sharded (o_proj/down_proj/out_proj).  The top-k gather backends
    then select a balanced per-shard channel budget so the gather stays
    local instead of lowering to a cross-shard masked-gather + all-reduce
    (see ``_topk_gather_grouped``).
    """
    if policy is None:
        policy = DENSE
    backend = policy.backend_at(role=role)
    if isinstance(w, LayerWeight) and (sp is None or backend != "pallas"):
        w = w.sliced()
    if policy.capture is not None:
        policy.capture.record(w, x)
    if sp is None or backend == "off":
        return _matmul(x, w)
    if backend == "mask":
        s = scores(x, sp["g"], sp["alpha"])
        m = (s >= sp["tau"]).astype(x.dtype)           # Eq. 5
        return _matmul(x * m, w)
    if backend in ("topk_shared", "topk_block"):
        groups = 1
        if row_parallel:
            from repro.distributed.sharding import current_ctx
            ctx = current_ctx()
            if ctx is not None:
                sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
                g = sizes.get("model", 1)
                if w.shape[0] % g == 0:
                    groups = g
        return _topk_gather(x, w, sp, policy, backend=backend, groups=groups,
                            token_weights=token_weights)
    if backend == "pallas":
        from repro.kernels import ops as kops
        layer = None
        if isinstance(w, LayerWeight):
            w, layer = w.stack, w.index
        return kops.wisparse_project(x, w, sp, layer=layer,
                                     block=policy.block,
                                     k_frac=policy.k_max_frac,
                                     interpret=policy.interpret,
                                     token_weights=token_weights)
    raise ValueError(    # unreachable: policies validate at construction
        f"unknown sparsity backend {backend}")


def _topk_gather(x, w, sp, policy, *, backend: Optional[str] = None,
                 groups: int = 1, token_weights=None):
    """Shared-mask gather path: aggregate weight-aware scores over all
    tokens in the call, keep the top k_max channels (static), mask ranks
    beyond the layer's own traced keep_frac, gather the corresponding
    weight rows and run a compact matmul.  FLOPs ~ k/n of dense.

    ``policy`` supplies the static knobs (k_max_frac, block).

    groups > 1: balanced per-shard selection for row-parallel weights —
    the channel budget is split evenly across `groups` contiguous input
    slices (= the weight's model shards) so every gather is shard-local."""
    backend = backend or policy.backend
    if groups > 1:
        return _topk_gather_grouped(x, w, sp, policy, groups,
                                    token_weights=token_weights)
    n_in = w.shape[0]
    xf = x.reshape(-1, n_in)
    sal = _saliency(xf, sp, token_weights)                       # (n_in,)
    if backend == "topk_block":
        b = policy.block
        nb = max(n_in // b, 1)
        if n_in % b:
            pad = nb * b + b - n_in
            sal = jnp.pad(sal, (0, pad))
            nb += 1
        blk = sal.reshape(nb, -1).sum(axis=1)
        kb_max = max(1, round(nb * policy.k_max_frac))
        _, bidx = jax.lax.top_k(blk, kb_max)
        idx = (bidx[:, None] * b + jnp.arange(b)[None, :]).reshape(-1)
        idx = jnp.minimum(idx, n_in - 1)
        k_l = jnp.round(sp["keep_frac"] * nb).astype(jnp.int32)
        rank_ok = (jnp.arange(kb_max) < k_l)
        rank_ok = jnp.repeat(rank_ok, b)
    else:
        k_max = max(1, round(n_in * policy.k_max_frac))
        _, idx = jax.lax.top_k(sal, k_max)
        k_l = jnp.round(sp["keep_frac"] * n_in).astype(jnp.int32)
        rank_ok = jnp.arange(k_max) < k_l
    ws = jnp.take(w.reshape(n_in, -1), idx, axis=0)              # (k, m)
    xs = jnp.take(xf, idx, axis=1) * rank_ok.astype(x.dtype)
    y = jax.lax.dot_general(xs, ws, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return y.astype(x.dtype).reshape(x.shape[:-1] + w.shape[1:])


def _topk_gather_grouped(x, w, sp, policy, groups: int, token_weights=None):
    """Balanced grouped selection: reshape the input-channel dim into
    (groups, n/groups), pick top-(k/groups) per group, gather within each
    group (shard-local for model-sharded weight rows), contract per group
    and sum.  Keeps the same global budget; selection is per-shard-balanced
    (accuracy delta measured in benchmarks/table1_accuracy.py)."""
    n_in = w.shape[0]
    G = groups
    ng = n_in // G
    xf = x.reshape(-1, n_in)
    sal = _saliency(xf, sp, token_weights).reshape(G, ng)
    k_max = max(1, round(ng * policy.k_max_frac))
    _, idx = jax.lax.top_k(sal, k_max)                    # (G, k)
    k_l = jnp.round(sp["keep_frac"] * ng).astype(jnp.int32)
    rank_ok = (jnp.arange(k_max) < k_l)[None, :]          # (1, k)
    from repro.distributed.sharding import constrain
    wg = constrain(w.reshape(G, ng, -1), "grouped_in", None, None)
    ws = jnp.take_along_axis(wg, idx[:, :, None], axis=1)  # (G, k, m)
    xg = xf.reshape(-1, G, ng)
    xs = jnp.take_along_axis(xg, idx[None], axis=2)        # (B, G, k)
    xs = xs * rank_ok[None].astype(xs.dtype)
    y = jnp.einsum("bgk,gkm->bm", xs, ws,
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype).reshape(x.shape[:-1] + w.shape[1:])


def column_norms(w) -> jnp.ndarray:
    """g_i = ||W[:, i]||_2 over all output dims; w: (n_in, *out)."""
    wf = w.reshape(w.shape[0], -1).astype(jnp.float32)
    return jnp.sqrt(jnp.sum(wf * wf, axis=1))


def default_sp(w) -> dict:
    """Dense-equivalent sparsity params (alpha=0, tau=-inf, keep=1)."""
    return {
        "g": column_norms(w),
        "alpha": jnp.zeros((), jnp.float32),
        "tau": jnp.full((), -jnp.inf, jnp.float32),
        "keep_frac": jnp.ones((), jnp.float32),
    }
