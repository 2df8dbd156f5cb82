"""Schema + builders for stacked sparsity-parameter trees (the form the
scanned production model consumes, and the abstract inputs the dry-run
lowers with)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import sparse_linear as sl
from repro.core.unstacked import SPARSIFIABLE
from repro.models.params import ParamSpec, abstract_params, logical_axes, stacked


def _rec_schema(d):
    out = {}
    for k, v in d.items():
        if isinstance(v, dict):
            sub = _rec_schema(v)
            if sub:
                out[k] = sub
        elif isinstance(v, ParamSpec) and k in SPARSIFIABLE and len(v.shape) >= 2:
            if len(v.shape) == 3:      # MoE (E, n, m): per-expert g
                gspec = ParamSpec(v.shape[:2], v.axes[:2], dtype="float32")
            else:
                gspec = ParamSpec(v.shape[:1], v.axes[:1], dtype="float32")
            out[k] = {
                "g": gspec,
                "alpha": ParamSpec((), (), dtype="float32"),
                "tau": ParamSpec((), (), dtype="float32"),
                "keep_frac": ParamSpec((), (), dtype="float32"),
            }
    return out


def sparsity_schema(cfg: ModelConfig):
    """List over layer groups of stacked sp ParamSpec trees."""
    from repro.models.model import layer_schema
    groups = []
    for pattern, reps in cfg.layer_groups():
        gd = {}
        for j, kind in enumerate(pattern):
            sub = _rec_schema(layer_schema(cfg, kind,
                                           cross=(cfg.family == "encdec")))
            gd[f"l{j}"] = stacked(sub, reps, "layers")
        groups.append(gd)
    return groups


def abstract_sp(cfg: ModelConfig):
    schema = sparsity_schema(cfg)
    return abstract_params(schema, "float32"), logical_axes(schema)


def default_sp_stacked(params, cfg: ModelConfig, keep_frac: float = 1.0,
                       alpha: float = 1.0):
    """Concrete stacked sp tree from model weights: g = column norms,
    uniform alpha/keep.  tau = -inf keeps every channel through the
    per-channel threshold (Eq. 5) of the ``mask`` and ``pallas``
    backends, so the keep budget alone sets the sparsity — a +inf tau
    would zero every projection input there.  The top-k backends never
    read tau."""
    groups = []
    for gi, (pattern, _reps) in enumerate(cfg.layer_groups()):
        gp = params["groups"][gi]

        def rec(d):
            out = {}
            for k, v in d.items():
                if isinstance(v, dict):
                    sub = rec(v)
                    if sub:
                        out[k] = sub
                elif k in SPARSIFIABLE and hasattr(v, "ndim") and v.ndim >= 3:
                    # stacked weight (reps, n, m) or (reps, E, n, m)
                    if v.ndim == 4:
                        g = jax.vmap(jax.vmap(sl.column_norms))(v)
                    else:
                        g = jax.vmap(sl.column_norms)(v)
                    ones = jnp.ones((v.shape[0],), jnp.float32)
                    out[k] = {"g": g,
                              "alpha": ones * alpha,
                              "tau": ones * -jnp.inf,
                              "keep_frac": ones * keep_frac}
            return out

        groups.append({f"l{j}": rec(gp[f"l{j}"])
                       for j in range(len(pattern))})
    return groups
