"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch x shape x mesh) cell, against the published peaks
of the cell's ``device_kind`` (``repro.launch.constants``; v5e: 197 TF/s
bf16, 819 GB/s HBM, 50 GB/s per ICI link):

    compute    = HLO_FLOPs            / peak FLOP/s
    memory     = HLO_bytes            / peak HBM B/s
    collective = collective_bytes     / peak ICI B/s per link

``compiled.cost_analysis()`` supplies FLOPs/bytes; collective bytes are
parsed from the *optimized* HLO (``compiled.as_text()`` — the collectives
only exist post-SPMD-partitioning).  For each collective op we count the
result-shape bytes (equal to operand bytes for all-reduce; the standard
proxy for the per-device wire bytes), with all-reduce counted twice
(reduce-scatter + all-gather decomposition).

MODEL_FLOPS = 6*N_active*tokens (train) or 2*N_active*tokens (serve); the
ratio MODEL_FLOPS / HLO_FLOPs exposes remat/causal-overcount/redundancy.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch import constants as C

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %foo = bf16[16,4096]{1,0} all-reduce(...)
_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?\s("
    + "|".join(_COLLECTIVES) + r")[\.\(]")
# tuple-result collectives: = (bf16[..], bf16[..]) all-to-all(
_RE_TUPLE = re.compile(
    r"=\s*\(([^)]*)\)\s*(" + "|".join(_COLLECTIVES) + r")[\.\(]")
_RE_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def executable_costs(compiled) -> "tuple[float, float]":
    """(FLOPs, bytes accessed) from a compiled executable's
    ``cost_analysis()`` dict.  A missing key reads as 0 (the Pallas
    interpreter reports no bytes); a backend that cannot analyse the
    executable raises.  The reusable core of the
    ``benchmarks/roofline_report`` extraction, shared with the
    serving-time per-rung roofline counters (``repro.obs.quality``)."""
    cost = compiled.cost_analysis()
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)))


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind result bytes summed over the module."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if not any(c in line for c in _COLLECTIVES):
            continue
        m = _RE.search(line)
        if m:
            dtype, dims, op = m.groups()
            out[op] += _shape_bytes(dtype, dims)
            continue
        mt = _RE_TUPLE.search(line)
        if mt:
            shapes, op = mt.groups()
            for dtype, dims in _RE_SHAPE.findall(shapes):
                out[op] += _shape_bytes(dtype, dims)
    return out


def wire_bytes(coll: Dict[str, int]) -> float:
    """Per-device wire bytes: all-reduce counts 2x (RS+AG decomposition)."""
    total = 0.0
    for k, v in coll.items():
        total += 2 * v if k == "all-reduce" else v
    return total


def active_matmul_params(cfg: ModelConfig) -> float:
    """N_active: per-token matmul params (MoE scaled by k/E), head included."""
    from repro.models import model as M
    from repro.models.params import _flatten

    schema = M.model_schema(cfg)
    total = 0.0
    for path, spec in _flatten(schema)[0]:
        keys = [str(getattr(p, "key", "")) for p in path]
        name = keys[-1]
        if name in ("embed", "lm_head"):
            continue                      # head counted separately below
        if len(spec.shape) < 2:
            continue
        shape = spec.shape
        # drop the stacked-layers dim from the product, multiply back reps
        if keys and any(k.startswith("l") and k[1:].isdigit() for k in keys):
            reps, shape = shape[0], shape[1:]
        else:
            reps = 1
        p = float(np.prod(shape)) * reps
        if len(shape) == 3:               # MoE expert weight (E, n, m)
            p *= cfg.num_experts_per_tok / cfg.num_experts
        total += p
    total += float(cfg.vocab_size) * cfg.d_model   # unembedding matmul
    return total


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    n = active_matmul_params(cfg)
    if shape.mode == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.mode == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch          # decode: one token per seq


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float           # per-device (XLA analyses the SPMD module)
    hlo_bytes: float
    coll_bytes: float          # per-device wire bytes
    model_flops_total: float
    device_kind: str           # whose peaks bound the terms

    @property
    def peaks(self) -> C.ChipPeaks:
        return C.peaks(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / self.peaks.flops_bf16

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_bytes / self.peaks.ici_bw_per_link

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time: max of the three terms (full overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        total_hlo = self.hlo_flops * self.chips
        return self.model_flops_total / max(total_hlo, 1.0)

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the roofline step time."""
        return (self.model_flops_total
                / (self.step_time_s * self.chips * self.peaks.flops_bf16))

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "device_kind": self.device_kind,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops_total,
            "useful_ratio": self.useful_flops_ratio,
            "mfu": self.mfu,
        }
