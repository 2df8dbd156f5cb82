"""The yardstick's arithmetic: chip peaks, the model FLOPs a token
needs, and the bytes and FLOPs the sparse kernel's work needs.  Counted
from a configuration file (the published sizes as served), never from
the program, so a PR that changes the program cannot change them.

Model FLOPs are a dense count: 2 x the active parameters a token passes
through (MoE experts scaled by experts-per-token over the published
expert count), the LM head included and the embedding lookup not, plus
attention over the live context.  They are the same whatever rung or
kernel does the work.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float       # FLOP/s
    hbm_bw: float           # B/s
    hbm_bytes: int
    source: str


# keyed by JAX's device_kind; a device missing here is an error
PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bw=819e9,
                         hbm_bytes=16 * 1024**3,
                         source='Google Cloud documentation, "TPU v5e"'),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the counts need, read from a configuration file."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int                 # dense MLP width, or one expert's width
    vocab: int
    experts: int = 0        # published count (0: dense MLP)
    experts_per_tok: int = 0
    tied: bool = False

    @classmethod
    def of(cls, cfg: dict) -> "Shape":
        heads = cfg["num_attention_heads"]
        return cls(layers=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                   heads=heads, kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
                   ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                   experts=cfg.get("num_local_experts", 0),
                   experts_per_tok=cfg.get("num_experts_per_tok", 0),
                   tied=bool(cfg.get("tie_word_embeddings", False)))


def projections(s: Shape) -> List[Tuple[str, int, int]]:
    """(role, n_in, n_out) of one layer's matmuls a token passes through
    (one expert's, for an MoE layer)."""
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    return [("attn/wq", s.d, q), ("attn/wk", s.d, kv), ("attn/wv", s.d, kv),
            ("attn/wo", q, s.d), ("mlp/wi_gate", s.d, s.ff),
            ("mlp/wi_up", s.d, s.ff), ("mlp/wo", s.ff, s.d)]


def layer_active_params(s: Shape) -> float:
    """Parameters one token multiplies through in one layer."""
    attn = sum(n * m for r, n, m in projections(s) if r.startswith("attn"))
    ffn = sum(n * m for r, n, m in projections(s) if r.startswith("mlp"))
    if s.experts:
        return attn + s.d * s.experts + ffn * s.experts_per_tok
    return attn + ffn


def active_params(s: Shape) -> float:
    """Per token: every layer's active parameters plus the LM head."""
    return s.layers * layer_active_params(s) + s.d * s.vocab


def step_flops(s: Shape, rows: int, context: float) -> float:
    """FLOPs a step needs for ``rows`` token rows whose attention spans
    ``context`` positions in all."""
    return rows * 2.0 * active_params(s) + 4.0 * context * s.heads \
        * s.head_dim * s.layers


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def least_s(self, p: Peaks) -> Tuple[float, str]:
        """Least time on a chip of peaks ``p``, and which bound sets it."""
        tc, tb = self.flops / p.flops_bf16, self.bytes / p.hbm_bw
        return (tc, "compute") if tc >= tb else (tb, "bytes")

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)


def sparse_matmul_work(s: Shape, rows: int, keep_frac: float,
                       block: int = 128, w_bytes: int = 2, x_bytes: int = 2,
                       y_bytes: int = 2) -> Optional[Work]:
    """The work one step's block-sparse projections need over ``rows``
    token rows at ``keep_frac`` of each projection's input blocks: the
    kept weight blocks read once, the kept part of x read, y written, and
    2 FLOPs per kept multiply-add.  None for MoE layers (no per-expert
    count yet)."""
    if s.experts:
        return None
    total = Work(0.0, 0.0)
    for _role, n, m in projections(s):
        nb = -(-n // block)
        kb = max(1, min(nb, round(nb * keep_frac)))
        k = kb * block
        total = total + Work(2.0 * rows * k * m,
                             k * m * w_bytes + rows * k * x_bytes
                             + rows * m * y_bytes)
    return Work(total.flops * s.layers, total.bytes * s.layers)
