"""How ``correct`` is decided: the served tokens against the plain
reference (``bench/reference.py``), by the widest gap by which a served
token's reference logit lies below the reference's best.

Every request's rows are what the program processed: the prompt chunks
it prefilled, then each served token but the last.  On a dense rung
requests are independent, so the reference runs over a sample of the
finished requests drawn from the seed, the longest among them, or over
every finished request where the cell's limits file asks for it
(``"requests": "all"``).  With random weights a request's logits barely
move from one served token to the next, so each request gives about one
independent reading of how near its top two logits lie, and a cell
whose requests are short needs many of them.  On a sparse rung a step's
rows choose their channel blocks together, so the reference replays
every row of every step since the engine started and compares every
finished request.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from bench import reference as ref

SAMPLE_TOKENS = 512
SAMPLE_MAX = 8


@dataclasses.dataclass
class Replay:
    rows: ref.Rows
    compare: np.ndarray             # row indices
    served: np.ndarray              # served token at each compared row
    requests: int
    tokens: int


def _rows_of(client, r, sparse: bool):
    """(tokens, steps) of the rows the program processed for ``r``."""
    C = client.chunk
    toks = [r.prompt[:r.prefilled]]
    steps = np.full(r.prefilled, -1, np.int32)
    if sparse:
        for j, si in enumerate(r.chunk_steps):
            if not client.steps[si].dense:
                steps[j * C:min((j + 1) * C, r.prefilled)] = si
    if r.tokens:
        toks.append(np.asarray(r.tokens[:-1], np.int32))
        later = np.asarray(r.steps[1:], np.int32) if sparse \
            else np.full(len(r.tokens) - 1, -1, np.int32)
        steps = np.concatenate([steps, later])
    return np.concatenate(toks).astype(np.int32), steps


def sample(client, seed: int, every: bool) -> List:
    done = [r for r in client.reqs if r.finished_at is not None and r.tokens]
    if every or not done:
        return done
    rng = np.random.default_rng([seed, 4])
    longest = max(done, key=lambda r: len(r.tokens))
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    out, n = [longest], len(longest.tokens)
    for r in rest:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(r)
        n += len(r.tokens)
    return out


def replay(client, seed: int, sparse: bool, every: bool = False) -> Replay:
    compared = sample(client, seed, sparse or every)
    pool = [r for r in client.reqs if r.prefilled] if sparse else compared
    want = {id(r) for r in compared}
    toks, steps, compare, served = [], [], [], []
    off = 0
    for r in pool:
        t, s = _rows_of(client, r, sparse)
        if id(r) in want:
            P = len(r.prompt)
            compare.append(off + P - 1 + np.arange(len(r.tokens)))
            served.append(np.asarray(r.tokens, np.int32))
        toks.append(t)
        steps.append(s)
        off += len(t)
    rows = ref.build_rows(toks, steps if sparse else None,
                          n_steps=len(client.steps))
    return Replay(rows, np.concatenate(compare).astype(np.int32),
                  np.concatenate(served), len(compared),
                  int(sum(len(r.tokens) for r in compared)))


def widest_gap(cfg: dict, rung: dict, seed: int, rp: Replay, max_len: int,
               control: bool = False) -> dict:
    """{"widest_gap": ...}, and with ``control`` the control's reading
    on the same rows: the fp8 reference's first choices, judged by the
    float32 reference."""
    out = {}
    ctrl: Optional[np.ndarray] = None
    if control:
        m8 = ref.Model(cfg, rung, seed, precision="fp8")
        ctrl = ref.control_tokens(m8, rp.rows, rp.compare, max_len)
        del m8
        ref.free_device_memory()
    m = ref.Model(cfg, rung, seed)
    g = ref.gaps(m, rp.rows, rp.compare, rp.served, max_len, ctrl)
    out["widest_gap"] = float(g[:, 0].max())
    if control:
        out["control_widest_gap"] = float(g[:, 1].max())
    return out
