"""The benchmark's client: sends a mix's requests to the engine, drives
``Engine.step()``, and keeps its own clock and records — when each
request was due and sent, when each of its tokens arrived and in which
engine step, and what each step processed.  End-to-end metrics come
from these records, not from the engine's statistics.

What each prefill step processed is mirrored here from the engine's
documented order (admission is first come first served, one chunk of
the oldest admitted prompt per prefill step), and checked against the
first token that the last chunk of each prompt returns.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from bench import traffic as T

clock = time.perf_counter


@dataclasses.dataclass
class Req:
    index: int
    prompt: np.ndarray
    max_new: int
    due: float                          # host clock
    sent: float = 0.0
    rid: int = -1
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    steps: List[int] = dataclasses.field(default_factory=list)
    chunk_steps: List[int] = dataclasses.field(default_factory=list)
    prefilled: int = 0                  # prompt tokens processed
    finished_at: Optional[float] = None

    @property
    def first(self) -> Optional[float]:
        return self.times[0] if self.times else None


@dataclasses.dataclass
class Step:
    action: str                         # "prefill" | "decode"
    t0: float
    t1: float
    rows: int                           # token rows with real work
    context: float                      # summed attention context of rows
    dense: bool = True                  # a prefill chunk under the dense phase


class Client:
    """Drives one engine under one mix."""

    def __init__(self, engine, mix: dict, seed: int, vocab: int,
                 prefill_dense_frac: float, sparse: bool):
        self.eng = engine
        self.mix = mix
        self.seed = seed
        self.vocab = vocab
        self.dense_frac = prefill_dense_frac
        self.sparse = sparse
        self.chunk = engine.ecfg.prefill_chunk
        self.slots = engine.ecfg.max_slots
        self.reqs: List[Req] = []
        self.by_rid: Dict[int, Req] = {}
        self.steps: List[Step] = []
        self.pending_prefill: List[Req] = []     # sent, prompt not done
        self.schedule_errors = 0
        self._emitted: List[int] = []
        self._finished: List[Req] = []

    # -- sending ---------------------------------------------------------
    def send(self, planned: T.Planned, due: float) -> Req:
        r = Req(planned.index, planned.prompt, planned.max_new, due)
        r.sent = clock()
        rs = self.eng.submit(planned.prompt, planned.max_new,
                             on_token=self._on_token,
                             on_finish=self._on_finish)
        r.rid = rs.request.request_id
        self.reqs.append(r)
        self.by_rid[r.rid] = r
        self.pending_prefill.append(r)
        return r

    def _on_token(self, rid: int, tok: int) -> None:
        r = self.by_rid[rid]
        r.tokens.append(int(tok))
        r.times.append(clock())
        r.steps.append(len(self.steps))
        self._emitted.append(rid)

    def _on_finish(self, rs) -> None:
        r = self.by_rid[rs.request.request_id]
        r.finished_at = clock()
        self._finished.append(r)

    # -- stepping ----------------------------------------------------------
    def has_work(self) -> bool:
        return self.eng.scheduler.has_work()

    def step(self) -> List[Req]:
        """One engine step; returns the requests that finished in it."""
        self._emitted = []
        self._finished = []
        t0 = clock()
        action = self.eng.step()
        t1 = clock()
        idx = len(self.steps)
        if action == "prefill":
            r = self.pending_prefill[0]
            off = r.prefilled
            real = min(self.chunk, len(r.prompt) - off)
            dense_end = int(np.ceil(len(r.prompt) * self.dense_frac))
            r.chunk_steps.append(idx)
            r.prefilled = off + real
            ctx = float(np.sum(np.arange(off, off + real) + 1))
            self.steps.append(Step(action, t0, t1, real, ctx,
                                   dense=not self.sparse or off < dense_end))
            if r.prefilled >= len(r.prompt):
                self.pending_prefill.pop(0)
                if self._emitted != [r.rid]:
                    self.schedule_errors += 1
            elif self._emitted:
                self.schedule_errors += 1
        elif action == "decode":
            ctx = 0.0
            for rid in self._emitted:
                r = self.by_rid[rid]
                ctx += len(r.prompt) + len(r.tokens) - 1
            self.steps.append(Step(action, t0, t1, len(self._emitted), ctx))
        return list(self._finished)

    # -- traffic loops -------------------------------------------------------
    def closed_warm(self, requests: T.Requests, clients: int,
                    cap_s: float) -> None:
        """Send each client's first request part-way through its life
        (outputs from :func:`bench.traffic.residual_outputs`, so finishes
        are staggered the same way on every seed), then step until every
        slot is decoding, as in steady state."""
        now = clock()
        for o in T.residual_outputs(self.mix, self.seed, clients):
            self.send(requests.take(o), now)
        end = clock() + cap_s
        while clock() < end:
            for _ in self.step():
                self.send(requests.take(), clock())
            decoding = sum(1 for r in self.reqs
                           if r.tokens and r.finished_at is None)
            if not self.pending_prefill and \
                    decoding == min(clients, self.slots):
                return

    def closed_window(self, requests: T.Requests, until: float,
                      on_step=None) -> None:
        while clock() < until:
            for _ in self.step():
                self.send(requests.take(), clock())
            if on_step is not None:
                on_step()

    def open_run(self, requests: T.Requests, dues: List[float],
                 until: float, drain_until: float, window_open: float,
                 on_step=None) -> None:
        """Send each request at its due time (host clock) up to
        ``until``; then keep stepping until every request due in
        [window_open, until) has its first token, or ``drain_until``."""
        i = 0
        while True:
            now = clock()
            while i < len(dues) and dues[i] <= now and dues[i] < until:
                self.send(requests.take(), dues[i])
                i += 1
            if now >= until:
                waiting = [r for r in self.reqs
                           if r.due >= window_open and r.first is None]
                if not waiting or now >= drain_until:
                    return
            if self.has_work():
                self.step()
                if on_step is not None:
                    on_step()
            else:
                nxt = dues[i] if i < len(dues) and dues[i] < until else until
                time.sleep(max(0.0, min(nxt - now, 0.05)))
