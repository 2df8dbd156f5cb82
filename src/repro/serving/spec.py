"""Self-speculative decoding: sparse rungs draft, the dense rung verifies.

WiSparse's training-free sparsity gives a family of cheaper variants of
the *same* model — the ladder rungs — sharing weights and KV cache with
the dense model: the textbook precondition for self-speculative decoding.
Per engine decode action the :class:`SpecDecoder` runs ``gamma``
sequential single-token draft steps at the (sparse) drafter rung, then
one batched length-``(gamma+1)`` verify forward at the verifier rung,
accepts each slot's longest draft prefix matching the verifier's greedy
tokens, commits the verifier-faithful KV the verify wrote in place, and
rolls the rejected suffix back out of the pool
(``SlotKVPool.rollback``).

Greedy-verify semantics: every committed token — accepted drafts and the
verifier's bonus token after the last accepted draft — is exactly the
token the verifier's own greedy decode would have produced, so the output
stream is token-identical to verifier-only decode while the per-token
cost approaches the drafter's.  The drafter's fidelity only moves the
*speed* (via the acceptance rate), never the output.

Compile-once discipline: drafting reuses the engine's batched slot-decode
executable at the drafter rung (precompiled for every rung by
``Engine.warmup()``); the verify forward compiles once per (gamma,
verifier policy) and warmup covers every gamma the adaptive controller
can reach, so rung and gamma switches are retrace-free.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api
from repro.serving.controller import SpecController


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding execution config.

    gamma          draft tokens per verify (the classic draft length).
    drafter_rung   ladder rung that drafts (must be sparser — higher —
                   than the verifier).
    verifier_rung  ladder rung whose greedy tokens the output is
                   guaranteed to match (0 = densest; the engine serves
                   prefill and emits tokens at this rung).  Its decode
                   policy must be *dense* — the engine validates: under
                   a sparse policy the shared top-k channel set depends
                   on the call's token rows, so the multi-token verify
                   forward and single-token decode would diverge and the
                   parity guarantee would silently break.
    adaptive       arm the :class:`SpecController`: tune gamma within
                   [gamma_min, gamma_max] (and, with ``adapt_drafter``,
                   the drafter rung) from the acceptance EWMA.
    accept_ewma_alpha / raise_at / lower_at / dwell
                   controller tuning (see :class:`SpecController`).
    """

    gamma: int = 2
    drafter_rung: int = 1
    verifier_rung: int = 0
    adaptive: bool = False
    gamma_min: int = 1
    gamma_max: int = 4
    adapt_drafter: bool = False
    accept_ewma_alpha: float = 0.2
    raise_at: float = 0.8
    lower_at: float = 0.4
    dwell: int = 8

    def __post_init__(self):
        if self.gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {self.gamma}")
        if self.verifier_rung < 0:
            raise ValueError(
                f"verifier_rung must be >= 0, got {self.verifier_rung}")
        if self.drafter_rung <= self.verifier_rung:
            raise ValueError(
                f"drafter_rung {self.drafter_rung} must be a sparser "
                f"(higher) rung than verifier_rung {self.verifier_rung} — "
                "drafting at the verifier's own cost cannot speed it up")
        if self.adaptive and not \
                1 <= self.gamma_min <= self.gamma <= self.gamma_max:
            raise ValueError(
                f"adaptive spec needs 1 <= gamma_min <= gamma <= gamma_max,"
                f" got ({self.gamma_min}, {self.gamma}, {self.gamma_max})")
        if self.adapt_drafter and not self.adaptive:
            raise ValueError("adapt_drafter needs adaptive=True")

    @property
    def max_gamma(self) -> int:
        """Largest draft length any operating point can use (sizes the
        pool slack and the warmup sweep)."""
        return self.gamma_max if self.adaptive else self.gamma

    def gammas(self):
        """Every draft length warmup must precompile a verify for."""
        if self.adaptive:
            return range(self.gamma_min, self.gamma_max + 1)
        return (self.gamma,)


def make_verify_jit(cfg, on_trace=None):
    """The jitted verify executable with the canonical static-arg and
    donation configuration (policy static, pool caches donated) — the
    single construction site shared by :class:`SpecDecoder` and the
    ``repro.analysis`` jaxpr passes, so the lint lowers exactly what
    serving runs.  ``on_trace`` runs only while XLA is (re)tracing."""
    verify = api.make_verify_step(cfg)

    def _verify(params, tokens, positions, caches, sp, weights, *,
                policy):
        if on_trace is not None:
            on_trace()
        return verify(params, tokens, positions, caches, sp, weights,
                      policy=policy)

    return jax.jit(_verify, static_argnames=("policy",),
                   donate_argnums=(3,))


class SpecDecoder:
    """Per-engine speculative decode driver (created by the engine when
    ``EngineConfig.spec`` is set; one per engine, like the scheduler).

    Owns the jitted verify step, the acceptance EWMA and — in adaptive
    mode — the :class:`SpecController`.  ``step()`` replaces the engine's
    plain batched decode step and may emit up to ``gamma + 1`` tokens per
    decoding request."""

    def __init__(self, engine, scfg: SpecConfig):
        self.engine = engine
        self.scfg = scfg
        self.gamma = scfg.gamma
        self.drafter_rung = scfg.drafter_rung
        self.verifier_rung = scfg.verifier_rung
        self._accept_ewma = None      # non-adaptive mode only; adaptive
        #                               mode's EWMA lives in the controller
        self._verify_traces = 0

        def _on_trace():
            self._verify_traces += 1        # runs only while tracing

        self._vstep = make_verify_jit(engine.cfg, on_trace=_on_trace)
        self.controller = None
        if scfg.adaptive:
            self.controller = SpecController(
                scfg.gamma, scfg.gamma_min, scfg.gamma_max,
                drafter_rung=scfg.drafter_rung,
                drafter_min=scfg.verifier_rung + 1,
                drafter_max=engine.num_rungs - 1,
                adapt_drafter=scfg.adapt_drafter,
                alpha=scfg.accept_ewma_alpha, raise_at=scfg.raise_at,
                lower_at=scfg.lower_at, dwell=scfg.dwell)

    # ------------------------------------------------------------------
    @property
    def accept_ewma(self):
        """Acceptance EWMA: the controller's (reset per switch) in
        adaptive mode, the decoder's lifetime EWMA otherwise — one owner,
        so the JSONL field always reflects the value decisions use."""
        if self.controller is not None:
            return self.controller.accept_ewma
        return self._accept_ewma

    def set_gamma(self, gamma: int) -> None:
        """Pin a draft length (tests / manual tuning).  Must be one the
        warmup precompiled, or the next verify would retrace."""
        if gamma not in self.scfg.gammas():
            raise ValueError(
                f"gamma {gamma} outside the precompiled set "
                f"{list(self.scfg.gammas())}; other values would retrace "
                "the verify executable")
        self.gamma = gamma
        if self.controller is not None:     # else the next round's update
            self.controller.gamma = gamma   # would clobber the pin

    # ------------------------------------------------------------------
    def step(self) -> None:
        """One spec round: gamma batched draft steps at the drafter rung,
        one batched verify at the verifier rung, then per-slot
        accept/commit/rollback."""
        eng = self.engine
        decoding = dict(eng.scheduler.decoding)
        if not decoding:
            return
        g = self.gamma
        S = eng.ecfg.max_slots
        params = eng.params
        _, _, draft_pol = eng._rung_phases[self.drafter_rung]
        draft_sp = eng._rung_sp[self.drafter_rung]
        _, _, ver_pol = eng._rung_phases[self.verifier_rung]
        ver_sp = eng._rung_sp[self.verifier_rung]

        # inactive slots window into the pool's slack region (beyond every
        # reachable real position, like the plain decode scratch slot)
        with eng.obs.annotate("repro/spec/prepare"):
            start = np.full((S,), eng.pool_len - (g + 1), np.int32)
            cur = np.zeros((S,), np.int32)
            active = np.zeros((S,), np.float32)
            for slot, rs in decoding.items():
                start[slot] = rs.position
                cur[slot] = rs.last_token
                active[slot] = 1.0

        # --- draft: g sequential single-token steps, batched over slots --
        # the argmax chain stays on device (each draft feeds the next
        # without a host round-trip); one block per phase keeps the
        # draft/verify latency split honest without per-step syncs
        t0 = eng._now("spec.t0")
        with eng.obs.annotate("repro/spec_draft", gamma=g,
                              drafter_rung=self.drafter_rung,
                              active=len(decoding)):
            with eng.obs.annotate("repro/spec_draft/launch"):
                act = jnp.asarray(active)
                toks = jnp.asarray(cur)
                draft_cols = []
                for i in range(g):
                    logits, eng.pool.caches = eng._dstep(
                        params, toks, jnp.asarray(start + i),
                        eng.pool.caches, draft_sp, act, policy=draft_pol)
                    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    draft_cols.append(toks)
                drafts_dev = jnp.stack(draft_cols, axis=1)     # (S, g)
            with eng.obs.annotate("repro/spec_draft/readback"):
                drafts_dev.block_until_ready()
        t1 = eng._now("spec.t1")

        # --- verify: one batched (g+1)-token forward ---------------------
        with eng.obs.annotate("repro/spec_verify", gamma=g,
                              verifier_rung=self.verifier_rung):
            with eng.obs.annotate("repro/spec_verify/launch"):
                vtokens = jnp.concatenate(
                    [jnp.asarray(cur)[:, None], drafts_dev], axis=1)
                weights = np.repeat(active[:, None], g + 1, axis=1)
                logits, eng.pool.caches = self._vstep(
                    params, vtokens, jnp.asarray(start),
                    eng.pool.caches, ver_sp, jnp.asarray(weights),
                    policy=ver_pol)
            with eng.obs.annotate("repro/spec_verify/readback"):
                ver = np.asarray(jnp.argmax(logits, axis=-1))  # (S, g+1)
                drafts = np.asarray(drafts_dev)
        t2 = eng._now("spec.t2")

        stats = eng.stats
        stats.spec_rounds += 1
        stats.spec_draft_steps += g
        stats.decode_steps += g
        stats.observe_spec_draft(t1 - t0)
        stats.observe_spec_verify(t2 - t1)
        tracer = eng.obs.tracer

        # --- accept, then one batched rollback, then emit ----------------
        accept_fracs = []
        commits = {}
        rollbacks = {}
        for slot, rs in decoding.items():
            d, v = drafts[slot], ver[slot]
            n_acc = 0
            while n_acc < g and d[n_acc] == v[n_acc]:
                n_acc += 1
            # accepted drafts + the verifier's bonus token — exactly the
            # verifier's own greedy continuation
            cand = [int(t) for t in d[:n_acc]] + [int(v[n_acc])]
            # the request's budget and EOS truncate the commit so that
            # only the *last* committed token can finish the request
            # (matching plain decode's one-finish-check-per-step)
            m = min(len(cand), rs.request.max_new_tokens - len(rs.tokens))
            eos = rs.request.eos_id
            if eos is not None and eos in cand[:m]:
                m = cand[:m].index(eos) + 1
            # the verify wrote g+1 verifier-faithful positions at
            # [start, start+g]; keep the m committed ones (the last
            # committed token's own KV is written by the *next* round,
            # like plain decode), truncate the rest out of the cache
            eng.pool.commit(slot, g + 1)
            rollbacks[slot] = g + 1 - m
            commits[slot] = (rs, cand[:m], n_acc)
        with eng.obs.annotate("repro/spec_rollback",
                              rollback_tokens=sum(rollbacks.values())):
            eng.pool.rollback_many(rollbacks)
        t3 = eng._now("spec.t3")
        # the round's decode cost includes the rollback dispatch — it is
        # real per-round work plain decode doesn't pay
        stats.decode_time += t3 - t0
        events = eng.obs.events

        for slot, (rs, committed, n_acc) in commits.items():
            m = len(committed)
            accept_fracs.append(n_acc / g)
            stats.spec_verifies += 1
            stats.spec_draft_tokens += g
            stats.spec_accepted_tokens += n_acc
            stats.spec_committed_tokens += m
            stats.observe_spec_accepted(n_acc)
            if events is not None and rollbacks[slot] > 0:
                events.emit(
                    "kv_rollback", t=t3, slot=slot,
                    request=rs.request.request_id,
                    tokens=rollbacks[slot], accepted=n_acc,
                    committed=m, gamma=g)
            if rs.last_token_time is not None:
                gap = (t3 - rs.last_token_time) / m   # amortized TPOT
                for _ in range(m):
                    stats.observe_tpot(gap)
            rs.last_token_time = t3
            for tok in committed:
                eng._emit(rs, tok)
            eng._maybe_finish(rs, committed[-1])

        # --- adapt -------------------------------------------------------
        frac = float(np.mean(accept_fracs))
        if self.controller is not None:
            old_g, old_d = self.gamma, self.drafter_rung
            self.gamma, self.drafter_rung = self.controller.update(frac)
            if (self.gamma, self.drafter_rung) != (old_g, old_d):
                reason = self.controller.transitions[-1][3] \
                    if self.controller.transitions else None
                if events is not None:
                    events.emit(
                        "gamma_switch" if self.gamma != old_g
                        else "drafter_switch", t=t3,
                        from_gamma=old_g, to_gamma=self.gamma,
                        from_drafter=old_d, to_drafter=self.drafter_rung,
                        reason=reason,
                        accept_ewma=self.controller.accept_ewma)
                if tracer is not None:
                    tracer.instant(
                        "spec_switch", t=t3, gamma=self.gamma,
                        drafter_rung=self.drafter_rung, reason=reason)
                fr = eng.obs.flight
                if fr is not None:
                    fr.decision(
                        "gamma_switch" if self.gamma != old_g
                        else "drafter_switch",
                        from_gamma=old_g, to_gamma=self.gamma,
                        from_drafter=old_d, to_drafter=self.drafter_rung,
                        reason=reason)
        else:
            a = self.scfg.accept_ewma_alpha
            self._accept_ewma = frac if self._accept_ewma is None else \
                (1 - a) * self._accept_ewma + a * frac

    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        """Spec state for the engine's JSONL snapshot record."""
        ewma = self.accept_ewma
        out = {
            "spec_gamma": self.gamma,
            "spec_drafter_rung": self.drafter_rung,
            "spec_accept_ewma": None if ewma is None else round(ewma, 4),
        }
        if self.controller is not None:
            out["spec_switches"] = len(self.controller.transitions)
        return out
