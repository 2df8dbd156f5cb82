"""Granite MoE FFN: a softmax router over the published experts, the
top ``num_experts_per_tok`` renormalized (softmax over the chosen
logits), each chosen expert a gated MLP, outputs summed by gate.  No
token is dropped: every expert sees every token routed to it.

On a sparse rung each expert is WiSparse'd on its own: per engine step,
its gate and up projections keep the ``keep_frac`` of their 128-channel
input blocks with the largest summed |x| * ||W_row||^alpha over the
step's live rows routed to that expert, and its down projection keeps
its blocks by the same score over that expert's activations.  Dense
rows (step -1) keep every block; the router stays dense.  The program
also lets the decode rows of free slots and a last chunk's pad positions
into each expert's sum (the configuration's ``departures``)."""
import jax
import jax.numpy as jnp

from bench.reference import block_sums

SPARSE_FFN = True


def leaves(s):
    e = s.experts
    return {"moe/router": ((s.d, e), 0), "moe/wi_gate": ((s.d, s.ff), e),
            "moe/wi_up": ((s.d, s.ff), e), "moe/wo": ((s.ff, s.d), e)}


def ffn(m, lw, h, step, n_steps):
    from bench.reference import matmul
    s = m.s
    logits = matmul(h, lw["moe/router"], m.precision)
    top, idx = jax.lax.top_k(logits, s.experts_per_tok)
    gate = jax.nn.softmax(top, axis=-1)
    rows = jnp.arange(h.shape[0])[:, None]
    dense_gate = jnp.zeros_like(logits).at[rows, idx].set(gate)
    if m.sparse and step is not None:
        routed = jnp.zeros_like(logits).at[rows, idx].set(1.0)
        return _sparse_experts(m, lw, h, step, n_steps, dense_gate, routed)

    def expert(acc, e):
        wg, wu, wo = (lw["moe/wi_gate"][e], lw["moe/wi_up"][e],
                      lw["moe/wo"][e])
        a = jax.nn.silu(matmul(h, wg, m.precision)) * matmul(h, wu, m.precision)
        return acc + dense_gate[:, e][:, None] * matmul(a, wo, m.precision), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(s.experts))
    return out


def _sparse_experts(m, lw, h, step, n_steps, dense_gate, routed):
    """Each expert over every row, its projections' kept blocks chosen
    per step from the rows routed to it (``routed`` (N, E) 0/1)."""
    def keep(x, w, mine):
        return m.keep(None, w, step, n_steps,
                      sums=block_sums(x, w, m.alpha) * mine)

    def expert(acc, e):
        wg, wu, wo = (lw["moe/wi_gate"][e], lw["moe/wi_up"][e],
                      lw["moe/wo"][e])
        mine = routed[:, e][:, None]
        a = (jax.nn.silu(m.proj(h, wg, step, n_steps, keep(h, wg, mine)))
             * m.proj(h, wu, step, n_steps, keep(h, wu, mine)))
        y = m.proj(a, wo, step, n_steps, keep(a, wo, mine))
        return acc + dense_gate[:, e][:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          jnp.arange(m.s.experts))
    return out
