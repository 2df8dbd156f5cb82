"""Granite MoE FFN: a softmax router over the published experts, the
top ``num_experts_per_tok`` renormalized (softmax over the chosen
logits), each chosen expert a gated MLP, outputs summed by gate.  No
token is dropped: every expert sees every token routed to it."""
import jax
import jax.numpy as jnp

SPARSE_FFN = False


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
    dense_gate = jnp.zeros_like(logits).at[
        jnp.arange(h.shape[0])[:, None], idx].set(gate)

    def expert(acc, e):
        wg, wu, wo = (lw["moe/wi_gate"][e], lw["moe/wi_up"][e],
                      lw["moe/wo"][e])
        a = jax.nn.silu(matmul(h, wg, m.precision)) * matmul(h, wu, m.precision)
        return acc + dense_gate[:, e][:, None] * matmul(a, wo, m.precision), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(s.experts))
    return out
