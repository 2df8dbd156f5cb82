"""Llama-style gated MLP: y = W_down(silu(W_gate x) * (W_up x)).  Used by
DeepSeek LLM (``model_type`` llama)."""
import jax
import jax.numpy as jnp

from bench.reference import block_sums

SPARSE_FFN = True


def leaves(s):
    return {"mlp/wi_gate": ((s.d, s.ff), 0), "mlp/wi_up": ((s.d, s.ff), 0),
            "mlp/wo": ((s.ff, s.d), 0)}


def ffn(m, lw, h, step, n_steps):
    """Over every row, in row blocks; on a sparse rung each projection
    keeps its per-step channel blocks, chosen over all of the step's
    rows."""
    wg, wu, wo = lw["mlp/wi_gate"], lw["mlp/wi_up"], lw["mlp/wo"]
    kg = m.keep(h, wg, step, n_steps)
    ku = m.keep(h, wu, step, n_steps)
    st = step if step is not None else jnp.full((h.shape[0],), -1, jnp.int32)

    def act(hb, sb):
        a = (jax.nn.silu(m.proj(hb, wg, sb, n_steps, kg))
             * m.proj(hb, wu, sb, n_steps, ku))
        return a, block_sums(a, wo, m.alpha) if m.sparse else a[:, :1]

    a, sums = m.row_blocks(act, h, st)
    ko = m.keep(a, wo, step, n_steps, sums)
    return m.row_blocks(lambda ab, sb: m.proj(ab, wo, sb, n_steps, ko), a, st)
