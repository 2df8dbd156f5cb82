"""The plain reference's Granite MoE FFN (``bench/models/granitemoe.py``)
at a CPU size: the dense rung as it was, the sparse rung at keep 1.0
equal to it, and the sparse rung against the program's own MoE layer
under the Pallas backend (interpret mode) on the same seeded weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cell as cellmod
from bench import reference as ref
from bench.models import granitemoe

CELL = "granite-3.0-3b-a800m.chat.pallas50"
SEED = 3_000_000_019
DENSE = {"backend": "off"}


@pytest.fixture(scope="module")
def small():
    return cellmod.find(CELL, rehearse=True)


def _parent_ffn(m, lw, h, step, n_steps):
    """The dense FFN as it stood before the sparse rung was added."""
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


def _inputs(m, n=96):
    h = jax.random.normal(jax.random.PRNGKey(7), (n, m.s.d), jnp.float32)
    # three steps and dense rows, interleaved as requests' rows are
    step = jnp.asarray(np.arange(n) % 4 - 1, jnp.int32)
    return m.layer_weights(jnp.uint32(1)), h, step


def test_dense_rung_is_bit_identical_to_the_parent(small):
    m = ref.Model(small.cfg, DENSE, SEED)
    lw, h, _ = _inputs(m)
    new = jax.jit(lambda lw, h: granitemoe.ffn(m, lw, h, None, 0))(lw, h)
    old = jax.jit(lambda lw, h: _parent_ffn(m, lw, h, None, 0))(lw, h)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


def test_sparse_rung_at_keep_one_equals_the_dense_rung(small):
    dense = ref.Model(small.cfg, DENSE, SEED)
    full = ref.Model(small.cfg, dict(small.rung, keep_frac=1.0), SEED)
    half = ref.Model(small.cfg, small.rung, SEED)
    lw, h, step = _inputs(dense)
    want = jax.jit(lambda lw, h: granitemoe.ffn(dense, lw, h, None, 0))(lw, h)

    def sparse(m):
        return np.asarray(jax.jit(lambda lw, h, st: granitemoe.ffn(
            m, lw, h, st, 3))(lw, h, step))

    np.testing.assert_array_equal(sparse(full), np.asarray(want))
    # at keep 0.5 the sparse steps' rows move; the dense rows (step -1) not
    got = sparse(half)
    dense_rows = np.asarray(step) < 0
    np.testing.assert_array_equal(got[dense_rows], np.asarray(want)[dense_rows])
    assert np.abs(got[~dense_rows] - np.asarray(want)[~dense_rows]).min(
        axis=1).max() > 1e-3


def test_expert_keeps_blocks_by_its_own_routed_rows(small):
    """A row that no expert of interest gets cannot move that expert's
    kept blocks: scaling one row's input changes only the outputs of
    steps that the row shares with others through its own experts."""
    m = ref.Model(small.cfg, small.rung, SEED)
    lw, h, step = _inputs(m)
    f = jax.jit(lambda h: granitemoe.ffn(m, lw, h, step, 3))
    base = np.asarray(f(h))
    logits = np.asarray(ref.matmul(h, lw["moe/router"], "f32"))
    chosen = np.argsort(-logits, axis=1)[:, :m.s.experts_per_tok]
    # rows of step 0 that share no expert with row 1 (step 0 too)
    step_np = np.asarray(step)
    r = 1
    assert step_np[r] == 0
    apart = [i for i in range(len(step_np)) if step_np[i] == 0 and i != r
             and not set(chosen[i]) & set(chosen[r])]
    assert apart
    moved = np.asarray(f(h.at[r].multiply(50.0)))
    for i in apart:
        np.testing.assert_array_equal(moved[i], base[i])


def _program_logits(cfg, rung, tokens):
    from repro.core.sp_schema import default_sp_stacked
    from repro.models import model as M
    from repro.sparsity import SparsityPolicy
    pcfg = cellmod.program_config(cfg)
    params = cellmod.make_params(pcfg, cfg, SEED)
    sp = default_sp_stacked(params, pcfg, keep_frac=float(rung["keep_frac"]),
                            alpha=float(rung["alpha"]))
    policy = SparsityPolicy.uniform("pallas",
                                    k_max_frac=float(rung["k_max_frac"]))
    logits, _ = M.forward(params, pcfg, tokens=jnp.asarray(tokens)[None],
                          mode="train", sp=sp, policy=policy)
    return np.asarray(logits[0], np.float32)


def test_sparse_reference_agrees_with_the_program(small):
    """One request of 48 tokens through the program's whole model at
    ``pallas`` 50% (``forward`` in train mode: every row shares each
    projection's, and each expert's, kept blocks, as one engine step's
    rows do) and through the reference with all rows in one step.
    Logits are compared at each row's eight largest program logits."""
    cfg, rung = small.cfg, small.rung
    n = 48
    tokens = np.random.default_rng(5).integers(
        0, cfg["vocab_size"], n).astype(np.int32)
    prog = _program_logits(cfg, rung, tokens)
    top = np.argsort(-prog, axis=1)[:, :8].astype(np.int32)
    rows = ref.build_rows([tokens], [np.zeros(n, np.int32)], n_steps=1)

    def reference(model):
        best, _arg, at = model.run(rows, np.arange(n, dtype=np.int32), top,
                                   max_len=n)
        return best, at

    best, at = reference(ref.Model(cfg, rung, SEED))
    want = np.take_along_axis(prog, top, axis=1)
    # float32 on both sides; the program sums in another order (blocked
    # kernel, fused matmuls) over contractions of at most 256, so its
    # logits (of order 1) differ by rounding, about 1e-6: 1e-4 leaves
    # room, and a kept block chosen differently moves them by 1e-2 or more
    tol = 1e-4
    assert np.abs(at - want).max() < tol
    assert np.abs(best - prog.max(axis=1)).max() < tol
    # each reading fails at the nearest precision below float32's and on
    # the dense rung, so the comparison separates what it has to
    _best8, at8 = reference(ref.Model(cfg, rung, SEED, precision="fp8"))
    assert np.abs(at8 - want).max() > 10 * tol
    _bestd, atd = reference(ref.Model(cfg, DENSE, SEED))
    assert np.abs(atd - want).max() > 10 * tol
