"""Pallas kernel validation: interpret-mode execution vs the pure-jnp
oracles in kernels/ref.py, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import sparse_matmul as K

SHAPES = [
    (1, 256, 128, 128),     # matvec, tiny
    (4, 512, 384, 128),     # uneven m
    (8, 1024, 512, 256),    # bigger blocks
    (3, 384, 256, 128),     # B not multiple of bt
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _data(B, n, m, dtype, key=0):
    k = jax.random.PRNGKey(key)
    x = jax.random.normal(k, (B, n), dtype)
    w = (jax.random.normal(jax.random.fold_in(k, 1), (n, m), dtype) * 0.1
         ).astype(dtype)
    g = jnp.abs(jax.random.normal(jax.random.fold_in(k, 2), (n,))) + 0.1
    return x, w, g


@pytest.mark.parametrize("B,n,m,blk", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_sparse_matmul_shared(B, n, m, blk, dtype):
    x, w, _ = _data(B, n, m, dtype)
    nb = n // blk
    idx = jnp.arange(0, nb, 2, dtype=jnp.int32)      # every other block
    y = K.sparse_matmul_shared(x, w, idx, blk=blk, interpret=True)
    yr = ref.ref_sparse_matmul_shared(x, w, idx, blk)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,n,m,blk", SHAPES[:3])
@pytest.mark.parametrize("dtype", DTYPES)
def test_sparse_matmul_per_seq(B, n, m, blk, dtype):
    x, w, _ = _data(B, n, m, dtype)
    nb = n // blk
    kb = max(nb // 2, 1)
    idx = jnp.stack([(jnp.arange(kb) + b) % nb for b in range(B)]
                    ).astype(jnp.int32)
    y = K.sparse_matmul_per_seq(x, w, idx, blk=blk, interpret=True)
    yr = ref.ref_sparse_matmul_per_seq(x, w, idx, blk)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,n,m,blk", SHAPES)
@pytest.mark.parametrize("alpha,tau", [(0.0, 0.3), (0.7, 0.5), (1.5, 1.0)])
def test_score_mask(B, n, m, blk, alpha, tau):
    x, _, g = _data(B, n, m, jnp.float32)
    xm, bs = K.score_mask(x, g, alpha, tau, blk=blk, interpret=True)
    xmr, bsr = ref.ref_score_mask(x, g, alpha, tau, blk)
    np.testing.assert_allclose(np.asarray(xm), np.asarray(xmr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(bs), np.asarray(bsr), rtol=1e-4)


@pytest.mark.parametrize("B,n,m,blk", SHAPES[:3])
@pytest.mark.parametrize("k_frac,keep_frac", [(1.0, 1.0), (0.75, 0.5),
                                              (0.5, 0.5)])
def test_wisparse_project_vs_oracle(B, n, m, blk, k_frac, keep_frac):
    x, w, g = _data(B, n, m, jnp.float32)
    sp = {"g": g, "alpha": jnp.float32(0.7), "tau": jnp.float32(0.2),
          "keep_frac": jnp.float32(keep_frac)}
    y = ops.wisparse_project(x, w, sp, block=blk, k_frac=k_frac,
                             interpret=True)
    kb = max(1, min(n // blk, round(n // blk * k_frac)))
    yr = ref.ref_wisparse_project(x, w, sp, k_blocks=kb, blk=blk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)


# awkward (prime / non-divisible) batch and output dims: the kernels
# must pad to full tiles and slice, not silently degrade to 1-wide tiles
AWKWARD = [
    (5, 256, 257, 128),     # prime m, B below bt
    (13, 384, 131, 128),    # prime B above bt, prime m below mt
    (9, 512, 384, 256),     # B pads 9 -> 16, m tiles at 256 -> pads to 512
    (1, 128, 1, 128),       # matvec to a single output column
]


@pytest.mark.parametrize("B,n,m,blk", AWKWARD)
def test_sparse_matmul_shared_awkward_shapes(B, n, m, blk):
    x, w, _ = _data(B, n, m, jnp.float32)
    nb = n // blk
    idx = jnp.arange(0, nb, 2, dtype=jnp.int32)
    y = K.sparse_matmul_shared(x, w, idx, blk=blk, interpret=True)
    yr = ref.ref_sparse_matmul_shared(x, w, idx, blk)
    assert y.shape == (B, m)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,n,m,blk", AWKWARD[:3])
def test_sparse_matmul_per_seq_awkward_shapes(B, n, m, blk):
    x, w, _ = _data(B, n, m, jnp.float32)
    nb = n // blk
    kb = max(nb // 2, 1)
    idx = jnp.stack([(jnp.arange(kb) + b) % nb for b in range(B)]
                    ).astype(jnp.int32)
    y = K.sparse_matmul_per_seq(x, w, idx, blk=blk, interpret=True)
    yr = ref.ref_sparse_matmul_per_seq(x, w, idx, blk)
    assert y.shape == (B, m)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,n,m,blk", [(4, 257, 128, 128),
                                       (3, 384 + 7, 131, 128)])
def test_wisparse_project_awkward_channel_dim(B, n, m, blk):
    """Non-divisible channel dims pad to full-width blocks (the old
    fallback degraded blk to 1, changing both tiles and block-selection
    granularity).  Oracle: the same op on explicitly zero-padded
    inputs — padded channels score 0 and multiply zero weight rows."""
    x, w, g = _data(B, n, m, jnp.float32)
    sp = {"g": g, "alpha": jnp.float32(0.7), "tau": jnp.float32(0.2),
          "keep_frac": jnp.float32(0.5)}
    y = ops.wisparse_project(x, w, sp, block=blk, k_frac=0.75,
                             interpret=True)
    pad = -n % blk
    xp = jnp.pad(x, ((0, 0), (0, pad)))
    wp = jnp.pad(w, ((0, pad), (0, 0)))
    sp_p = {**sp, "g": jnp.pad(g, (0, pad))}
    nb = (n + pad) // blk
    kb = max(1, min(nb, round(nb * 0.75)))
    yr = ref.ref_wisparse_project(xp, wp, sp_p, k_blocks=kb, blk=blk)
    assert y.shape == (B, m)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)


# (B, n, m, blk) for an (L, n, m) layer stack: m a multiple of the 256
# output tile; m under one tile (the tile is the whole dim); m that the
# plan could only tile padded, so the stack must not be read in place
STACKED = [
    (4, 512, 512, 128),
    (3, 256, 128, 128),
    (2, 256, 300, 128),
]


@pytest.mark.parametrize("B,n,m,blk", STACKED)
@pytest.mark.parametrize("kernel", ["shared", "per_seq"])
@pytest.mark.parametrize("at", ["first", "last"])
def test_stacked_weight_matches_layer_slice(B, n, m, blk, kernel, at):
    """The kernel reading one layer's kept tiles straight from the
    (L, n, m) stack returns, bit for bit, what the call on that layer's
    own 2-D slice returns; a stack it could only read padded is refused
    (padding would copy every layer), and its callers slice instead."""
    L = 3
    x, _, _ = _data(B, n, m, jnp.bfloat16)
    ws = jnp.stack([_data(B, n, m, jnp.bfloat16, key=k)[1]
                    for k in range(L)])
    layer = 0 if at == "first" else L - 1
    nb = n // blk
    if kernel == "shared":
        fn = K.sparse_matmul_shared
        idx = jnp.arange(0, nb, 2, dtype=jnp.int32)
    else:
        fn = K.sparse_matmul_per_seq
        idx = jnp.stack([(jnp.arange(max(nb // 2, 1)) + b) % nb
                         for b in range(B)]).astype(jnp.int32)
    want = fn(x, ws[layer], idx, blk=blk, interpret=True)
    stacked = jax.jit(lambda x, w, i, lyr: fn(x, w, i, layer=lyr, blk=blk,
                                              interpret=True))
    if K.reads_in_place(n, m, blk=blk):
        got = stacked(x, ws, idx, jnp.int32(layer))
        assert got.shape == (B, m)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        assert m == 300
        with pytest.raises(ValueError, match="copy the whole stack"):
            stacked(x, ws, idx, jnp.int32(layer))


def test_interpret_auto_detects_backend():
    """interpret=None (the new default everywhere, including
    SparsityPolicy) resolves from the JAX backend: interpret-mode off
    TPU, compiled on TPU — forgetting the kwarg can no longer run the
    interpreter on real hardware."""
    assert K.default_interpret() == (jax.default_backend() != "tpu")
    x, w, g = _data(2, 256, 128, jnp.float32)
    idx = jnp.arange(0, 2, dtype=jnp.int32)
    y_auto = K.sparse_matmul_shared(x, w, idx)          # interpret=None
    y_explicit = K.sparse_matmul_shared(x, w, idx,
                                        interpret=K.default_interpret())
    np.testing.assert_array_equal(np.asarray(y_auto), np.asarray(y_explicit))
    sp = {"g": g, "alpha": jnp.float32(0.5), "tau": jnp.float32(0.1),
          "keep_frac": jnp.float32(0.6)}
    y1 = ops.wisparse_project(x, w, sp, block=128, k_frac=0.8)
    y2 = ops.wisparse_project(x, w, sp, block=128, k_frac=0.8,
                              interpret=K.default_interpret())
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    # the policy default threads through to the kernels
    from repro.sparsity import SparsityPolicy
    pol = SparsityPolicy.uniform("pallas", k_max_frac=0.8)
    assert pol.interpret is None
    assert SparsityPolicy.from_dict(pol.to_dict()) == pol   # survives io


def test_full_keep_matches_dense():
    """keep everything (tau=-inf, k=all) -> exactly the dense matmul."""
    x, w, g = _data(4, 512, 256, jnp.float32)
    sp = {"g": g, "alpha": jnp.float32(1.0), "tau": jnp.float32(-jnp.inf),
          "keep_frac": jnp.float32(1.0)}
    y = ops.wisparse_project(x, w, sp, block=128, k_frac=1.0, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)


def test_project_jit_and_grad_free():
    x, w, g = _data(2, 256, 128, jnp.float32)
    sp = {"g": g, "alpha": jnp.float32(0.5), "tau": jnp.float32(0.1),
          "keep_frac": jnp.float32(0.6)}
    f = jax.jit(lambda x: ops.wisparse_project(x, w, sp, block=128,
                                               k_frac=0.8))
    y1, y2 = f(x), ops.wisparse_project(x, w, sp, block=128, k_frac=0.8)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-6)
