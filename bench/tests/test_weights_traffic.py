"""The seeded weight generator and the traffic generator."""
import jax.numpy as jnp
import numpy as np

from bench import traffic as T
from bench import weights as W

SEED = W.seed_words(3_000_000_019)        # past 2**31, as the driver's


def test_stacked_slices_equal_single_draws():
    st = W.draw_stacked(SEED, "attn/wq", (256, 64), layers=3)
    for l in range(3):
        np.testing.assert_array_equal(
            st[l], W.draw(SEED, "attn/wq", l, 0, (256, 64)))
    ex = W.draw_stacked(SEED, "moe/wi_up", (128, 32), layers=2, experts=3)
    np.testing.assert_array_equal(
        ex[1, 2], W.draw(SEED, "moe/wi_up", 1, 2, (128, 32)))


def test_rows_equal_the_drawn_matrix():
    full = W.draw(SEED, "embed", 0, 0, (50, 16))
    rows = W.draw_rows(SEED, "embed", jnp.array([3, 0, 49]), 16)
    np.testing.assert_array_equal(rows, full[jnp.array([3, 0, 49])])


def test_block_scales_quarter_each_level():
    s = np.asarray(W.block_scales(jnp.uint32(7), 64 * 128))
    blocks = s.reshape(64, 128)
    assert (blocks == blocks[:, :1]).all()
    vals, counts = np.unique(blocks[:, 0], return_counts=True)
    np.testing.assert_allclose(sorted(vals), [1 / 8, 1 / 4, 1 / 2, 1])
    assert (counts == 16).all()


def test_values_and_seeds():
    a = W.draw(SEED, "mlp/wo", 0, 0, (512, 256))
    b = W.draw(W.seed_words(3_000_000_020), "mlp/wo", 0, 0, (512, 256))
    assert not np.array_equal(a, b)
    assert float(jnp.abs(a).max()) <= np.sqrt(3 / 512) + 1e-7
    e = W.draw(SEED, "embed", 0, 0, (64, 64))
    assert float(jnp.abs(e).max()) <= W.EMBED_STD * np.sqrt(3) + 1e-7
    n = W.draw(SEED, "ln1", 0, 0, (1000,))
    assert float(jnp.abs(n).max()) <= W.NORM_HALF_WIDTH
    assert W.seed_words(2**33 + 5) == (5, 2)


MIX = {"prompt": {"median": 512, "sigma": 0.6, "min": 128, "max": 1536},
       "output": {"median": 384, "sigma": 0.7, "min": 64, "max": 1536},
       "max_total": 2047, "rate": 4.0, "block": 8}


def test_lengths_are_seeded_clipped_and_stratified():
    p1, o1 = T.lengths(MIX, 5, 256, 32)
    p2, o2 = T.lengths(MIX, 5, 256, 32)
    np.testing.assert_array_equal(p1, p2)
    assert p1.min() >= 128 and p1.max() <= 1536
    assert (p1 + o1 <= 2047).all() and o1.min() >= 1
    # every block of 32 draws one from each stratum: block medians agree
    med = [np.median(p1[i:i + 32]) for i in range(0, 256, 32)]
    assert max(med) / min(med) < 1.3
    p3, _ = T.lengths(MIX, 6, 256, 32)
    assert not np.array_equal(p1, p3)


def test_open_schedule_spans_the_window_at_the_rate():
    d = T.open_schedule(MIX, 11, 50.0)
    assert len(d) == 200 and d[0] == 0.0
    assert d[-1] < 50.0
    gaps = np.diff(d)
    assert (gaps > 0).all() and abs(np.mean(gaps) - 0.25) < 0.05
    d2 = T.open_schedule(MIX, 12, 50.0)
    # the same load in another order: the quartiles of the gaps agree
    np.testing.assert_allclose(np.quantile(np.diff(d), [0.25, 0.5, 0.75]),
                               np.quantile(np.diff(d2), [0.25, 0.5, 0.75]),
                               rtol=0.15)
    assert not np.allclose(np.diff(d), np.diff(d2))
    # every 8 arrivals hold one gap from each eighth of the exponential:
    # no block of 8 is a burst (the seven lower eighths alone sum to
    # 4.92 mean gaps); the block with the schedule's longest gap, from
    # the exponential's far tail, is the longest
    g = np.diff(np.append(d, 50.0))
    blocks = g[:len(g) // 8 * 8].reshape(-1, 8).sum(1) * 4.0
    assert blocks.min() > 8 * 0.55 and blocks.max() < 8 * 2.0


def test_requests_stream():
    r = T.Requests(MIX, 9, 1000, 64, 32)
    a = r.take(7)
    assert a.prompt.dtype == np.int32 and a.prompt.max() < 1000
    assert a.max_new == 7
    b = r.take()
    assert b.max_new == r.output_lens[1] and len(b.prompt) == r.prompt_lens[1]


def test_residual_outputs_same_set_every_seed():
    a = T.residual_outputs(MIX, 1, 32)
    b = T.residual_outputs(MIX, 2, 32)
    assert sorted(a) == sorted(b) and a != b
    assert min(a) >= 1 and max(a) <= 1536
    # a residual life is shorter than a whole one on the median
    assert np.median(a) < 384 * 1.5


def test_fixed_schedule_is_the_same_for_every_seed():
    mix = dict(MIX, schedule_seed=7)
    np.testing.assert_array_equal(T.open_schedule(mix, 1, 30.0),
                                  T.open_schedule(mix, 2, 30.0))
    a, b = T.Requests(mix, 1, 1000, 32, 8), T.Requests(mix, 2, 1000, 32, 8)
    np.testing.assert_array_equal(a.prompt_lens, b.prompt_lens)
    np.testing.assert_array_equal(a.output_lens, b.output_lens)
    assert T.residual_outputs(mix, 1, 32) == T.residual_outputs(mix, 2, 32)
    # the prompts' token ids still come from the seed
    assert not np.array_equal(a.take().prompt, b.take().prompt)


def test_stratified_levels_cover_every_eighth_in_blocks():
    rng = np.random.default_rng(0)
    lv = T._stratified(rng, 64, 8)
    # each block of 8 holds one level from each eighth
    for k in range(8):
        assert sorted((lv[8 * k:8 * k + 8] * 8).astype(int)) == list(range(8))
