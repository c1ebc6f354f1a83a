"""The grouped expert products (``dl.pallas_grouped``) through the Pallas
interpreter against the three ``jax.lax.ragged_dot`` passes they replace,
at reduced shapes of the three expert decoders, and ``models.moe.
dropless_moe`` over either path: its rows and all five counts."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.dl import pallas_grouped as pg
from mmlspark_tpu.models.moe import MOE_STATS, dropless_moe, route_top_k

# (T, k, experts, held, D, F): the short-chat decoder's 4 of 32 all held,
# the chat decoder's 10 of 512 with 128 held, the doc-qa decoder's 6 of 160
# with 40 held — widths cut to a few lanes, the ratios kept
SHAPES = {"short-conv": (24, 4, 32, (0, 32), 128, 256),
          "gated-delta": (40, 10, 64, (0, 16), 128, 128),
          "latent": (20, 6, 32, (8, 16), 256, 128)}


def _weights(rng, n, D, F, dtype=jnp.bfloat16):
    return [jnp.asarray(rng.standard_normal(s) * s[1] ** -0.5, dtype)
            for s in ((n, D, F), (n, D, F), (n, F, D))]


def _both(xs, sizes, w, **kw):
    """The lax path and the kernels (interpreted) over the same rows."""
    want, pairs = pg.grouped_expert_products(xs, sizes, *w, impl="lax")
    got, rows = pg.grouped_expert_products(xs, sizes, *w, impl="pallas",
                                           interpret=True, **kw)
    held = int(np.sum(np.asarray(sizes)))
    return (np.asarray(got[:held]), np.asarray(want[:held]), int(rows),
            int(pairs), np.asarray(got[held:]))


# the counts of pairs a held expert has, with the cases the schedule must
# walk: experts with none (first, inside, last), one expert holding every
# pair, groups that are no multiple of a tile, a single row, nothing
SIZES = {"ragged": [5, 0, 17, 3, 0, 10, 0],
         "one-expert-holds-all": [0, 0, 35, 0],
         "tile-multiples-and-not": [16, 1, 15, 32, 2],
         "one-row": [0, 1, 0],
         "no-pair": [0, 0, 0]}


@pytest.mark.parametrize("case", sorted(SIZES))
def test_the_kernels_match_the_ragged_dot_passes(case):
    sizes = SIZES[case]
    rng = np.random.default_rng(len(case))
    D, F, M = 128, 256, max(sum(sizes) + 3, 1)
    xs = jnp.asarray(rng.standard_normal((M, D)), jnp.bfloat16)
    got, want, rows, pairs, rest = _both(
        xs, jnp.asarray(sizes, jnp.int32), _weights(rng, len(sizes), D, F))
    # bfloat16 rounding of h before the down product, nothing more
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    assert pairs == sum(sizes) and rows >= pairs and rows % 32 == 0
    # rows past the held pairs in the last tile an expert visited read
    # zero (the row tile here is 32)
    assert np.all(rest[:-(-pairs // 32) * 32 - pairs] == 0)


@pytest.mark.parametrize("tm", [16, 32, 64, 128])
def test_every_row_tile_gives_the_same_rows(tm):
    """Straddles of one, two and several experts in a tile, and tiles an
    expert fills alone: the row tile moves the work, never the rows."""
    rng = np.random.default_rng(tm)
    sizes = jnp.asarray([3, 70, 0, 1, 1, 1, 40, 9], jnp.int32)
    D, F, M = 128, 128, 140
    xs = jnp.asarray(rng.standard_normal((M, D)), jnp.bfloat16)
    w = _weights(rng, 8, D, F)
    want, _ = pg.grouped_expert_products(xs, sizes, *w, impl="lax")
    got, rows = pg._grouped_pallas(xs, sizes, *w, tm=tm, interpret=True)
    np.testing.assert_allclose(np.asarray(got[:125]),
                               np.asarray(want[:125]), atol=2e-2,
                               rtol=2e-2)
    n_visits, experts, tiles = pg.tile_schedule(
        sizes, tm, -(-M // tm) * tm)[:3]
    n_visits = int(n_visits)
    assert int(rows) == n_visits * tm
    # expert-major, tiles in order: a tile's visits are consecutive and an
    # expert's blocks are read in one run of visits
    e, t = np.asarray(experts[:n_visits]), np.asarray(tiles[:n_visits])
    assert np.all(np.diff(e) >= 0) and np.all(np.diff(t) >= 0)
    assert set(e) == {0, 1, 3, 4, 5, 6, 7}          # expert 2 has no pair


def test_an_output_cut_into_blocks_reads_each_expert_once_a_block(
        monkeypatch):
    """Where a whole weight block would not fit fast memory the output is
    cut into blocks of 128 lanes: the same rows, block after block."""
    monkeypatch.setattr(pg, "_WEIGHT_BLOCK_BYTES", 128 * 128 * 2)
    assert pg.out_tile(128, 384, 2) == 128
    rng = np.random.default_rng(4)
    sizes = jnp.asarray([0, 20, 16, 0, 23, 1], jnp.int32)
    xs = jnp.asarray(rng.standard_normal((64, 128)), jnp.bfloat16)
    got, want, _, _, _ = _both(xs, sizes, _weights(rng, 6, 128, 384))
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def test_the_row_tile_follows_the_pairs_an_expert_is_expected_to_hold():
    # decode steps and riding windows of the three cells: twice the pairs
    # an expert is expected to hold, 32 rows at the least, 128 at the most
    assert pg.row_tile(256 * 4, 32) == 64
    assert pg.row_tile(1280 * 4, 32) == 128
    assert pg.row_tile(128 * 10, 512) == 32
    assert pg.row_tile(1152 * 10, 512) == 64
    assert pg.row_tile(128 * 6, 160) == 32
    assert pg.row_tile(320 * 6, 160) == 32
    assert pg.row_tile(1, 4) == 32
    # a whole block where it fits, halves of the gate/up blocks of doc-qa
    assert pg.out_tile(2048, 1792, 2) == 1792
    assert pg.out_tile(5120, 1536, 2) == 768
    assert pg.out_tile(1536, 5120, 2) == 2560


def _routed(shape, seed, valid_rows=None, dtype=jnp.bfloat16):
    T, k, E, held, D, F = shape
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, D)), dtype)
    probs = jax.nn.softmax(jnp.asarray(rng.standard_normal((T, E))), -1)
    experts, weights = route_top_k(probs, top_k=k)
    n = held[1] - held[0]
    valid = None if valid_rows is None else jnp.arange(T) < valid_rows
    return x, experts, weights, _weights(rng, n, D, F, dtype), valid


@pytest.mark.parametrize("decoder", sorted(SHAPES))
@pytest.mark.parametrize("padded", [False, True], ids=["all-valid",
                                                       "padding-rows"])
def test_dropless_moe_gives_the_same_rows_and_counts_either_way(decoder,
                                                                padded):
    T, k, E, held, D, F = SHAPES[decoder]
    x, experts, weights, w, valid = _routed(SHAPES[decoder], T,
                                            T - 5 if padded else None)
    out, counts = {}, {}
    for impl in ("lax", "pallas"):
        out[impl], c = dropless_moe(x, experts, weights, *w, held=held,
                                    valid=valid, num_experts=E, impl=impl,
                                    interpret=True)
        counts[impl] = dict(zip(MOE_STATS, np.asarray(c).tolist()))
    np.testing.assert_allclose(out["pallas"], out["lax"], atol=3e-2,
                               rtol=3e-2)
    lax, pallas = counts["lax"], counts["pallas"]
    assert len(MOE_STATS) == 5
    for name in MOE_STATS[:4]:
        assert lax[name] == pallas[name], name
    assert lax["rows_computed"] == lax["pairs_held"]
    assert pallas["rows_computed"] >= pallas["pairs_held"]
    assert pallas["rows_computed"] % pg.row_tile(T * k, E) == 0
    real = (T - 5 if padded else T) * k
    assert lax["pairs_held"] + lax["pairs_absent"] == real
    if held != (0, E):
        assert lax["pairs_absent"] > 0          # absent experts left out
    if padded:
        assert np.all(np.asarray(out["pallas"][T - 5:]) == 0)


def test_dropless_moe_over_one_row():
    """A single token: T = 1 row, one tile of the smallest size."""
    x, experts, weights, w, _ = _routed((1, 4, 8, (0, 8), 128, 128), 1)
    a, ca = dropless_moe(x, experts, weights, *w, held=(0, 8), impl="lax")
    b, cb = dropless_moe(x, experts, weights, *w, held=(0, 8),
                         impl="pallas", interpret=True)
    np.testing.assert_allclose(b, a, atol=3e-2, rtol=3e-2)
    assert int(ca[4]) == int(ca[0]) == 4 and int(cb[4]) == 32 * 4
