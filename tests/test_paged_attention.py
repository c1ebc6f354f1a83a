"""Paged-attention kernel (``dl/pallas_paged_attention.py``): the
block-table-indexed decode kernel behind the serving executors, over
pools that rest lane-dense ``[num_blocks, block_len, heads*head_dim]``.

Two layers of contract. Kernel-level: the pure-lax reference is
bit-compatible with the dense ``decode_window`` formulation over
caches gathered whole in the test, and the Pallas kernel (interpret
mode on CPU) matches the reference across windows, ragged chains, and
every ``block_kv x slots_tile`` tiling, in both forms of its cell (every
head in one product while ``heads * w`` rows fit a tile group, a loop
over the heads above it), and its index map fetches nothing past a
chain's end. Engine-level: greedy /
speculative serving over contexts spanning >= 8 pool blocks — with
mid-generation eviction pressure and ragged per-slot lengths — stays
byte-identical to ``dl.generate``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.dl import (MaskedLMModel, TextEncoder, generate,
                             make_attention_fn, paged_attention,
                             paged_window_attention)
from mmlspark_tpu.dl.paged_kv import TRASH_BLOCK
from mmlspark_tpu.dl.pallas_paged_attention import (_BATCHED_ROWS,
                                                    chain_block)
from mmlspark_tpu.obs.metrics import MetricsRegistry
from mmlspark_tpu.perf import autotune
from mmlspark_tpu.serving.llm import LLMEngine

# ---------------------------------------------------------- kernel level

S, H, HD, BL, MB = 3, 2, 8, 4, 5   # ragged 3-slot micro case
H6 = 6                             # heads whose window of 3 takes the loop
NB = 13                            # pool rows (incl. trash row 0)


def _pools(seed=0, nb=NB, bl=BL, heads=H, hd=HD):
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.standard_normal((nb, bl, heads * hd)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((nb, bl, heads * hd)),
                    jnp.float32)
    return k, v


def _ragged_case(w=1):
    """Three chains of 2 / 4 / 1 blocks; ``pos`` keeps the whole query
    window inside the slot's real blocks (the serving invariant —
    windows are scattered before they attend)."""
    rows = np.full((S, MB), TRASH_BLOCK, np.int32)
    rows[0, :2] = [1, 2]
    rows[1, :4] = [6, 7, 8, 9]
    rows[2, :1] = [11]
    lengths = (2 * BL, 4 * BL, 1 * BL)
    pos = np.asarray([n - w for n in lengths], np.int32)
    return jnp.asarray(rows), jnp.asarray(pos)


def _q(seed, s, heads, w, hd):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((s, heads, w, hd)),
                       jnp.float32)


def _dense_ref(q, k_pool, v_pool, rows, pos):
    """The decode_window formulation over each slot's chain gathered
    into a dense ``[S, H, L, hd]`` cache — the math ``dl.generate``'s
    cached path runs."""
    s_, h_, w_, hd_ = q.shape

    def dense(pool):
        blocks = jnp.take(pool, rows, axis=0)       # [S, MB, BL, H*hd]
        return jnp.transpose(
            blocks.reshape(s_, -1, h_, hd_), (0, 2, 1, 3))

    k, v = dense(k_pool), dense(v_pool)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * hd_**-0.5
    length = k.shape[2]
    allowed = (jnp.arange(length)[None, None, :]
               <= (pos[:, None] + jnp.arange(w_)[None, :])[:, :, None])
    scores = jnp.where(allowed[:, None, :, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


class TestKernelReference:
    @pytest.mark.parametrize("w", [1, 3])
    def test_lax_matches_dense_formulation(self, w):
        kp, vp = _pools()
        rows, pos = _ragged_case(w)
        q = _q(w, S, H, w, HD)
        ref = _dense_ref(q, kp, vp, rows, pos)
        got = paged_window_attention(q, kp, vp, rows, pos, impl="lax")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)

    def test_lax_is_deterministic(self):
        kp, vp = _pools(3)
        rows, pos = _ragged_case()
        q = _q(5, S, H, 1, HD)
        a = paged_window_attention(q, kp, vp, rows, pos, impl="lax")
        b = paged_window_attention(q, kp, vp, rows, pos, impl="lax")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_single_token_wrapper_is_w1(self):
        kp, vp = _pools(1)
        rows, pos = _ragged_case(1)
        q = _q(2, S, H, 1, HD)
        flat = paged_attention(q[:, :, 0, :], kp, vp, rows, pos,
                               impl="lax")
        win = paged_window_attention(q, kp, vp, rows, pos, impl="lax")
        np.testing.assert_array_equal(np.asarray(flat),
                                      np.asarray(win[:, :, 0, :]))


def _cell_case(w):
    """The benchmark cell's own head geometry (16 heads of 128,
    ``block_len`` 128) over a table of 4 entries: chains that end in the
    first, a middle and the last entry, one whose tail block is a single
    position full, and a slot whose row is all padding."""
    heads, hd, bl, mb = 16, 128, 128, 4
    rows = np.full((5, mb), TRASH_BLOCK, np.int32)
    rows[0, :1] = [3]
    rows[1, :3] = [9, 1, 6]
    rows[2, :4] = [2, 8, 5, 4]
    rows[3, :2] = [7, 10]
    # the window's last row sits at the chain's newest position
    newest = np.asarray([bl // 2, 2 * bl + 77, 4 * bl - 1, bl, 0])
    pos = (newest - (w - 1)).astype(np.int32)
    kp, vp = _pools(60 + w, nb=11, bl=bl, heads=heads, hd=hd)
    return (_q(70 + w, 5, heads, w, hd), kp, vp, jnp.asarray(rows),
            jnp.asarray(pos))


class TestKernelInterpret:
    """Pallas-in-interpret-mode smoke vs the lax reference (tier-1:
    tiny shapes; the full-size sweep is under ``slow``)."""

    @pytest.mark.parametrize("heads", [H, H6])
    @pytest.mark.parametrize("w", [1, 3, 4])
    @pytest.mark.parametrize("block_kv,slots_tile",
                             [(BL, 1), (1, 2), (3, 8)])
    def test_matches_lax(self, heads, w, block_kv, slots_tile):
        kp, vp = _pools(w, heads=heads)
        rows, pos = _ragged_case(w)
        q = _q(10 + w, S, heads, w, HD)
        ref = paged_window_attention(q, kp, vp, rows, pos, impl="lax")
        got = paged_window_attention(q, kp, vp, rows, pos,
                                     impl="pallas", interpret=True,
                                     block_kv=block_kv,
                                     slots_tile=slots_tile)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("heads,kv_heads,w", [
        (2, 2, 1), (2, 2, 3),            # a key head a query head: as ever
        (16, 2, 1),                      # 8 a key head, the batched form
        (16, 2, 3),                      # 8 a key head, a loop over 2
        (8, 1, 4)])
    def test_grouped_query_heads_match_lax(self, heads, kv_heads, w):
        """Query heads ``g * heads / kv_heads ...`` read key head ``g``
        of pools ``[blocks, block_len, kv_heads * hd]``: the kernel
        against the lax reference (which repeats each key head), and the
        lax reference against plain attention over the chain."""
        kp, vp = _pools(w, heads=kv_heads)
        rows, pos = _ragged_case(w)
        q = _q(20 + w, S, heads, w, HD)
        ref = paged_window_attention(q, kp, vp, rows, pos, impl="lax")
        got = paged_window_attention(q, kp, vp, rows, pos, impl="pallas",
                                     interpret=True, block_kv=3,
                                     slots_tile=2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        if kv_heads == heads:
            return
        r = heads // kv_heads
        wide = lambda pool: jnp.repeat(
            pool.reshape(NB, BL, kv_heads, HD), r, axis=2) \
            .reshape(NB, BL, heads * HD)
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(paged_window_attention(
                q, wide(kp), wide(vp), rows, pos, impl="lax")),
            rtol=1e-6, atol=1e-6)

    def test_a_window_wider_than_the_kernel_holds_goes_in_sub_windows(
            self, monkeypatch):
        """A window past ``max_window`` is attended in equal sub-windows
        over the same chain: the same rows come back."""
        import mmlspark_tpu.dl.pallas_paged_attention as paged
        w, bl = 20, 8
        kp, vp = _pools(3, bl=bl, heads=1)
        rows = jnp.asarray([[6, 7, 8, 9, TRASH_BLOCK]], jnp.int32)
        pos = jnp.asarray([4 * bl - w], jnp.int32)
        q = _q(31, 1, 4, w, HD)
        want = paged_window_attention(q, kp, vp, rows, pos, impl="lax")
        monkeypatch.setattr(paged, "max_window", lambda *a: 8)
        got = paged_window_attention(q, kp, vp, rows, pos, impl="pallas",
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_both_forms_of_the_cell_are_driven(self):
        """The cases fall on both sides of the row count up to which
        every head shares one product."""
        assert H * 4 <= _BATCHED_ROWS and H6 * 1 <= _BATCHED_ROWS < H6 * 3
        assert 16 * 1 <= _BATCHED_ROWS < 16 * 3

    @pytest.mark.parametrize("w,block_kv", [(1, 128), (1, 32), (3, 128)],
                             ids=["decode", "decode-chunked", "window3"])
    def test_cell_geometry_matches_lax(self, w, block_kv):
        """16 heads of 128 over blocks of 128: the decode row takes the
        batched form (16 rows), a window of 3 the loop over the heads;
        chains end in the first, a middle and the last table entry, one
        tail block holds a single position, one slot is all padding."""
        q, kp, vp, rows, pos = _cell_case(w)
        ref = paged_window_attention(q, kp, vp, rows, pos, impl="lax")
        got = paged_window_attention(q, kp, vp, rows, pos,
                                     impl="pallas", interpret=True,
                                     block_kv=block_kv, slots_tile=1)
        assert not np.asarray(got[4]).any()
        np.testing.assert_allclose(np.asarray(got[:4]),
                                   np.asarray(ref[:4]),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("heads,w", [(H, 1), (H, 3), (H6, 3)])
    def test_inactive_all_trash_slot_emits_zero(self, heads, w):
        kp, vp = _pools(9, heads=heads)
        rows, pos = _ragged_case(w)
        rows = rows.at[2].set(TRASH_BLOCK)     # slot 2 fully inactive
        pos = pos.at[2].set(0)
        q = _q(11, S, heads, w, HD)
        got = paged_window_attention(q, kp, vp, rows, pos,
                                     impl="pallas", interpret=True)
        assert not np.asarray(got[2]).any()
        ref = paged_window_attention(q, kp, vp, rows, pos, impl="lax")
        np.testing.assert_allclose(np.asarray(got[:2]),
                                   np.asarray(ref[:2]),
                                   rtol=1e-5, atol=1e-5)

    def test_blocks_past_the_window_are_skipped_not_read(self):
        """A chain may hold blocks the window has not reached (a prompt's
        chain is whole while its chunks prefill): they are neither
        fetched nor computed, and what they hold changes nothing."""
        kp, vp = _pools(4)
        rows, _ = _ragged_case(1)
        pos = jnp.asarray([BL - 1, 2 * BL + 1, 0], jnp.int32)
        q = _q(12, S, H, 1, HD)
        got = paged_window_attention(q, kp, vp, rows, pos,
                                     impl="pallas", interpret=True)
        beyond = np.asarray([2, 9])            # slot 0's and 1's unreached
        bad = [p.at[beyond].set(jnp.nan) for p in (kp, vp)]
        again = paged_window_attention(q, *bad, rows, pos,
                                       impl="pallas", interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(again))
        ref = paged_window_attention(q, kp, vp, rows, pos, impl="lax")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("w", [1, 3])
    def test_index_map_repeats_the_last_live_block(self, w):
        """The index map alone, over a table with padded rows: it never
        names an entry past ``(pos + w - 1) // block_len``, and repeats
        that one for every later cell (an unchanged index is a copy the
        pipeline does not issue)."""
        rows, pos = _ragged_case(w)
        rows_np, pos_np = np.asarray(rows), np.asarray(pos)
        fetches = 0
        for s in range(S):
            last = (pos_np[s] + w - 1) // BL
            named = [int(chain_block(rows, pos[:, None], s, j, w=w,
                                     block_len=BL)) for j in range(MB)]
            assert named[:last + 1] == list(rows_np[s, :last + 1])
            assert set(named[last:]) == {int(rows_np[s, last])}
            assert TRASH_BLOCK not in named
            fetches += len(set(named))
        assert fetches == int((rows_np != TRASH_BLOCK).sum())
        # a slot whose row is all padding maps to the trash block once
        empty = jnp.full((1, MB), TRASH_BLOCK, jnp.int32)
        assert {int(chain_block(empty, jnp.zeros((1, 1), jnp.int32), 0, j,
                                w=w, block_len=BL))
                for j in range(MB)} == {TRASH_BLOCK}

    @pytest.mark.slow
    @pytest.mark.parametrize("w", [1, 4])
    def test_matches_lax_large(self, w):
        nb, bl, mb, s, heads, hd = 34, 16, 8, 5, 4, 32
        kp, vp = _pools(w, nb=nb, bl=bl, heads=heads, hd=hd)
        rng = np.random.default_rng(40 + w)
        rows = np.full((s, mb), TRASH_BLOCK, np.int32)
        for i in range(s):
            n = int(rng.integers(1, mb + 1))
            rows[i, :n] = 1 + rng.choice(nb - 1, size=n, replace=False)
        lengths = (rows != TRASH_BLOCK).sum(1) * bl
        pos = (lengths - w).astype(np.int32)
        q = _q(50 + w, s, heads, w, hd)
        ref = paged_window_attention(q, kp, vp, jnp.asarray(rows),
                                     jnp.asarray(pos), impl="lax")
        for block_kv, slots_tile in [(bl, 1), (5, 2), (2, 4)]:
            got = paged_window_attention(
                q, kp, vp, jnp.asarray(rows), jnp.asarray(pos),
                impl="pallas", interpret=True, block_kv=block_kv,
                slots_tile=slots_tile)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(ref),
                                       rtol=1e-5, atol=1e-5)


class TestKernelTuned:
    def test_tuned_winner_consulted_and_equal(self):
        from mmlspark_tpu.dl.pallas_paged_attention import _resolve_paged
        from mmlspark_tpu.utils.platform import target_platform
        kp, vp = _pools(7)
        rows, pos = _ragged_case(1)
        q = _q(21, S, H, 1, HD)
        plat = target_platform()
        context = MB * BL
        autotune.clear()
        try:
            timed = {(BL, 2): 0.5}
            autotune.tune_paged_attention(
                context, BL, H, HD, platform=plat, persist=False,
                registry=MetricsRegistry(),
                measure=lambda c: timed.get(
                    (c["block_kv"], c["slots_tile"]), 2.0))
            # the resolver sees the winner at call/trace time
            assert _resolve_paged(None, None, context=context,
                                  block_len=BL, hd=HD, w=1,
                                  platform=plat) == (BL, 2)
            tuned = paged_window_attention(q, kp, vp, rows, pos,
                                           impl="pallas",
                                           interpret=True)
            default = paged_window_attention(q, kp, vp, rows, pos,
                                             impl="pallas",
                                             interpret=True,
                                             block_kv=BL, slots_tile=1)
            # slots_tile is pure launch geometry: tuned == default
            np.testing.assert_array_equal(np.asarray(tuned),
                                          np.asarray(default))
        finally:
            autotune.clear()

    def test_untuned_falls_back_to_defaults(self):
        from mmlspark_tpu.dl.pallas_paged_attention import _resolve_paged
        autotune.clear()
        assert _resolve_paged(None, None, context=64, block_len=8,
                              hd=16, w=1, platform="nosuchpf") == (8, 1)
        # explicit caller values always win and clamp into the block
        assert _resolve_paged(999, 3, context=64, block_len=8, hd=16,
                              w=1, platform="nosuchpf") == (8, 3)


# ---------------------------------------------------------- engine level

VOCAB, MAXNEW = 32, 6
ENG_BL, MAX_SEQ = 4, 36            # >= 9 pool blocks of context


@pytest.fixture(scope="module")
def lm():
    enc = TextEncoder(vocab=VOCAB, width=16, depth=1, heads=2,
                      mlp_dim=32, dtype=jnp.float32,
                      attention_fn=make_attention_fn("dense",
                                                     causal=True))
    module = MaskedLMModel(enc)
    variables = module.init(jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    return module, variables


@pytest.fixture(scope="module")
def draft_lm(lm):
    module, _ = lm
    variables = module.init(jax.random.PRNGKey(7),
                            np.zeros((1, 8), np.int32))
    return module, variables


def _prompts(seed=0, sizes=(30, 21, 9, 26)):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, VOCAB, size=n).astype(np.int32)
            for n in sizes]


def _ref(lm, prompts, max_new=MAXNEW):
    module, variables = lm
    return {i: np.asarray(generate(module, variables, p[None, :],
                                   max_new_tokens=max_new,
                                   temperature=0.0)[0])
            for i, p in enumerate(prompts)}


def _run(lm, prompts, **kw):
    module, variables = lm
    eng = LLMEngine(module, variables, slots=2, block_len=ENG_BL,
                    max_seq_len=MAX_SEQ, **kw)
    for i, p in enumerate(prompts):
        eng.submit(i, p, MAXNEW)
    return eng, eng.run_until_drained()


def _counter_sum(reg, name):
    return sum(v for k, v in reg.snapshot().items()
               if k.startswith(name))


class TestLongContextIdentity:
    @pytest.mark.parametrize("prompt_set", [
        {}, dict(seed=5, sizes=(18, 11, 25))],
        ids=["four-prompts", "three-prompts"])
    def test_greedy_ragged_matches_generate(self, lm, prompt_set):
        prompts = _prompts(**prompt_set)
        ref = _ref(lm, prompts)
        reg = MetricsRegistry()
        eng, got = _run(lm, prompts, registry=reg,
                        service=f"llmlongg{len(prompts)}")
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(got[i],
                                          ref[i][:len(p) + MAXNEW])
        assert _counter_sum(reg, "gen_decode_attn_seconds_count") > 0

    def test_speculative_disagreeing_draft(self, lm, draft_lm):
        dmod, dvar = draft_lm
        # spec_k headroom: draft windows write up to spec_k positions
        # past the committed length, so chains need max_seq_len +
        # spec_k resident positions
        prompts = _prompts(seed=3, sizes=(28, 19, 7, 24))
        ref = _ref(lm, prompts)
        reg = MetricsRegistry()
        eng, got = _run(lm, prompts, draft_module=dmod,
                        draft_variables=dvar, spec_k=2, registry=reg,
                        service="llmlongs")
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(got[i],
                                          ref[i][:len(p) + MAXNEW])
        # the draft genuinely disagreed somewhere mid-window: the
        # cumulative accept ratio ends below 1
        ratios = [v for k, v in reg.snapshot().items()
                  if k.startswith("gen_spec_accept_ratio")]
        assert ratios and ratios[0] < 1.0

    def test_eviction_pressure_mid_generation(self, lm):
        prompts = _prompts(seed=11)
        ref = _ref(lm, prompts)
        reg = MetricsRegistry()
        module, variables = lm
        # pool fits two resident chains but not their parked prefix
        # caches too: admitting later sequences evicts mid-run
        eng = LLMEngine(module, variables, slots=2, block_len=ENG_BL,
                        max_seq_len=MAX_SEQ, num_blocks=20,
                        registry=reg, service="llmevict")
        for i, p in enumerate(prompts):
            eng.submit(i, p, MAXNEW)
        got = eng.run_until_drained()
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(got[i],
                                          ref[i][:len(p) + MAXNEW])
        assert _counter_sum(reg, "kv_evictions_total") > 0
