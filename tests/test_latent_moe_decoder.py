"""The latent-attention / dropless-expert decoder against the benchmark's
plain reference (``benchmark/references/deepseek_v2.py``, which imports
nothing of the program) at a small size: every width shrunk, the structure
whole (one dense layer, expert layers of 16 experts in 4 groups, top-3 of
2 groups, 2 shared, latent 32 + rope 8)."""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import deepseek_v2 as ref  # noqa: E402
from mmlspark_tpu.dl import pallas_paged_attention as ppa  # noqa: E402
from mmlspark_tpu.dl.latent_moe_decoder import LatentMoEDecoder  # noqa: E402
from mmlspark_tpu.dl.paged_kv import init_pools, pool_block_bytes  # noqa: E402
from mmlspark_tpu.models.moe import dropless_moe, route_top_k  # noqa: E402
from mmlspark_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from mmlspark_tpu.obs.tracing import tracer  # noqa: E402
from mmlspark_tpu.serving.llm import LLMEngine  # noqa: E402


def small_cfg(**over):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "deepseek-v2.doc-qa.json")) as f:
        tiny = json.load(f)["tiny"]["config"]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek-v2.json")) as f:
        return {**json.load(f), **tiny, "param_dtype": "float32",
                "cache_dtype": "float32", **over}


@pytest.fixture(scope="module")
def model():
    cfg = small_cfg()
    return cfg, ref.make_weights(cfg, 11), LatentMoEDecoder(
        cfg, dtype=jnp.float32)


def _rows_for(chains, max_blocks):
    rows = np.zeros((len(chains), max_blocks), np.int32)
    for i, chain in enumerate(chains):
        rows[i, :len(chain)] = chain
    return jnp.asarray(rows)


def test_prefill_then_decode_through_the_pool_matches_the_reference(model):
    """Two sequences of different lengths: a prefill window each (one
    program call, the shorter padded), then decode steps of width 1, the
    logits of every fed row (``logits`` over every hidden row a ``walk``
    returns) against the reference's full forward pass.
    The walk computes attention absorbed, the reference published: in
    float32 the two agree to rounding."""
    cfg, weights, module = model
    bl, mb = 8, 6
    rng = np.random.default_rng(3)
    seqs = [rng.integers(1, 256, n) for n in (29, 18)]
    prefix = (21, 13)
    pools = init_pools(module.cache_spec(), 16, bl)
    rows = _rows_for([[1, 2, 3, 4], [5, 6, 7]], mb)
    w = 24
    toks = np.zeros((2, w), np.int32)
    for i, (s, p) in enumerate(zip(seqs, prefix)):
        toks[i, :p] = s[:p]
    lens = jnp.asarray(prefix)
    valid = jnp.arange(w)[None] < lens[:, None]

    def head(hidden):
        return module.apply({"params": weights}, hidden, method="logits")

    (hidden,), pools, counts = module.apply(
        {"params": weights},
        ((jnp.asarray(toks), rows, jnp.zeros(2, jnp.int32), valid),),
        pools)
    assert hidden.shape == (2, w, 64)        # hidden rows, no head
    logits = head(hidden)
    assert counts.shape == (5,) and int(counts[0]) > 0
    got = [[np.asarray(logits[i, :p])] for i, p in enumerate(prefix)]
    for step in range(5):
        pos = jnp.asarray([p + step for p in prefix], jnp.int32)
        tok = jnp.asarray([[s[p + step]] for s, p in zip(seqs, prefix)])
        (hidden,), pools, _ = module.apply(
            {"params": weights},
            ((tok, rows, pos, jnp.ones((2, 1), bool)),), pools)
        logits = head(hidden)
        for i in range(2):
            got[i].append(np.asarray(logits[i]))
    for i, (s, p) in enumerate(zip(seqs, prefix)):
        want = np.asarray(ref.forward(weights, cfg, s[:p + 5],
                                      np.arange(p + 5)))
        np.testing.assert_allclose(np.concatenate(got[i]), want,
                                   atol=2e-4, rtol=2e-4)


def test_latent_kernel_matches_its_lax_twin_at_ragged_chains():
    """Interpret mode against the lax formulation: chains of different
    lengths, two slots sharing a prefix block, a window of several rows,
    an idle slot on the trash block, a tile narrower than the window."""
    rng = np.random.default_rng(0)
    S, w, H, C, R, BL, NB = 4, 5, 4, 128, 8, 8, 12
    width = 256                              # lane-padded C + R
    pool = np.zeros((NB, BL, width), np.float32)
    pool[..., :C + R] = rng.standard_normal((NB, BL, C + R))
    q = np.zeros((S, w, H, width), np.float32)
    q[..., :C + R] = rng.standard_normal((S, w, H, C + R))
    rows = jnp.asarray([[1, 2, 3, 0], [1, 4, 0, 0], [5, 0, 0, 0],
                        [0, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([17, 9, 0, 0], jnp.int32)
    for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)):
        qd, pd = jnp.asarray(q, dtype), jnp.asarray(pool, dtype)
        want = ppa.paged_latent_attention(qd, pd, rows, pos, scale=0.3,
                                          value_dim=C, impl="lax")
        for tile in (2, 8):
            got = ppa._paged_latent_pallas(
                qd, pd, rows, pos, scale=0.3, value_dim=C, tile_w=tile,
                interpret=True)
            np.testing.assert_allclose(
                np.asarray(got[:3], np.float32),
                np.asarray(want[:3], np.float32), atol=tol, rtol=tol)


def test_routing_matches_the_reference_at_planted_near_ties():
    """Probabilities with planted near-ties and exact ties: between the
    last expert chosen and the first left out, and between the last group
    kept and the first dropped. The program's routing and the
    reference's choose the same experts with the same weights."""
    rng = np.random.default_rng(5)
    T, E, G, Gk, K = 64, 16, 4, 2, 3
    g = jax.nn.softmax(jnp.asarray(rng.standard_normal((T, E)) * 2,
                                   jnp.float32), axis=-1)
    g = np.array(g)
    for t in range(T):
        order = np.argsort(-g[t])
        if t % 3 == 0:            # the K-th and (K+1)-th a hair apart
            g[t, order[K]] = g[t, order[K - 1]] * (1 - 1e-7 * (t % 2))
        if t % 3 == 1:            # two groups' best exactly tied
            a, b = order[0], next(e for e in order if e // 4 != order[0] // 4)
            g[t, b] = g[t, a]
    g = jnp.asarray(g)
    experts, probs = route_top_k(g, top_k=K, groups=G, keep_groups=Gk)
    want = np.asarray(ref.routing_weights(g, groups=G, keep_groups=Gk,
                                          top_k=K, scale=16.0))
    got = np.zeros((T, E), np.float32)
    got[np.arange(T)[:, None], np.asarray(experts)] = 16.0 * np.asarray(probs)
    np.testing.assert_array_equal(got, want)
    assert (np.count_nonzero(want, axis=1) == K).all()


def test_the_shares_add_up(model):
    """The four ``experts_held`` ranges' partial sums, with the shared
    experts counted once, equal the uncut layer of the reference."""
    cfg, _, _ = model
    whole = small_cfg(experts_held=[0, 16])
    weights = ref.make_weights(whole, 13)
    lw = weights["layers"][1]
    d = ref.dims(whole)
    h = jnp.asarray(np.random.default_rng(1).standard_normal((24, 64)),
                    jnp.float32)
    want = ref.moe_layer(h, lw, d, held=(0, 16))         # h + FFN(RMS(h))
    total = jnp.zeros_like(h)
    for lo in range(0, 16, 4):
        part = small_cfg(experts_held=[lo, lo + 4])
        module = LatentMoEDecoder(part, dtype=jnp.float32)
        mine = {**lw, **{k: lw[k][lo:lo + 4]
                         for k in ("exp_gate", "exp_up", "exp_down")}}
        u = module._rms(h, lw["ffn_norm"])
        ffn, counts = module._expert_layer(u, mine, jnp.ones(24, bool))
        shared = module._gated(u, lw["shared_gate"], lw["shared_up"],
                               lw["shared_down"])
        total = total + (ffn - shared)                   # the routed part
        # and the reference given the same share agrees with the program
        np.testing.assert_allclose(
            ffn - shared,
            ref.moe_layer(h, mine, d, held=(lo, lo + 4),
                          with_shared=False) - h, atol=1e-4, rtol=1e-4)
        assert int(counts[0] + counts[1]) == 24 * 3
    np.testing.assert_allclose(h + total + shared, want, atol=2e-4,
                               rtol=2e-4)


def test_dropless_moe_leaves_padding_and_absent_experts_out():
    rng = np.random.default_rng(2)
    T, D, F, E, k = 12, 16, 8, 8, 2
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    probs = jax.nn.softmax(jnp.asarray(rng.standard_normal((T, E))), -1)
    experts, weights = route_top_k(probs, top_k=k)
    w = [jnp.asarray(rng.standard_normal(s), jnp.float32)
         for s in ((E, D, F), (E, D, F), (E, F, D))]
    valid = jnp.arange(T) < 9
    out, counts = dropless_moe(x, experts, weights, w[0][2:6], w[1][2:6],
                               w[2][2:6], held=(2, 6), valid=valid)
    want = np.zeros((T, D), np.float32)
    held = 0
    for t in range(9):
        for e, p in zip(np.asarray(experts[t]), np.asarray(weights[t])):
            if 2 <= e < 6:
                held += 1
                want[t] += p * np.asarray(
                    (jax.nn.silu(x[t] @ w[0][e]) * (x[t] @ w[1][e]))
                    @ w[2][e])
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)
    assert int(counts[0]) == held and int(counts[1]) == 9 * k - held
    assert np.all(np.asarray(out[9:]) == 0)


def test_walk_gives_the_same_rows_through_the_grouped_kernels(
        model, grouped_kernels):
    """The expert products through the Pallas kernels give the hidden rows
    of the ``ragged_dot`` passes (float32: the order of the sums) for a
    prefill window of two sequences, one of them padded, and a decode
    step; every count but the tiles' rows is the same."""
    cfg, weights, module = model
    bl, mb, w = 8, 6, 24
    rng = np.random.default_rng(9)
    toks = jnp.asarray(rng.integers(1, 256, (2, w)), jnp.int32)
    rows = _rows_for([[1, 2, 3, 4], [5, 6, 7]], mb)
    valid = jnp.arange(w)[None] < jnp.asarray([[21], [13]])

    def walk():
        pools = init_pools(module.cache_spec(), 16, bl)
        (hidden,), pools, c = module.apply(
            {"params": weights},
            ((toks, rows, jnp.zeros(2, jnp.int32), valid),), pools)
        (step,), _, c2 = module.apply(
            {"params": weights}, ((toks[:, :1], rows,
                                   jnp.asarray([21, 13], jnp.int32),
                                   jnp.ones((2, 1), bool)),), pools)
        return (np.concatenate([np.asarray(hidden)[0, :21],
                                np.asarray(hidden)[1, :13],
                                np.asarray(step)[:, 0]]),
                dict(zip(module.walk_stats, np.asarray(c) + np.asarray(c2))))

    via_kernels, a = walk()
    grouped_kernels.undo()
    via_lax, b = walk()
    np.testing.assert_allclose(via_kernels, via_lax, rtol=1e-4, atol=1e-4)
    assert {k: v for k, v in a.items() if k != "moe_rows_computed"} == \
        {k: v for k, v in b.items() if k != "moe_rows_computed"}
    assert b["moe_rows_computed"] == b["moe_pairs_held"]
    assert a["moe_rows_computed"] > a["moe_pairs_held"] > 0


def test_cache_spec_prices_and_shapes_the_latent_pool(model):
    _, _, module = model
    spec = module.cache_spec()
    # one array a layer: the decoder states its 40 numbers a token in
    # whole lanes, and the pools are allocated as stated
    assert spec == ((((128,), jnp.dtype("float32")),),) * 3
    pools = init_pools(spec, 5, 8)
    assert [tuple(p.shape) for (p,) in pools] == [(5, 8, 128)] * 3
    assert pool_block_bytes(spec, 8) == (3 + 2) * 8 * 128 * 4
    full = LatentMoEDecoder(small_cfg(
        num_attention_heads=128, kv_lora_rank=512, qk_rope_head_dim=64),
        dtype=jnp.bfloat16)
    assert full.cache_width == 640           # 576 numbers in five tiles
    assert full.cache_spec()[0] == (((640,), jnp.dtype("bfloat16")),)
    assert full.max_window() == 192


@pytest.fixture()
def engine_of(model):
    cfg, weights, module = model

    def build(**kw):
        reg = MetricsRegistry()
        eng = LLMEngine(module, {"params": weights}, slots=3, block_len=8,
                        max_seq_len=80, num_blocks=40, prefill_batch=1,
                        hbm_fraction=1.0, service="latent", registry=reg,
                        **kw)
        return eng, reg
    return build


def _value(reg, name):
    return next(m for m in reg.metrics(name) if m.name == name)


def test_engine_serves_the_reference_and_reuses_an_indexed_document(
        model, engine_of):
    """Through ``LLMEngine.submit/step``: a request whose document is in
    the prefix index prefills only its suffix and serves the same tokens
    as a cold one, and every served token is the reference's first (or
    within rounding of it) teacher-forced along the stream."""
    cfg, weights, _ = model
    rng = np.random.default_rng(9)
    doc = rng.integers(1, 256, 32)
    prompt = np.concatenate([doc, rng.integers(1, 256, 7)])
    cold, _ = engine_of()
    cold.submit("a", prompt, 9)
    served_cold = cold.run_until_drained()["a"]

    warm, reg = engine_of()
    warm.submit("doc", doc, 1)
    warm.run_until_drained()
    reused = _value(reg, "kv_prefix_tokens_reused_total")
    assert reused.value(service="latent") == 0
    warm.submit("b", prompt, 9)
    served_warm = warm.run_until_drained()["b"]
    assert reused.value(service="latent") == 32       # the whole document
    np.testing.assert_array_equal(served_warm, served_cold)

    served = served_warm[len(prompt):]
    margins, _, _ = ref.sample_margins(weights, cfg, prompt, served)
    assert margins.max() < 1e-3

    # the expert layers' counts reached the engine's registry
    held = _value(reg, "moe_pairs_held_total").value(service="latent")
    absent = _value(reg, "moe_pairs_absent_total").value(service="latent")
    assert held > 0 and absent > 0
    assert _value(reg, "moe_experts_touched_total").value(
        service="latent") > 0
    assert _value(reg, "moe_expert_load_max").value(service="latent") >= 1
    # every fed token through two expert layers, three experts each
    assert (held + absent) % (2 * 3) == 0


def test_engine_boundaries_leave_step_prefill_and_decode_spans(engine_of):
    eng, _ = engine_of()
    # the roots this engine adds, by id: other tests of the same process
    # may have filled the ring to its size, so its length need not move
    before = {s.span_id for s in tracer.recent("llm.step")}
    eng.submit("s", np.arange(1, 12), 4)
    eng.run_until_drained()
    roots = [s for s in tracer.recent("llm.step") if s.span_id not in before]
    assert len(roots) >= 3
    kids = [s for s in tracer.recent()
            if s.parent_id in {r.span_id for r in roots}]
    names = [s.name for s in kids]
    assert names.count("llm.prefill") == 1
    assert names.count("llm.decode") == len(roots)
    assert all(len([k for k in kids if k.parent_id == r.span_id]) <= 3
               for r in roots)


#: case -> (widest window, length of the prompt that rides, its new tokens)
RIDE_CASES = {"suffix_of_one_window": (192, 20, 5),
              "several_chunks": (8, 21, 4),
              "one_new_token": (8, 13, 1)}


@pytest.mark.parametrize("case", sorted(RIDE_CASES))
def test_a_riding_window_serves_the_tokens_and_counts_of_todays_order(
        model, engine_of, case):
    """A prompt that arrives while another decodes rides into the decode
    step's program: the served tokens, and every expert pair counted, are
    those of the engine that prefills alone and then decodes (``rider``
    None: today's order). Each expert is touched once a call where the
    two programs touched it once each."""
    max_window, length, new = RIDE_CASES[case]
    rng = np.random.default_rng(17)
    first, second = rng.integers(1, 256, 11), rng.integers(1, 256, length)
    served, counts, rode = {}, {}, {}
    for order in ("ride", "alone"):
        eng, reg = engine_of()
        eng.prefiller.max_window = max_window
        eng.prefiller.ride_from = 1
        if order == "alone":
            eng.prefiller.rider = None
        eng.submit("a", first, 10)
        out = dict(eng.step())
        eng.submit("b", second, new)
        out.update(eng.run_until_drained())
        served[order] = out
        counts[order] = {
            name: _value(reg, f"moe_{name}_total").value(service="latent")
            for name in ("pairs_held", "pairs_absent", "experts_touched")}
        rode[order] = _value(reg, "gen_prefill_rows_total").value(
            service="latent", ride="decode")
    assert rode == {"ride": length, "alone": 0}
    for seq_id in ("a", "b"):
        np.testing.assert_array_equal(served["ride"][seq_id],
                                      served["alone"][seq_id])
    for name in ("pairs_held", "pairs_absent"):
        assert counts["ride"][name] == counts["alone"][name] > 0
    assert 0 < counts["ride"]["experts_touched"] \
        <= counts["alone"]["experts_touched"]


def test_a_riding_boundary_keeps_its_prefill_and_decode_spans(engine_of):
    """``llm.prefill`` is the host's part of the riding window,
    ``llm.decode`` the program and its fetch: siblings under ``llm.step``,
    which says how many rows rode."""
    eng, _ = engine_of()
    eng.prefiller.ride_from = 1
    eng.submit("a", np.arange(1, 12), 8)
    eng.step()
    eng.submit("b", np.arange(3, 20), 2)
    eng.step()
    # the boundary just taken: the newest root on the ring, which other
    # tests of the same process may have filled to its size
    root = tracer.recent("llm.step")[-1]
    assert root.attrs["ride_rows"] == 17
    kids = [s.name for s in tracer.recent() if s.parent_id == root.span_id]
    assert sorted(kids) == ["llm.decode", "llm.prefill"]
    eng.run_until_drained()
