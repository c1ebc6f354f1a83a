"""The block-sparse / lightning decoder against the benchmark's plain
reference (``benchmark/references/minicpm_sala.py``, which imports nothing
of the program) at a small size: every width shrunk, the structure whole
(8 query heads over 2 key heads, 4 lightning heads, sparse and lightning
layers mixed, a ``dense_len`` of 32 so that blocks are chosen at 33
tokens), its kernels against plain numpy, and prefix reuse by state
snapshot through ``LLMEngine``."""

import json
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import minicpm_sala as ref  # noqa: E402
from mmlspark_tpu.dl import pallas_lightning as pll  # noqa: E402
from mmlspark_tpu.dl import pallas_paged_attention as ppa  # noqa: E402
from mmlspark_tpu.dl.paged_kv import init_pools  # noqa: E402
from mmlspark_tpu.dl.sparse_linear_decoder import (  # noqa: E402
    SparseLinearDecoder, lightning_slopes)
from mmlspark_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from mmlspark_tpu.obs.tracing import tracer  # noqa: E402
from mmlspark_tpu.serving.llm import LLMEngine  # noqa: E402

BL = 16


def small_cfg(**over):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "minicpm-sala.long-doc-qa.json")) as f:
        tiny = json.load(f)["tiny"]["config"]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "minicpm-sala.json")) as f:
        return {**json.load(f), **tiny, "param_dtype": "float32",
                "cache_dtype": "float32", **over}


@pytest.fixture(scope="module")
def model():
    cfg = small_cfg()
    return cfg, ref.make_weights(cfg, 11), SparseLinearDecoder(
        cfg, dtype=jnp.float32, max_window=24)


# ------------------------------------------------------------- the kernels
def _naive_lightning(q, k, v, state, srows, pos, lens, slopes):
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    st = np.asarray(state, np.float64).copy()
    S, w, H, hd = q.shape
    o = np.zeros((S, w, H, hd))
    for s in range(S):
        for h in range(H):
            cur = st[srows[s], h] if pos[s] > 0 else np.zeros((hd, hd))
            for t in range(lens[s]):
                cur = np.exp(-slopes[h]) * cur + np.outer(k[s, t, h],
                                                          v[s, t, h])
                o[s, t, h] = q[s, t, h] @ cur
            st[srows[s], h] = cur
    return o, st


@pytest.mark.parametrize("impl", ["lax", "pallas"])
@pytest.mark.parametrize("w", [1, 12, 128, 192],
                         ids=["step", "w12", "w128", "w192"])
def test_lightning_attention_is_the_recurrence(w, impl):
    rng = np.random.default_rng(w)
    S, H, hd = 3, 4, 16
    q, k, v = (jnp.asarray(rng.standard_normal((S, w, H, hd)), jnp.float32)
               for _ in range(3))
    state = jnp.asarray(rng.standard_normal((6, H, hd, hd)), jnp.float32)
    srows, pos = np.array([2, 4, 1]), np.array([5, 0, 7])
    lens = np.array([w, max(w - 3, 1), w])
    slopes = np.asarray([0.5, 0.1, 0.01, 0.001], np.float32)
    want_o, want_s = _naive_lightning(q, k, v, state, srows, pos, lens,
                                      slopes)
    o, s = pll.lightning_attention(q, k, v, state, srows, pos, lens, slopes,
                                   impl=impl, interpret=True)
    for i in range(S):
        np.testing.assert_allclose(np.asarray(o)[i, :lens[i]],
                                   want_o[i, :lens[i]], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-4, atol=2e-4)
    # rows of the pool no slot named are as they were
    np.testing.assert_array_equal(np.asarray(s)[[0, 3, 5]],
                                  np.asarray(state)[[0, 3, 5]])


def _listed_case(rng, T=5, G=2, R=4, hd=16, NB=12, bl=32, bs=8, K=6):
    q = jnp.asarray(rng.standard_normal((T, G, R, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((NB, bl, G * hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NB, bl, G * hd)), jnp.float32)
    per = bl // bs
    logical = np.full((T, G, K), -1, np.int32)
    phys = np.zeros((T, G, K), np.int32)
    qpos = np.array([3, 20, 47, 60, 63], np.int32)
    chain = rng.permutation(np.arange(1, NB))[:4]
    for t in range(T):
        for g in range(G):
            nblk = qpos[t] // bs + 1
            pick = np.sort(rng.permutation(nblk)[:min(nblk, K - t % 2)])
            ph = chain[pick * bs // bl] * per + pick * bs % bl // bs
            logical[t, g, :len(pick)] = pick
            phys[t, g, :len(pick)], phys[t, g, len(pick):] = ph, ph[-1]
    # the pool as it rests: a head's key and value side by side
    kv = jnp.concatenate([kp.reshape(NB, bl, G, hd),
                          vp.reshape(NB, bl, G, hd)], -1).reshape(NB, bl, -1)
    return q, kp, vp, kv, phys, logical, qpos, bs, per


@pytest.mark.parametrize("impl", ["lax", "pallas"])
def test_sparse_block_attention_attends_the_listed_blocks(impl):
    """Grouped heads (4 query heads a key head), each (token, key head)
    its own list, the last listed block seen as far as the query."""
    q, kp, vp, kv, phys, logical, qpos, bs, per = _listed_case(
        np.random.default_rng(0))
    T, G, R, hd = q.shape
    want = np.zeros((T, G, R, hd))
    for t in range(T):
        for g in range(G):
            ks, vs, ok = [], [], []
            for m, ph in zip(logical[t, g], phys[t, g]):
                if m < 0:
                    continue
                rows = slice(ph % per * bs, (ph % per + 1) * bs)
                lanes = slice(g * hd, (g + 1) * hd)
                ks.append(np.asarray(kp[ph // per, rows, lanes], np.float64))
                vs.append(np.asarray(vp[ph // per, rows, lanes], np.float64))
                ok.append(m * bs + np.arange(bs) <= qpos[t])
            ks, vs, ok = (np.concatenate(a) for a in (ks, vs, ok))
            s = np.asarray(q[t, g], np.float64) @ ks.T * hd ** -0.5
            s[:, ~ok] = -np.inf
            p = np.exp(s - s.max(-1, keepdims=True))
            want[t, g] = p / p.sum(-1, keepdims=True) @ vs
    got = ppa.sparse_block_attention(q, kv, phys, logical, qpos,
                                     block_size=bs, scale=hd ** -0.5,
                                     impl=impl, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["lax", "pallas"])
def test_select_scores_reads_the_compressed_keys_through_the_table(impl):
    rng = np.random.default_rng(1)
    S, G, M, hd, NB, rpb, MB = 2, 2, 12, 16, 9, 4, 5
    ck = jnp.asarray(rng.standard_normal((NB, rpb, G * hd)), jnp.float32)
    rows = rng.integers(0, NB, size=(S, MB)).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((S, G, M, hd)), jnp.float32)
    chain = np.asarray(ck, np.float64).reshape(NB, rpb, G, hd)[rows]
    want = np.einsum("sgmd,scgd->sgmc", np.asarray(q, np.float64),
                     chain.reshape(S, MB * rpb, G, hd)) * 0.25
    got = ppa.select_scores(q, ck, rows, scale=0.25, impl=impl,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_the_slopes_are_taken_at_the_published_depth_and_index():
    cfg = small_cfg()
    got = lightning_slopes(cfg, 1)
    np.testing.assert_allclose(got, ref.slopes(cfg, 1))
    H, L, l = 4, 8, cfg["layer_offset"] + 1
    np.testing.assert_allclose(
        got, 2.0 ** (-8 * (np.arange(H) + 1) / H) * (1 - l / (L - 1) + 1e-5),
        rtol=1e-6)


# ----------------------------------------------- the walk and the reference
def _walk_all(module, weights, tokens, windows, *, state_slots=2):
    """Feed ``tokens`` through ``module.walk`` in the given windows (the
    last ones of width 1) through fresh pools; the logits of every row."""
    n = len(tokens)
    blocks = -(-n // BL)
    pools = init_pools(module.cache_spec(), blocks + 2, BL, state_slots)
    rows = jnp.asarray(np.arange(1, blocks + 1, dtype=np.int32)[None])
    out, pos = [], 0
    counts = np.zeros(3, np.int64)
    for w in windows:
        k = min(w, n - pos)
        toks = np.zeros((1, w), np.int32)
        toks[0, :k] = tokens[pos:pos + k]
        (hidden,), pools, c = module.apply(
            {"params": weights},
            ((jnp.asarray(toks), rows, jnp.asarray([pos], jnp.int32),
              jnp.arange(w)[None] < k, jnp.asarray([1], jnp.int32)),),
            pools, method="walk")
        out.append(np.asarray(module.apply(
            {"params": weights}, hidden, method="logits"))[0, :k])
        counts += np.asarray(c)
        pos += k
    assert pos == n
    return np.concatenate(out), counts


@pytest.mark.parametrize("case", ["dense_side", "both_sides"])
def test_prefill_in_chunks_then_decode_matches_the_reference(model, case):
    """Windows of 24 (so that a chunk straddles ``dense_len`` = 32 and the
    compressed keys, the state and the chosen blocks are carried from
    chunk to chunk), then decode steps of width 1: the logits of every fed
    row against the reference's full forward pass, in float32."""
    cfg, weights, module = model
    n = 30 if case == "dense_side" else 70
    tokens = np.random.default_rng(3).integers(1, 256, n)
    got, counts = _walk_all(module, weights, tokens,
                            [24] * ((n - 6) // 24 + 1) + [1] * 24)
    want = np.asarray(ref.forward(weights, cfg, tokens, np.arange(n)))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    dense_rows = 2 * min(n, 32)                       # two sparse layers
    assert counts[2] == dense_rows
    if case == "both_sides":
        # past dense_len a row attends at most topk = 6 of its blocks
        assert counts[0] < counts[1]


def test_a_window_is_a_row_at_a_time(model):
    """One window of 40 rows and forty steps of width 1 give the same
    logits: the chunked form of the state, the compressed keys completed
    inside a window and each row's own choice of blocks."""
    cfg, weights, module = model
    wide = SparseLinearDecoder(cfg, dtype=jnp.float32, max_window=64)
    tokens = np.random.default_rng(5).integers(1, 256, 40)
    a, _ = _walk_all(wide, weights, tokens, [40])
    b, _ = _walk_all(module, weights, tokens, [1] * 40)
    np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


# ------------------------------------------------------- through the engine
def _engine(model, reg, **kw):
    cfg, weights, module = model
    kw = {"slots": 2, "block_len": BL, "max_seq_len": 128, "num_blocks": 24,
          "state_slots": 4, "prefill_batch": 2, "hbm_fraction": 1.0, **kw}
    return LLMEngine(module, {"params": weights}, service="sala",
                     registry=reg, **kw)


def _counter(reg, name):
    return next(m for m in reg.metrics(name) if m.name == name).value(
        service="sala")


def test_greedy_serving_is_the_references_argmax(model):
    """Two requests of different lengths in one prefill batch, chunked
    prefill, then decode: teacher-forced along what was served, every
    served token is the reference's first."""
    cfg, weights, _ = model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 256, n).astype(np.int32) for n in (75, 41)]
    reg = MetricsRegistry()
    eng = _engine(model, reg)
    for i, p in enumerate(prompts):
        eng.submit(i, p, 8)
    done = eng.run_until_drained()
    samples = [(p, done[i][len(p):], 0) for i, p in enumerate(prompts)]
    got = dict((name, value) for name, value, _ in ref.compare(
        weights, cfg, samples, {n: 0.0 for n in ref.NUMBERS}))
    assert got["argmax_margin_mean"] <= 1e-3, got
    assert _counter(reg, "sparse_blocks_chosen_total") > 0
    assert _counter(reg, "sparse_dense_rows_total") > 0
    assert _counter(reg, "kv_state_slots_used") == 0   # all given back


def test_a_prefix_hit_restores_the_snapshot_and_serves_a_cold_runs_tokens(
        model):
    rng = np.random.default_rng(9)
    doc = rng.integers(1, 256, 64).astype(np.int32)    # four whole blocks
    ask = np.concatenate([doc, rng.integers(1, 256, 7).astype(np.int32)])
    cold = _engine(model, MetricsRegistry())
    cold.submit("q", ask, 10)
    want = cold.run_until_drained()["q"]
    reg = MetricsRegistry()
    warm = _engine(model, reg)
    warm.submit("doc", doc, 1)
    warm.run_until_drained()
    assert _counter(reg, "kv_state_snapshots") == 1
    warm.submit("q", ask, 10)
    got = warm.run_until_drained()["q"]
    np.testing.assert_array_equal(got, want)
    assert _counter(reg, "kv_state_restores_total") == 1
    assert _counter(reg, "kv_prefix_tokens_reused_total") == 64
    restores = [s for s in tracer.recent()
                if s.name == "llm.state_restore"]
    prefills = {s.span_id for s in tracer.recent()
                if s.name == "llm.prefill"}
    assert restores and restores[-1].parent_id in prefills


def test_an_evicted_snapshot_makes_the_prefix_a_miss_not_a_wrong_answer(
        model):
    rng = np.random.default_rng(13)
    docs = [rng.integers(1, 256, 48).astype(np.int32) for _ in range(2)]
    ask = np.concatenate([docs[0], rng.integers(1, 256, 5).astype(np.int32)])
    cold = _engine(model, MetricsRegistry())
    cold.submit("q", ask, 6)
    want = cold.run_until_drained()["q"]
    reg = MetricsRegistry()
    eng = _engine(model, reg, slots=1, state_slots=2, prefill_batch=1)
    for i, doc in enumerate(docs):     # the second's snapshot takes the
        eng.submit(f"doc{i}", doc, 1)  # first's row: one live, one kept
        eng.run_until_drained()
    assert _counter(reg, "kv_state_snapshot_evictions_total") == 1
    eng.submit("q", ask, 6)
    got = eng.run_until_drained()["q"]
    np.testing.assert_array_equal(got, want)
    assert _counter(reg, "kv_state_restores_total") == 0
    assert _counter(reg, "kv_prefix_tokens_reused_total") == 0


def test_speculation_beside_a_state_is_refused(model):
    cfg, weights, module = model
    with pytest.raises(ValueError):
        LLMEngine(module, {"params": weights}, draft_module=module,
                  draft_variables={"params": weights}, spec_k=2,
                  block_len=BL, num_blocks=8, registry=MetricsRegistry())


#: case -> (tokens the question adds to the 64-token document, new tokens)
RIDE_CASES = {
    # restored, then 7 rows in one window
    "restore_then_one_window": (7, 6),
    # restored; two new whole blocks make a snapshot at 96: [64, 96) rides
    # in two windows, the cut falls between two boundaries, [96, 104) rides
    "restore_then_a_cut_between_two_boundaries": (40, 6),
    # finished by its prefill alone, a cut at 80 on its way
    "one_new_token": (21, 1),
}


@pytest.mark.parametrize("case", sorted(RIDE_CASES))
def test_a_riding_window_restores_cuts_and_serves_todays_tokens(model, case):
    """A question on an indexed document arrives while another request
    decodes: its snapshot is restored before the boundary's program, its
    rows ride in the decode step's program (a cut at ``snapshot_at``
    between two of them), and the tokens, the restores and the snapshots
    are those of the engine that prefills alone (``rider`` None)."""
    extra, new = RIDE_CASES[case]
    rng = np.random.default_rng(21)
    doc = rng.integers(1, 256, 64).astype(np.int32)    # four whole blocks
    other = rng.integers(1, 256, 37).astype(np.int32)
    ask = np.concatenate([doc, rng.integers(1, 256, extra).astype(np.int32)])
    served, seen = {}, {}
    for order in ("ride", "alone"):
        reg = MetricsRegistry()
        eng = _engine(model, reg, slots=3, state_slots=6, prefill_batch=1)
        eng.prefiller.ride_from = 1
        if order == "alone":
            eng.prefiller.rider = None
        eng.submit("doc", doc, 1)
        eng.run_until_drained()
        eng.submit("other", other, 12)
        out = dict(eng.step())
        eng.submit("q", ask, new)
        out.update(eng.run_until_drained())
        served[order] = out
        seen[order] = {name: _counter(reg, name) for name in (
            "kv_state_restores_total", "kv_state_snapshots",
            "kv_prefix_tokens_reused_total", "sparse_blocks_chosen_total",
            "sparse_dense_rows_total")}
        rode = next(m for m in reg.metrics("gen_prefill_rows_total")
                    if m.name == "gen_prefill_rows_total").value(
                        service="sala", ride="decode")
        assert rode == (extra if order == "ride" else 0)
        assert _counter(reg, "kv_state_slots_used") == 0   # all given back
    for seq_id in ("other", "q"):
        np.testing.assert_array_equal(served["ride"][seq_id],
                                      served["alone"][seq_id])
    assert seen["ride"] == seen["alone"]
    assert seen["ride"]["kv_state_restores_total"] == 1
    # the document's, the other request's two whole blocks', and the
    # question's where it brings a whole block of its own
    assert seen["ride"]["kv_state_snapshots"] == 2 + (extra >= BL)
