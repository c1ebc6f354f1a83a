"""The short-convolution / grouped-query attention / expert decoder against
the benchmark's plain reference (``benchmark/references/lfm2_moe.py``, which
imports nothing of the program) at a small size: every width shrunk, the
structure whole (two dense convolution blocks, then attention and
convolution layers with experts, 4 query heads on 2 key heads, a
convolution of 3 taps, top-2 of 8 experts chosen by a biased score),
through ``walk`` in uneven windows and through ``LLMEngine`` with prefix
reuse by the tail's snapshot."""

import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import lfm2_moe as ref  # noqa: E402
from mmlspark_tpu.dl import ShortConvMoEDecoder  # noqa: E402
from mmlspark_tpu.dl.paged_kv import (copy_state_rows,  # noqa: E402
                                      init_pools, state_row_bytes)
from mmlspark_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from mmlspark_tpu.serving.llm import LLMEngine  # noqa: E402

BL = 16
CFG = {
    "hidden_size": 64, "vocab_size": 256, "num_hidden_layers": 6,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
    "conv_bias": False, "intermediate_size": 96, "num_dense_layers": 2,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True, "norm_eps": 1e-5, "rope_theta": 1000000,
    "param_dtype": "float32", "cache_dtype": "float32"}
N_CONV = CFG["layer_types"].count("conv")


@pytest.fixture(scope="module")
def model():
    return CFG, ref.make_weights(CFG, 11), ShortConvMoEDecoder(
        CFG, dtype=jnp.float32, max_window=24)


def _walk_all(module, weights, tokens, windows):
    """Feed ``tokens`` through ``module.walk`` in the given windows through
    fresh pools; the logits of every row, and the counts."""
    n = len(tokens)
    blocks = -(-n // BL)
    pools = init_pools(module.cache_spec(), blocks + 2, BL, 2)
    rows = jnp.asarray(np.arange(1, blocks + 1, dtype=np.int32)[None])
    out, pos = [], 0
    counts = np.zeros(len(module.walk_stats), np.int64)
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


def test_prefill_in_uneven_windows_then_decode_matches_the_reference(model):
    """Windows of 24, 7, 1, 24 and 8 rows (the tail carried from one to
    the next, a window of one row shorter than the tail's reach among
    them), then decode steps of width 1: the logits of every fed row
    against the reference's full forward pass. float32 on both sides: XLA's
    default float32 products on the CPU against the reference's
    ``highest``."""
    cfg, weights, module = model
    n = 80
    tokens = np.random.default_rng(3).integers(1, 256, n)
    got, counts = _walk_all(module, weights, tokens,
                            [24, 7, 1, 24, 8] + [1] * 16)
    want = np.asarray(ref.forward(weights, cfg, tokens, np.arange(n)))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    stats = dict(zip(module.walk_stats, counts))
    assert stats["conv_step_rows"] == 17 * N_CONV   # the 1-row window too
    assert stats["conv_window_rows"] == 63 * N_CONV
    assert stats["conv_tails_carried"] == 3 * N_CONV  # all but the first
    moe_layers = CFG["num_hidden_layers"] - CFG["num_dense_layers"]
    assert stats["moe_pairs_held"] == n * 2 * moe_layers and \
        stats["moe_pairs_absent"] == 0
    # the bias moved some pairs, not most
    assert 0 < stats["moe_pairs_biased"] < stats["moe_pairs_held"] // 2


@pytest.mark.parametrize("cuts", [[40], [1] * 40, [3, 1, 2, 30, 4]],
                         ids=["one-window", "a-row-at-a-time", "ragged"])
def test_a_prompt_cut_into_any_windows_gives_the_same_rows(model, cuts):
    """The tail carries the convolution across windows: any cut of 40
    rows gives the rows of one window of 40."""
    cfg, weights, _ = model
    wide = ShortConvMoEDecoder(cfg, dtype=jnp.float32, max_window=64)
    tokens = np.random.default_rng(5).integers(1, 256, 40)
    a, _ = _walk_all(wide, weights, tokens, [40])
    b, _ = _walk_all(wide, weights, tokens, cuts)
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_walk_gives_the_same_rows_through_the_grouped_kernels(
        model, grouped_kernels):
    """The expert products through the Pallas kernels give the rows of the
    ``ragged_dot`` passes (float32: the order of the sums), in windows and
    in steps; every count but the tiles' rows is the same."""
    cfg, weights, module = model
    tokens = np.random.default_rng(9).integers(1, 256, 33)
    windows = [24, 7, 1, 1]
    via_kernels, counts = _walk_all(module, weights, tokens, windows)
    grouped_kernels.undo()
    via_lax, lax_counts = _walk_all(module, weights, tokens, windows)
    np.testing.assert_allclose(via_kernels, via_lax, rtol=1e-4, atol=1e-4)
    a, b = (dict(zip(module.walk_stats, c)) for c in (counts, lax_counts))
    assert {k: v for k, v in a.items() if k != "moe_rows_computed"} == \
        {k: v for k, v in b.items() if k != "moe_rows_computed"}
    assert b["moe_rows_computed"] == b["moe_pairs_held"]
    assert a["moe_rows_computed"] > a["moe_pairs_held"]


def test_the_tail_rounds_to_the_serving_type():
    """In bfloat16 the program's windows read the tail as the reference
    rounds it: a row at a time and one window give the same rows."""
    cfg = {**CFG, "param_dtype": "bfloat16", "cache_dtype": "bfloat16"}
    weights = ref.make_weights(cfg, 13)
    module = ShortConvMoEDecoder(cfg, dtype=jnp.bfloat16, max_window=32)
    tokens = np.random.default_rng(8).integers(1, 256, 24)
    a, _ = _walk_all(module, weights, tokens, [24])
    b, _ = _walk_all(module, weights, tokens, [5, 1, 18])
    np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)
    assert np.array_equal(a.argmax(-1), b.argmax(-1))


def test_the_cache_spec_states_one_tail_a_sequence(model):
    _, _, module = model
    spec = module.cache_spec()
    kinds = [[e[2] if len(e) > 2 else "token" for e in layer]
             for layer in spec]
    assert kinds == [["seq"] if t == "conv" else ["token", "token"]
                     for t in CFG["layer_types"]]
    (tail,) = spec[0]
    assert tail[0] == (2, 64) and tail[1] == jnp.float32
    assert spec[2][0][0] == (2 * 16,)
    assert state_row_bytes(spec) == N_CONV * 2 * 64 * 4
    pools = init_pools(spec, 4, BL, 3)
    assert pools[0][0].shape == (4, 2, 64)
    assert pools[2][0].shape == (4, BL, 32)
    # a snapshot copies the tails and leaves the chained pools alone
    conv = [i for i, t in enumerate(CFG["layer_types"]) if t == "conv"]
    marked = tuple(tuple(p.at[2].set(7) if i in conv else p for p in layer)
                   for i, layer in enumerate(pools))
    out = copy_state_rows(spec, marked, jnp.asarray([2]), jnp.asarray([3]))
    for i in conv:
        assert float(out[i][0][3].min()) == 7.0
    assert out[2][0] is marked[2][0]


# ------------------------------------------------------- through the engine
def _engine(model, reg, **kw):
    cfg, weights, module = model
    kw = {"slots": 2, "block_len": BL, "max_seq_len": 128, "num_blocks": 24,
          "state_slots": 4, "prefill_batch": 2, "hbm_fraction": 1.0, **kw}
    return LLMEngine(module, {"params": weights}, service="lfm2",
                     registry=reg, **kw)


def _counter(reg, name):
    return next(m for m in reg.metrics(name) if m.name == name).value(
        service="lfm2")


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
    samples = [(p, done[i][len(p):], 0, 0) for i, p in enumerate(prompts)]
    got = dict((name, value) for name, value, _ in ref.compare(
        weights, cfg, samples, {n: 0.0 for n in ref.NUMBERS}))
    assert got["argmax_margin_mean"] <= 1e-4, got
    assert got["argmax_flipped_share"] == 0.0, got
    for name in ("conv_window_rows_total", "conv_step_rows_total",
                 "moe_pairs_held_total", "moe_pairs_biased_total"):
        assert _counter(reg, name) > 0, name
    assert _counter(reg, "kv_state_slots_used") == 0   # all given back
    assert _counter(reg, "kv_state_snapshots") == 2
    assert _counter(reg, "kv_state_bytes") == 2 * state_row_bytes(
        eng.module.cache_spec())


#: case -> (tokens the turn adds to the 64-token system prompt, new tokens)
RIDE_CASES = {
    "restore_then_one_window": (7, 6),
    # two new whole blocks make a snapshot at 96: the cut between windows
    "restore_then_a_cut_between_two_boundaries": (40, 6),
    "one_new_token": (21, 1),
}


@pytest.mark.parametrize("case", sorted(RIDE_CASES))
@pytest.mark.parametrize("order", ["ride", "alone", "ahead"])
def test_a_prefix_hit_restores_the_tails(model, monkeypatch, case, order):
    """A turn on an indexed system prompt arrives while another request
    decodes: the snapshot of every convolution layer's tail (a layer's
    WHOLE per-sequence cache) is restored, its rows ride in the decode
    step's program, go alone, or ride while the engine runs one boundary
    ahead, and the tokens are those of a cold engine that never saw the
    system prompt."""
    from mmlspark_tpu.serving import llm
    extra, new = RIDE_CASES[case]
    rng = np.random.default_rng(21)
    doc = rng.integers(1, 256, 64).astype(np.int32)    # four whole blocks
    other = rng.integers(1, 256, 37).astype(np.int32)
    ask = np.concatenate([doc, rng.integers(1, 256, extra).astype(np.int32)])
    cold = _engine(model, MetricsRegistry())
    cold.submit("q", ask, new)
    want = cold.run_until_drained()["q"]
    if order == "ahead":
        monkeypatch.setattr(llm, "_device_beside_host", lambda: True)
    reg = MetricsRegistry()
    eng = _engine(model, reg, slots=3, state_slots=6, prefill_batch=1)
    eng.prefiller.ride_from = 1
    if order == "alone":
        eng.prefiller.rider = None
    eng.submit("doc", doc, 1)
    eng.run_until_drained()
    assert _counter(reg, "kv_state_snapshots") == 1
    eng.submit("other", other, 12)
    out = dict(eng.step())
    eng.submit("q", ask, new)
    out.update(eng.run_until_drained())
    assert eng.decoder.flying is None
    np.testing.assert_array_equal(out["q"], want)
    assert _counter(reg, "kv_state_restores_total") == 1
    assert _counter(reg, "kv_prefix_tokens_reused_total") == 64
    assert _counter(reg, "kv_state_slots_used") == 0
    rode = next(m for m in reg.metrics("gen_prefill_rows_total")
                if m.name == "gen_prefill_rows_total")
    assert (rode.value(service="lfm2", ride="decode") > 0) == \
        (order != "alone")


def test_without_the_restore_the_logits_differ(model):
    """The control of the test above: the turn served from a zero tail at
    the system prompt's end is not the whole prompt's, so the tail's
    snapshot is what carried the prefix."""
    cfg, weights, _ = model
    rng = np.random.default_rng(21)
    doc = rng.integers(1, 256, 64).astype(np.int32)
    ask = np.concatenate([doc, rng.integers(1, 256, 7).astype(np.int32)])
    rows = np.arange(len(ask) - 1, len(ask))
    whole = np.asarray(ref.forward(weights, cfg, ask, rows))
    for fault in ("no_restore", "window_tail_zero"):
        cut = np.asarray(ref.forward(weights, cfg, ask, rows, fault=fault,
                                     doc_len=64, window_start=64))
        assert np.abs(cut - whole).max() > 1e-3, fault


# --------------------------------- the convolution helper chat shares with it
def _conv_in_line(m, tail, srows, pos, lens, taps_w, activation=None):
    """The convolution over a tail written out step by step: the window
    behind its last inputs, the taps summed, the tail's rows set."""
    import jax
    taps, w = taps_w.shape[1], m.shape[1]
    before = jnp.where((pos > 0)[:, None, None], tail[srows],
                       jnp.zeros((), tail.dtype))
    ext = jnp.concatenate([before, m], axis=1)
    conv = sum(ext[:, j:j + w].astype(jnp.float32) * taps_w[:, j]
               for j in range(taps))
    conv = activation(conv) if activation is not None else conv
    keep = lens[:, None] + jnp.arange(taps - 1)[None]
    tail = tail.at[srows].set(jax.numpy.take_along_axis(
        ext, keep[:, :, None], axis=1))
    return conv, tail


@pytest.mark.parametrize("windows", ["decode", "ride"])
def test_the_gated_delta_decoder_traces_the_same_program_with_the_helper(
        monkeypatch, windows):
    """Qwen3-Next's decoder (the chat cell's) calls the convolution helper
    this decoder calls: its walk, a decode row alone or with a window
    riding, traces the very program it traces with the convolution
    written in line."""
    import jax

    import mmlspark_tpu.dl.gated_delta_moe_decoder as gdm
    from benchmark import run
    from benchmark.references import qwen3_next
    _, _, cfg, _ = run.load_cell("qwen3-next-80b-a3b.chat",
                                 run.load_bench(), tiny=True)
    weights = qwen3_next.make_weights(cfg, 1)
    module = gdm.GatedDeltaMoEDecoder(cfg, dtype=jnp.float32, max_window=32)
    pools = init_pools(module.cache_spec(), 8, 16, 4)
    rows = jnp.asarray(np.arange(1, 5, dtype=np.int32)[None].repeat(2, 0))
    dec = (jnp.ones((2, 1), jnp.int32), rows, jnp.asarray([5, 9], jnp.int32),
           jnp.ones((2, 1), bool), jnp.asarray([1, 2], jnp.int32))
    win = (jnp.ones((1, 16), jnp.int32), rows[:1], jnp.asarray([3], jnp.int32),
           jnp.ones((1, 16), bool), jnp.asarray([3], jnp.int32))
    wins = (dec,) if windows == "decode" else (dec, win)

    def traced():
        return str(jax.make_jaxpr(
            lambda p, pl: module.walk(p, wins, pl))(weights, pools))

    got = traced()
    monkeypatch.setattr(gdm, "causal_conv_tail", _conv_in_line)
    assert traced() == got
