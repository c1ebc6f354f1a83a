"""The gated DeltaNet / gated attention / expert decoder against the
benchmark's plain reference (``benchmark/references/qwen3_next.py``, which
imports nothing of the program) at a small size: every width shrunk, the
structure whole (DeltaNet and attention layers 3 : 1, 8 query heads on 2 key
heads, 2 key heads serving 4 value heads, a convolution of 4 taps, top-2 of
8 experts with a gated shared expert), through ``walk`` in uneven windows
and through ``LLMEngine`` with prefix reuse by state snapshot."""

import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import qwen3_next as ref  # noqa: E402
from mmlspark_tpu.dl import GatedDeltaMoEDecoder  # noqa: E402
from mmlspark_tpu.dl.paged_kv import (copy_state_rows,  # noqa: E402
                                      init_pools, state_row_bytes)
from mmlspark_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from mmlspark_tpu.serving.llm import LLMEngine  # noqa: E402

BL = 16
CFG = {
    "hidden_size": 64, "vocab_size": 256, "num_hidden_layers": 4,
    "full_attention_interval": 4, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "param_dtype": "float32",
    "cache_dtype": "float32"}


@pytest.fixture(scope="module")
def model():
    return CFG, ref.make_weights(CFG, 11), GatedDeltaMoEDecoder(
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
    """Windows of 24, 7, 24 and 9 rows (the state and the convolution's
    tail carried from one to the next, a window shorter than the tail's
    reach among them), then decode steps of width 1: the logits of every
    fed row against the reference's full forward pass. float32 on both
    sides: 2e-3 is the chunked rule's 64-row inverse and XLA's default
    float32 products on the CPU against the reference's ``highest``; no
    discontinuity is near (top-2 of 8 with a random router leaves gaps of
    1e-2 and more between the second and third expert at these seeds)."""
    cfg, weights, module = model
    n = 80
    tokens = np.random.default_rng(3).integers(1, 256, n)
    got, counts = _walk_all(module, weights, tokens,
                            [24, 7, 24, 9] + [1] * 16)
    want = np.asarray(ref.forward(weights, cfg, tokens, np.arange(n)))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    stats = dict(zip(module.walk_stats, counts))
    assert stats["gdn_step_rows"] == 16 * 3           # three DeltaNet layers
    assert stats["gdn_chunk_rows"] == 64 * 3
    assert stats["gdn_windows_carried"] == 3 * 3      # all but the first
    assert stats["moe_pairs_held"] == n * 2 * 4 and \
        stats["moe_pairs_absent"] == 0


def test_a_window_is_a_row_at_a_time(model):
    """One window of 40 rows and forty steps of width 1 give the same
    logits: the chunked form against the step, the tail inside a window
    against the tail across calls."""
    cfg, weights, module = model
    wide = GatedDeltaMoEDecoder(cfg, dtype=jnp.float32, max_window=64)
    tokens = np.random.default_rng(5).integers(1, 256, 40)
    a, _ = _walk_all(wide, weights, tokens, [40])
    b, _ = _walk_all(module, weights, tokens, [1] * 40)
    np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


def test_absent_experts_terms_are_left_out_as_in_the_reference(model):
    """This chip's share: experts 2-5 of 8 held, the router still over 8."""
    cfg, weights, _ = model
    held = {**cfg, "experts_held": [2, 6]}
    cut = {**weights, "layers": [
        {**lw, **{k: lw[k][2:6] for k in ("exp_gate", "exp_up", "exp_down")}}
        for lw in weights["layers"]]}
    module = GatedDeltaMoEDecoder(held, dtype=jnp.float32, max_window=24)
    tokens = np.random.default_rng(6).integers(1, 256, 30)
    got, counts = _walk_all(module, cut, tokens, [24, 6])
    want = np.asarray(ref.forward(cut, held, tokens, np.arange(30)))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    stats = dict(zip(module.walk_stats, counts))
    assert stats["moe_pairs_absent"] > 0 and \
        stats["moe_pairs_held"] + stats["moe_pairs_absent"] == 30 * 2 * 4


def test_walk_gives_the_same_rows_through_the_grouped_kernels(
        model, grouped_kernels):
    """The expert products through the Pallas kernels give the rows of the
    ``ragged_dot`` passes (float32: the order of the sums) with experts
    2-5 of 8 held, in a window and in steps; every count but the tiles'
    rows is the same."""
    cfg, weights, _ = model
    held = {**cfg, "experts_held": [2, 6]}
    cut = {**weights, "layers": [
        {**lw, **{k: lw[k][2:6] for k in ("exp_gate", "exp_up", "exp_down")}}
        for lw in weights["layers"]]}
    module = GatedDeltaMoEDecoder(held, dtype=jnp.float32, max_window=24)
    tokens = np.random.default_rng(9).integers(1, 256, 26)
    windows = [24, 1, 1]
    via_kernels, counts = _walk_all(module, cut, tokens, windows)
    grouped_kernels.undo()
    via_lax, lax_counts = _walk_all(module, cut, tokens, windows)
    np.testing.assert_allclose(via_kernels, via_lax, rtol=1e-4, atol=1e-4)
    a, b = (dict(zip(module.walk_stats, c)) for c in (counts, lax_counts))
    assert {k: v for k, v in a.items() if k != "moe_rows_computed"} == \
        {k: v for k, v in b.items() if k != "moe_rows_computed"}
    assert b["moe_rows_computed"] == b["moe_pairs_held"]
    assert a["moe_rows_computed"] > a["moe_pairs_held"] > 0


def test_the_cache_spec_states_two_arrays_a_sequence(model):
    _, _, module = model
    spec = module.cache_spec()
    kinds = [[e[2] if len(e) > 2 else "token" for e in layer]
             for layer in spec]
    assert kinds == [["seq", "seq"]] * 3 + [["token", "token"]]
    state, tail = spec[0]
    assert state[0] == (4, 16, 16) and state[1] == jnp.float32
    assert tail[0] == (3, 2 * 2 * 16 + 4 * 16)
    # a row's bytes are both arrays', every DeltaNet layer
    assert state_row_bytes(spec) == 3 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    pools = init_pools(spec, 4, BL, 3)
    assert pools[0][0].shape == (4, 4, 16, 16)
    assert pools[0][1].shape == (4, 3, 128)
    assert pools[3][0].shape == (4, BL, 32)
    # a snapshot copies both arrays and leaves the chained pools alone
    marked = tuple(tuple(p + 1 if i < 3 else p for p in layer)
                   for i, layer in enumerate(pools))
    marked = tuple(tuple(p.at[2].set(7) if i < 3 else p for p in layer)
                   for i, layer in enumerate(marked))
    out = copy_state_rows(spec, marked, jnp.asarray([2]), jnp.asarray([3]))
    for i in range(3):
        for p in out[i]:
            assert float(p[3].min()) == 7.0 and float(p[1].max()) == 1.0
    assert out[3][0] is marked[3][0]


# ------------------------------------------------------- through the engine
def _engine(model, reg, **kw):
    cfg, weights, module = model
    kw = {"slots": 2, "block_len": BL, "max_seq_len": 128, "num_blocks": 24,
          "state_slots": 4, "prefill_batch": 2, "hbm_fraction": 1.0, **kw}
    return LLMEngine(module, {"params": weights}, service="q3n",
                     registry=reg, **kw)


def _counter(reg, name):
    return next(m for m in reg.metrics(name) if m.name == name).value(
        service="q3n")


def test_greedy_serving_is_the_references_argmax(model):
    """Two requests of different lengths in one prefill batch, chunked
    prefill, then decode: teacher-forced along what was served, every
    served token is the reference's first (mean margin under 1e-3)."""
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
    assert got["argmax_margin_mean"] <= 1e-3, got
    assert _counter(reg, "gdn_chunk_rows_total") > 0
    assert _counter(reg, "gdn_step_rows_total") > 0
    assert _counter(reg, "moe_pairs_held_total") > 0
    assert _counter(reg, "kv_state_slots_used") == 0   # all given back
    # what is left are the two prompts' snapshots, both arrays counted
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
def test_a_prefix_hit_restores_state_and_tail_riding_or_alone(model, case):
    """A turn on an indexed system prompt arrives while another request
    decodes: the snapshot of every DeltaNet layer's state AND tail is
    restored, its rows ride in the decode step's program or go alone, and
    either way the tokens are those of a cold engine that never saw the
    system prompt."""
    cfg, weights, _ = model
    extra, new = RIDE_CASES[case]
    rng = np.random.default_rng(21)
    doc = rng.integers(1, 256, 64).astype(np.int32)    # four whole blocks
    other = rng.integers(1, 256, 37).astype(np.int32)
    ask = np.concatenate([doc, rng.integers(1, 256, extra).astype(np.int32)])
    cold = _engine(model, MetricsRegistry())
    cold.submit("q", ask, new)
    want = cold.run_until_drained()["q"]
    for order in ("ride", "alone"):
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
        np.testing.assert_array_equal(out["q"], want)
        assert _counter(reg, "kv_state_restores_total") == 1
        assert _counter(reg, "kv_prefix_tokens_reused_total") == 64
        rode = next(m for m in reg.metrics("gen_prefill_rows_total")
                    if m.name == "gen_prefill_rows_total")
        assert (rode.value(service="q3n", ride="decode") > 0) == \
            (order == "ride")


@pytest.mark.parametrize("case", sorted(RIDE_CASES))
def test_one_boundary_ahead_restores_state_and_tail(model, monkeypatch,
                                                    case):
    """The same turn while the engine runs ONE BOUNDARY AHEAD (as it does
    where a device runs beside the host): the restore's copy, the cut's
    snapshot and the handoff by the first token's row fall between
    programs whose tokens are not fetched yet, and the tokens are still
    the cold engine's."""
    from mmlspark_tpu.serving import llm
    extra, new = RIDE_CASES[case]
    rng = np.random.default_rng(21)
    doc = rng.integers(1, 256, 64).astype(np.int32)
    other = rng.integers(1, 256, 37).astype(np.int32)
    ask = np.concatenate([doc, rng.integers(1, 256, extra).astype(np.int32)])
    cold = _engine(model, MetricsRegistry())
    cold.submit("q", ask, new)
    want = cold.run_until_drained()["q"]
    cold.submit("other", other, 12)
    want_other = cold.run_until_drained()["other"]
    monkeypatch.setattr(llm, "_device_beside_host", lambda: True)
    reg = MetricsRegistry()
    eng = _engine(model, reg, slots=3, state_slots=6, prefill_batch=1)
    eng.prefiller.ride_from = 1
    eng.submit("doc", doc, 1)
    eng.run_until_drained()
    eng.submit("other", other, 12)
    out = dict(eng.step())
    eng.submit("q", ask, new)
    out.update(eng.run_until_drained())
    assert eng.decoder.flying is None
    np.testing.assert_array_equal(out["q"], want)
    np.testing.assert_array_equal(out["other"], want_other)
    assert _counter(reg, "kv_state_restores_total") == 1
    assert _counter(reg, "gen_steps_ahead_total") >= 3
    assert _counter(reg, "kv_state_slots_used") == 0


def test_without_the_restore_the_tokens_differ(model):
    """The control of the test above: the same turn served from zeros at
    the system prompt's end is not what the cold engine serves, so the
    snapshot (state and tail) is what carried the prefix."""
    cfg, weights, _ = model
    rng = np.random.default_rng(21)
    doc = rng.integers(1, 256, 64).astype(np.int32)
    ask = np.concatenate([doc, rng.integers(1, 256, 7).astype(np.int32)])
    rows = np.arange(len(ask) - 1, len(ask))
    whole = np.asarray(ref.forward(weights, cfg, ask, rows))
    for fault in ("no_restore", "window_state_zero", "window_tail_zero"):
        cut = np.asarray(ref.forward(weights, cfg, ask, rows, fault=fault,
                                     doc_len=64, window_start=64))
        assert np.abs(cut - whole).max() > 1e-3, fault
