"""The KDA / latent-attention / expert decoder against the benchmark's plain
reference (``benchmark/references/kimi_linear.py``, which imports nothing
of the program) at a small size: every width shrunk, the structure whole
(a dense KDA layer, then KDA, KDA, MLA, KDA with experts; 2 KDA heads of 16
with a decay a key channel, 4 latent heads without query low rank and
without positions, top-2 of 8 experts by sigmoid score plus a choosing
bias, renormalised and scaled, and a shared expert), through ``walk`` in
uneven windows and through ``LLMEngine``."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import kimi_linear as ref  # noqa: E402
from mmlspark_tpu.dl import KimiLinearDecoder  # noqa: E402
from mmlspark_tpu.dl.latent_attention import LatentAttention  # noqa: E402
from mmlspark_tpu.dl.paged_kv import init_pools, state_row_bytes  # noqa
from mmlspark_tpu.models.moe import route_top_k  # noqa: E402
from mmlspark_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from mmlspark_tpu.serving.llm import LLMEngine  # noqa: E402

BL = 16
CFG = {
    "hidden_size": 64, "vocab_size": 256, "num_hidden_layers": 5,
    "linear_attn_config": {"full_attn_layers": [4], "kda_layers": [1, 2, 3, 5],
                           "head_dim": 16, "num_heads": 2,
                           "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "mla_use_nope": True, "rope_scaling": None, "rope_theta": 10000,
    "intermediate_size": 96, "first_k_dense_replace": 1, "num_experts": 8,
    "num_experts_per_token": 2, "moe_intermediate_size": 32,
    "num_shared_experts": 1, "routed_scaling_factor": 2.446,
    "moe_renormalize": True, "rms_norm_eps": 1e-5,
    "param_dtype": "float32", "cache_dtype": "float32"}
N_KDA, N_MOE = 4, 4


@pytest.fixture(scope="module")
def model():
    return CFG, ref.make_weights(CFG, 11), KimiLinearDecoder(
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
    """Windows of 24, 7, 1, 24 and 8 rows (state and tail carried from one
    to the next), then decode steps of width 1: the logits of every fed
    row against the reference's full forward pass (KDA token by token,
    latent attention expanded). float32 on both sides: XLA's default
    float32 products on the CPU against the reference's ``highest``."""
    cfg, weights, module = model
    n = 80
    tokens = np.random.default_rng(3).integers(1, 256, n)
    got, counts = _walk_all(module, weights, tokens,
                            [24, 7, 1, 24, 8] + [1] * 16)
    want = np.asarray(ref.forward(weights, cfg, tokens, np.arange(n)))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    stats = dict(zip(module.walk_stats, counts))
    assert stats["kda_step_rows"] == 17 * N_KDA     # the 1-row window too
    assert stats["kda_chunk_rows"] == 63 * N_KDA
    assert stats["kda_windows"] == 4 * N_KDA
    assert stats["kda_windows_carried"] == 3 * N_KDA  # all but the first
    # the decoding rows at positions 63..79 read 64..80 cached positions
    assert stats["latent_decode_positions"] == sum(range(64, 81)) - 32
    assert stats["moe_pairs_held"] == n * 2 * N_MOE
    # the decay a channel: the reference with it averaged is far off
    avg = np.asarray(ref.forward(weights, cfg, tokens, np.arange(n),
                                 fault="decay_averaged"))
    assert np.max(np.abs(avg - want)) > 100 * 1e-3


def test_latent_attention_without_query_rank_or_positions_is_expanded_mla():
    """The shared mixer (absorbed, through the paged kernel's lax twin) on
    a 10-row window against the expanded form written out here: ``q = u
    W_q``, ``[k_nope | v] = RMS(c) W_kvb``, scores over ``[k_nope | k_pe]``
    with ``k_pe`` as projected, no rotary."""
    mla = LatentAttention(CFG, jnp.float32)
    assert mla.rotary(jnp.arange(4)) is None
    lw = ref.make_weights(CFG, 5)["layers"][3]
    n, H, N, R, C = 10, 4, 16, 8, 32
    u = jax.random.normal(jax.random.PRNGKey(0), (n, 64))
    pool = jnp.zeros((3, BL, mla.cache_width), jnp.float32)
    rows = jnp.asarray([[1, 2]])
    at = jnp.arange(n)[None]
    got, _ = mla(lw, u, pool, ((rows, jnp.asarray([0]),
                                jnp.ones((1, n), bool), at),), [(1, n)], None)
    hi = jax.lax.Precision.HIGHEST
    q = jnp.matmul(u, lw["q"], precision=hi).reshape(n, H, N + R)
    kv = jnp.matmul(u, lw["kv_a"], precision=hi)
    c = kv[:, :C] * jax.lax.rsqrt(jnp.mean(kv[:, :C] ** 2, -1, keepdims=True)
                                  + 1e-5) * lw["kv_a_norm"]
    kvb = jnp.matmul(c, lw["kv_b"], precision=hi).reshape(n, H, N + 16)
    k = jnp.concatenate([kvb[..., :N], jnp.broadcast_to(
        kv[:, None, C:], (n, H, R))], -1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=hi) * (N + R) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    out = jnp.einsum("hts,shv->thv", jax.nn.softmax(s, -1), kvb[..., N:],
                     precision=hi).reshape(n, H * 16)
    want = jnp.matmul(out, lw["o"], precision=hi)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_the_router_is_deepseek_v3s_gate_with_one_group():
    """Sigmoid scores, top-k by score plus the correction bias, weights
    renormalised and scaled: the program's route (``route_top_k`` and the
    decoder's renormalisation), the reference's and ``transformers``'
    ``DeepseekV3TopkRouter`` with ``n_group`` 1 choose the same experts
    with the same weights."""
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV3Config
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import \
        DeepseekV3TopkRouter
    E, K, D, T = 16, 4, 32, 24
    hf_cfg = DeepseekV3Config(
        hidden_size=D, n_routed_experts=E, num_experts_per_tok=K, n_group=1,
        topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.446)
    rng = np.random.default_rng(4)
    w = rng.standard_normal((E, D)).astype(np.float32) * 0.5
    bias = rng.standard_normal(E).astype(np.float32) * 0.1
    x = rng.standard_normal((T, D)).astype(np.float32)
    gate = DeepseekV3TopkRouter(hf_cfg)
    with torch.no_grad():
        gate.weight.copy_(torch.from_numpy(w))
        gate.e_score_correction_bias.copy_(torch.from_numpy(bias))
        idx, wt = gate(torch.from_numpy(x))
    want = np.zeros((T, E), np.float32)
    np.put_along_axis(want, idx.numpy(), wt.numpy(), axis=1)
    scores = jax.nn.sigmoid(jnp.matmul(x, w.T,
                                       precision=jax.lax.Precision.HIGHEST))
    experts, weights = route_top_k(scores, top_k=K, bias=jnp.asarray(bias))
    weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    got = np.zeros((T, E), np.float32)
    np.put_along_axis(got, np.asarray(experts),
                      np.asarray(weights * 2.446), axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.routing_weights(
        scores, jnp.asarray(bias), top_k=K, renorm=True, scale=2.446)),
        want, rtol=1e-5, atol=1e-6)


def test_four_holders_partial_sums_are_the_uncut_layer(model):
    """The expert share: the partial sums of four holders of two experts
    each, plus the shared expert counted once, are the uncut layer."""
    cfg, weights, _ = model
    d = ref.dims(cfg)
    lw = weights["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(1), (12, 64))
    whole = ref.moe_layer(h, lw, d, held=(0, 8))
    parts = sum(ref.moe_layer(
        h, {**lw, **{k: lw[k][lo:lo + 2] for k in
                     ("exp_gate", "exp_up", "exp_down")}},
        d, held=(lo, lo + 2), with_shared=False) - h for lo in (0, 2, 4, 6))
    shared = ref.moe_layer(h, lw, d, held=(0, 0)) - h
    np.testing.assert_allclose(np.asarray(h + parts + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(parts))) > 0.1


def test_the_cache_spec_mixes_latent_tokens_with_state_rows(model):
    _, _, module = model
    spec = module.cache_spec()
    kinds = [[e[2] if len(e) > 2 else "token" for e in layer]
             for layer in spec]
    assert kinds == [["seq", "seq"]] * 3 + [["token"]] + [["seq", "seq"]]
    state, tail = spec[0]
    assert state[0] == (2, 16, 16) and state[1] == jnp.float32
    assert tail[0] == (3, 3 * 2 * 16)
    assert spec[3][0][0] == (128,)
    assert state_row_bytes(spec) == N_KDA * (2 * 16 * 16 + 3 * 96) * 4


def test_greedy_serving_is_the_references_argmax(model):
    """Two requests of different lengths in one prefill batch, chunked
    prefill, then decode through ``LLMEngine``: teacher-forced along what
    was served, every served token is the reference's first."""
    cfg, weights, module = model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 256, n).astype(np.int32) for n in (75, 41)]
    reg = MetricsRegistry()
    eng = LLMEngine(module, {"params": weights}, slots=2, block_len=BL,
                    max_seq_len=128, num_blocks=24, state_slots=4,
                    prefill_batch=2, hbm_fraction=1.0, service="kimi",
                    registry=reg)
    for i, p in enumerate(prompts):
        eng.submit(i, p, 8)
    done = eng.run_until_drained()
    samples = [(p, done[i][len(p):], 0) for i, p in enumerate(prompts)]
    got = dict((name, value) for name, value, _ in ref.compare(
        weights, cfg, samples, {n: 0.0 for n in ref.NUMBERS}))
    assert got["argmax_margin_mean"] <= 1e-4, got
    assert got["argmax_flipped_share"] == 0.0, got

    def counter(name):
        return next(m for m in reg.metrics(name)
                    if m.name == name).value(service="kimi")

    for name in ("kda_chunk_rows_total", "kda_step_rows_total",
                 "kda_windows_total", "latent_decode_positions_total",
                 "moe_pairs_held_total"):
        assert counter(name) > 0, name
    assert counter("kv_state_slots_used") == 0      # all given back
