"""The Qwen3-Next reference (``benchmark/references/qwen3_next.py``) tied to
the family's own code, and the chip's share tied to the whole layer."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.references import qwen3_next as ref

TINY = {
    "hidden_size": 64, "vocab_size": 128, "num_hidden_layers": 4,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "param_dtype": "float32",
    "cache_dtype": "float32"}


def _family_model(cfg, weights):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    hf_cfg = transformers.Qwen3NextConfig(
        **{k: cfg[k] for k in (
            "hidden_size", "vocab_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim", "num_experts",
            "num_experts_per_tok", "moe_intermediate_size",
            "shared_expert_intermediate_size", "norm_topk_prob",
            "rms_norm_eps")},
        intermediate_size=64, max_position_embeddings=512,
        tie_word_embeddings=False, attn_implementation="eager")
    assert list(hf_cfg.layer_types) == list(ref.layer_types(cfg))
    model = transformers.Qwen3NextForCausalLM(hf_cfg).to(torch.float32).eval()

    def t(a, transpose=True):
        a = np.asarray(a, np.float32)
        return torch.from_numpy(np.ascontiguousarray(a.T if transpose else a))

    sd = {"model.embed_tokens.weight": t(weights["embed"], False),
          "model.norm.weight": t(weights["final_norm"], False),
          "lm_head.weight": t(weights["head"])}
    for i, lw in enumerate(weights["layers"]):
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = t(lw["attn_norm"], False)
        sd[p + "post_attention_layernorm.weight"] = t(lw["ffn_norm"], False)
        sd[p + "mlp.gate.weight"] = t(lw["router"])
        for e in range(cfg["num_experts"]):
            q = p + f"mlp.experts.{e}."
            sd[q + "gate_proj.weight"] = t(lw["exp_gate"][e])
            sd[q + "up_proj.weight"] = t(lw["exp_up"][e])
            sd[q + "down_proj.weight"] = t(lw["exp_down"][e])
        sd[p + "mlp.shared_expert.gate_proj.weight"] = t(lw["shared_gate"])
        sd[p + "mlp.shared_expert.up_proj.weight"] = t(lw["shared_up"])
        sd[p + "mlp.shared_expert.down_proj.weight"] = t(lw["shared_down"])
        sd[p + "mlp.shared_expert_gate.weight"] = t(lw["shared_router"])
        if "qkvz" in lw:
            qkvz, ba = ref.to_family_order(cfg, lw["qkvz"], lw["ba"])
            a = p + "linear_attn."
            sd[a + "in_proj_qkvz.weight"] = t(qkvz)
            sd[a + "in_proj_ba.weight"] = t(ba)
            sd[a + "conv1d.weight"] = t(
                np.asarray(lw["conv"])[:, None, :], False)
            sd[a + "dt_bias"] = t(lw["dt_bias"], False)
            sd[a + "A_log"] = t(lw["A_log"], False)
            sd[a + "norm.weight"] = t(lw["o_norm"], False)
            sd[a + "out_proj.weight"] = t(lw["o"])
        else:
            a = p + "self_attn."
            for name in "qkvo":
                sd[a + f"{name}_proj.weight"] = t(lw[name])
            sd[a + "q_norm.weight"] = t(lw["q_norm"], False)
            sd[a + "k_norm.weight"] = t(lw["k_norm"], False)
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and not missing, (missing, unexpected)
    return model, torch


def test_reference_agrees_with_the_family_code():
    """All experts held, whole vocabulary, float32 on both sides: logits
    within 1e-4 (the two are the same arithmetic in another order; the
    family's chunked rule of 64 against the recurrence here). 70 tokens:
    more than one chunk of the family's rule."""
    weights = ref.make_weights(TINY, 7)
    model, torch = _family_model(TINY, weights)
    tokens = np.random.default_rng(0).integers(1, 128, size=70)
    with torch.no_grad():
        want = model(torch.from_numpy(tokens[None])).logits[0].numpy()
    got = np.asarray(ref.forward(weights, TINY, tokens,
                                 np.arange(len(tokens))))
    assert np.abs(got - want).max() < 1e-4, np.abs(got - want).max()
    # and the logits are not flat: the comparison compares something
    assert np.abs(want).max() > 0.5


def test_the_four_shares_add_up_to_the_whole_layer():
    """Each chip's partial sum over its own experts, the shared expert
    counted once, add up to what the uncut layer gives."""
    weights = ref.make_weights(TINY, 11)
    d = ref.dims(TINY)
    lw = weights["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(3), (24, TINY["hidden_size"]))
    whole = ref.moe_layer(h, lw, d) - h
    total = jnp.zeros_like(h)
    n = TINY["num_experts"] // 4
    for chip in range(4):
        held = (chip * n, (chip + 1) * n)
        part = {**lw, **{k: lw[k][held[0]:held[1]]
                         for k in ("exp_gate", "exp_up", "exp_down")}}
        total += ref.moe_layer(h, part, d, held=held,
                               with_shared=chip == 0) - h
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5)
    # a share is not the whole: leaving three chips out shows
    assert float(jnp.abs(total - whole).max()) < 1e-5 < float(
        jnp.abs(ref.moe_layer(h, part, d, held=held) - h - whole).max())
