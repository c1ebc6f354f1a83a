"""CPU rehearsal of ``chip_smoke.py``: the same phase functions the chip
runs, at tiny sizes, with the Pallas kernels in interpret mode — wrong
paths, arguments and control flow are found here at no chip time. Plus
the script's contract: no result without a TPU, and a last line with
exactly the three ``device`` keys."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def _run(fn, *args, **kw):
    rec: dict = {}
    out = fn(rec, *args, **kw)
    json.dumps(rec, default=str)    # every phase line must serialize
    return rec, out


@pytest.fixture(scope="module")
def tiny_gbdt():
    return _run(chip_smoke.phase_gbdt, n_rows=4000, n_test=1000, iters=5,
                leaves=7, min_auc=0.7)


def test_phase_featurizer_tiny():
    rec, _ = _run(chip_smoke.phase_featurizer, model="ResNet18",
                  n_images=8, size=32, minibatch=4, feature_dim=512,
                  ref_rows=4)
    assert rec["out_shape"] == [8, 512]
    assert rec["rel_l2_vs_float32"] < rec["tolerance"]


def test_phase_train_tiny():
    rec, _ = _run(chip_smoke.phase_train, model="ResNet18", batch=4,
                  size=32, steps=2, num_classes=10)
    assert len(rec["losses"]) == 2


def test_phase_gbdt_tiny(tiny_gbdt):
    rec, (model, rows) = tiny_gbdt
    assert rec["trees"] == 5 and rec["auc"] > 0.7
    assert rec["auc"] == pytest.approx(rec["auc_after_load"], abs=1e-6)
    # on the CPU the scatter path is the right one — and it is the
    # platform that says so, not a swallowed exception
    assert rec["use_pallas_hist"] == [False, False]
    assert rows.shape == (1000, 28)


def test_phase_encoder_tiny():
    rec, _ = _run(chip_smoke.phase_encoder, vocab=128, width=32, depth=1,
                  heads=2, mlp=64, seq=128, batch=2)
    assert rec["out_shape"] == [2, 32]
    assert rec["rel_l2_pallas_vs_dense"] < rec["tolerance"]


def test_phase_llm_tiny():
    import jax.numpy as jnp
    rec, _ = _run(chip_smoke.phase_llm, vocab=64, width=32, depth=1,
                  heads=2, mlp=64, slots=4, block_len=4, max_seq_len=64,
                  prefill_batch=2, n_prompts=4, shared=2, prefix_len=8,
                  prompt_lo=9, prompt_hi=24, new_tokens=6,
                  dtype=jnp.float32)
    assert rec["identical_to_generate"] == "4/4"
    assert rec["compiles_after_warm"] == 0
    assert rec["prefix_hits"] > 0
    # no allocator stats on the CPU: the engine's default sizing
    assert rec["num_blocks"] == 1 + 2 * 4 * 16


def test_phase_serving_tiny(tiny_gbdt):
    _, (model, rows) = tiny_gbdt
    rec, _ = _run(chip_smoke.phase_serving, model, rows, n_requests=10,
                  clients=2)
    assert rec["scored_on"] == ["cpu"]
    assert rec["fronts"]["python"]["answered_by"] == "ServingServer"
    if rec["gxx"]:
        assert rec["fronts"]["native"]["answered_by"] == \
            "NativeServingServer"


def test_phase_gbdt_sharded_tiny():
    """``--chips 4``, rehearsed on four of the eight virtual devices."""
    rec, _ = _run(chip_smoke.phase_gbdt_sharded, chips=4, n_rows=8192,
                  n_test=1000, iters=5, leaves=7)
    assert rec["placement"]["devices"] == 4
    assert rec["placement"]["shard_shape"] == [2048, 28]
    assert rec["placement_single"]["devices"] == 1


def test_phase_train_sharded_tiny():
    rec, _ = _run(chip_smoke.phase_train_sharded, chips=4, vocab=512,
                  width=64, depth=1, heads=4, mlp=128, seq=32, batch=8)
    assert rec["mesh"] == {"dp": 2, "tp": 2}
    assert rec["input_placement"][0] == 4


def test_explain_divergence_tells_logic_from_rounding():
    """The divergence diagnostic: a greedy sequence sits at gap 0 from
    the float32 reference; a wrong token lies far outside the model's
    own logit noise and is called ``logic``."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.dl import MaskedLMModel, TextEncoder
    from mmlspark_tpu.dl.generate import generate
    from mmlspark_tpu.dl.text_encoder import make_attention_fn

    module = MaskedLMModel(TextEncoder(
        vocab=64, width=32, depth=1, heads=2, mlp_dim=64,
        dtype=jnp.float32,
        attention_fn=make_attention_fn("dense", causal=True)))
    variables = {"params": module.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]}
    prompt = np.arange(2, 12, dtype=np.int32)
    seq = np.asarray(generate(module, variables, prompt[None],
                              max_new_tokens=5, temperature=0.0)[0])
    good = chip_smoke._greedy_margins(module, variables, {0: seq},
                                      {0: 10}, 32)
    assert good["margin"].max() < 1e-4
    bad = seq.copy()
    bad[12] = (bad[12] + 1) % 64 or 2
    out = chip_smoke.explain_divergence(
        module, variables, [prompt], {0: bad}, {0: seq}, {0: 12}, 32)
    assert out["divergence_verdict"] == "logic"
    first = out["first_divergences"][0]
    assert first["step"] == 2 and first["generate_gap_to_f32_top"] < 1e-4
    assert first["engine_gap_to_f32_top"] > first["own_dtype_logit_noise"]


def test_main_without_a_tpu_prints_no_result(capsys):
    """Here JAX finds the CPU only: non-zero, and no ``"ok": true``."""
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_final_line_has_exactly_the_contract_keys():
    import jax
    doc = json.loads(chip_smoke.final_line(jax.devices()))
    assert set(doc) == {"ok", "device"} and doc["ok"] is True
    assert set(doc["device"]) == {"platform", "kind", "count"}
    d = jax.devices()[0]
    assert doc["device"] == {"platform": d.platform,
                             "kind": d.device_kind,
                             "count": len(jax.devices())}
