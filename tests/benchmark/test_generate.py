"""The generate cell (ISSUE 28): its plain reference against the
published implementation, the traffic generator, the needed-work
arithmetic, the per-layer readers, and that ``correct`` comes out false
for the lower-precision control and with the timed path broken."""

import json
import math
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import (generate_stats, run, traffic_requests,  # noqa: E402
                       work_decoder)
from benchmark.references import xglm as ref  # noqa: E402
from test_harness import run_tiny  # noqa: E402

CELL = "xglm-1.7b.generate"
READERS = ["generate_mfu", "generate_mfu_hbm", "paged_attn_hbm_roofline",
           "paged_attn_kernel_share", "device_idle.generate",
           "generate.decode_step_ms", "generate.prefill_step_ms",
           "generate.prefill_time_share", "generate.batch_occupancy",
           "generate.pool_used_share"]


def _tiny():
    _, wl, cfg, params = run.load_cell(CELL, run.load_bench(), tiny=True)
    return run._load_module("drivers", wl["driver"]), cfg, params


# ---------------------------------------------------- the configuration
def test_config_lists_every_constant_it_changed():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xglm-1.7b.json")) as f:
        cfg = json.load(f)
    published = {k: v for k, v in cfg["published"].items()
                 if k != "why_changed"}
    assert sorted(published) == sorted(cfg["reduced"])
    assert all(cfg[k] != published[k] for k in published)
    # no width, depth, head count or vocabulary among them
    assert (cfg["vocab_size"], cfg["d_model"], cfg["ffn_dim"],
            cfg["num_layers"], cfg["attention_heads"]) == \
        (256008, 2048, 8192, 24, 16)


# -------------------------------------------------------- the reference
def _published(**over):
    _, cfg, _ = _tiny()
    return {**cfg, **cfg["published"], "param_dtype": "float32", **over}


def test_reference_is_the_published_model():
    """``references/xglm.py`` with the four published constants against
    ``transformers``' XGLMForCausalLM (torch, CPU, float32) on seeded
    random weights, positions past 64 included."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    cfg = _published()
    hf = transformers.XGLMForCausalLM(transformers.XGLMConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["d_model"],
        ffn_dim=cfg["ffn_dim"], num_layers=cfg["num_layers"],
        attention_heads=cfg["attention_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        activation_function="gelu", scale_embedding=True, dropout=0.0,
        attention_dropout=0.0, activation_dropout=0.0, layerdrop=0.0,
        pad_token_id=cfg["pad_token_id"])).eval()
    w = {k: np.asarray(v, np.float32)
         for k, v in ref.make_weights(cfg, 7).items()}

    def t(a):
        return torch.tensor(np.ascontiguousarray(a))

    state = {"model.embed_tokens.weight": t(w["embed"]),
             "lm_head.weight": t(w["embed"]),
             "model.layer_norm.weight": t(w["ln_f_scale"]),
             "model.layer_norm.bias": t(w["ln_f_bias"])}
    for i in range(cfg["num_layers"]):
        pre = f"model.layers.{i}."
        for ours, theirs in (("q", "self_attn.q_proj"),
                             ("k", "self_attn.k_proj"),
                             ("v", "self_attn.v_proj"),
                             ("o", "self_attn.out_proj"),
                             ("fc1", "fc1"), ("fc2", "fc2")):
            state[pre + theirs + ".weight"] = t(w[ours + "_w"][i].T)
            state[pre + theirs + ".bias"] = t(w[ours + "_b"][i])
        for ours, theirs in (("ln1", "self_attn_layer_norm"),
                             ("ln2", "final_layer_norm")):
            state[pre + theirs + ".weight"] = t(w[ours + "_scale"][i])
            state[pre + theirs + ".bias"] = t(w[ours + "_bias"][i])
    missing, unexpected = hf.load_state_dict(state, strict=False)
    assert not unexpected
    assert all("embed_positions" in k for k in missing), missing
    tokens = np.random.default_rng(3).integers(
        2, cfg["vocab_size"], size=100).astype(np.int32)
    with torch.no_grad():
        want = hf(torch.tensor(tokens[None].astype(np.int64))
                  ).logits[0].numpy()
    got = np.asarray(ref.forward(w, cfg, tokens, np.arange(100)))
    keep = np.arange(cfg["vocab_size"]) != cfg["pad_token_id"]
    assert np.all(np.isneginf(got[:, cfg["pad_token_id"]]))
    np.testing.assert_allclose(got[:, keep], want[:, keep], atol=1e-5,
                               rtol=0)
    assert np.abs(want).max() > 0.1          # not a comparison of noughts


def test_each_of_the_four_constants_changes_the_logits():
    cfg = _published()
    w = ref.make_weights(cfg, 7)
    tokens = np.arange(2, 82, dtype=np.int32)
    rows = np.arange(60, 80)
    base = np.asarray(ref.forward(w, cfg, tokens, rows))[:, 2:]
    _, as_run, _ = _tiny()
    for key in cfg["reduced"]:
        got = np.asarray(ref.forward(w, {**cfg, key: as_run[key]}, tokens,
                                     rows))[:, 2:]
        assert np.abs(got - base).max() > 1e-6, key


def test_padding_the_sequence_changes_nothing():
    _, cfg, _ = _tiny()
    w = ref.make_weights(cfg, 11)
    tokens = np.arange(5, 45, dtype=np.int32)
    rows = np.arange(30, 40)
    plain = np.asarray(ref.forward(w, cfg, tokens, rows))
    padded = np.asarray(ref.forward(w, cfg, tokens, rows, pad_to=96,
                                    pad_rows_to=16))
    np.testing.assert_allclose(padded, plain, atol=1e-6, rtol=0)


def test_weights_are_seeded_and_in_the_serving_type():
    _, cfg, _ = _tiny()
    big = 2**31 + 12345
    a, b = ref.make_weights(cfg, big), ref.make_weights(cfg, big)
    c = ref.make_weights(cfg, big + 1)
    assert all(str(v.dtype) == cfg["param_dtype"] for v in a.values())
    assert all(np.array_equal(np.asarray(a[k], np.float32),
                              np.asarray(b[k], np.float32)) for k in a)
    assert not np.array_equal(np.asarray(a["embed"], np.float32),
                              np.asarray(c["embed"], np.float32))


# ---------------------------------------------------------- the traffic
def _inputs(tiny=False):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           CELL + ".json")) as f:
        wl = json.load(f)
    return (wl["tiny"] if tiny else wl)["params"]["inputs"], wl


def test_traffic_same_seed_same_stream_and_lengths_inside_their_clips():
    spec, wl = _inputs()
    a = traffic_requests.RequestStream(spec, 2**31 + 5)
    b = traffic_requests.RequestStream(spec, 2**31 + 5)
    for k in (0, 1, 63, 64, 200):
        (pa, na), (pb, nb) = a.request(k), b.request(k)
        assert na == nb and np.array_equal(pa, pb)
        assert spec["prompt"]["min"] <= len(pa) <= spec["prompt"]["max"]
        assert spec["output"]["min"] <= na <= spec["output"]["max"]
        assert pa.min() >= spec["token_low"] and \
            pa.max() < spec["token_high"]
        assert len(pa) + na <= wl["params"]["engine"]["max_seq_len"]
    table = traffic_requests.size_table(spec)
    assert (table.sum(axis=1)
            <= wl["params"]["engine"]["max_seq_len"]).all()
    # the issue's means: about 590 in, about 140 out
    assert 560 < table[:, 0].mean() < 620 and 130 < table[:, 1].mean() < 150


def test_traffic_every_seed_gets_the_same_sizes_in_another_order():
    spec, _ = _inputs()
    n = spec["table"]
    a = traffic_requests.RequestStream(spec, 1)
    b = traffic_requests.RequestStream(spec, 2)
    for epoch in (0, 1):
        sa = [a.size(k) for k in range(epoch * n, (epoch + 1) * n)]
        sb = [b.size(k) for k in range(epoch * n, (epoch + 1) * n)]
        assert sa != sb and sorted(sa) == sorted(sb)
    # no two prompts share a prefix
    assert not np.array_equal(a.request(0)[0][:8], a.request(1)[0][:8])


def test_check_sample_keeps_first_last_and_the_longest_prompt():
    driver, _, params = _tiny()
    done = [{"request": i, "boundary": 10 + i, "prompt_len": 8 + (i * 7) % 40,
             "max_new": 4, "tokens": None, "in_window": i >= 5}
            for i in range(30)]
    ctx = {"finished": done, "prefilled_at": {}, "seed": 9, "params": params}
    picked = [f["request"] for f in driver._check_samples(ctx)]
    window = [f for f in done if f["in_window"]]
    longest = max(window, key=lambda f: f["prompt_len"])["request"]
    assert len(picked) == params["check_sequences"]
    assert {5, 29, longest} <= set(picked) and min(picked) >= 5
    assert picked == [f["request"] for f in driver._check_samples(ctx)]
    assert driver._check_samples({**ctx, "finished": done[:5]}) == []


# ------------------------------------------------------ the needed work
SMALL = {"d_model": 8, "ffn_dim": 32, "num_layers": 3, "vocab_size": 100,
         "param_dtype": "bfloat16", "cache_dtype": "bfloat16"}


def test_needed_work_by_hand():
    blocks = 3 * (4 * 8 * 8 + 2 * 8 * 32)                 # 2,304
    assert work_decoder.block_matmul_params(SMALL) == blocks
    assert work_decoder.head_flops(SMALL) == 2 * 8 * 100
    # positions 2, 3, 4 see 3, 4, 5 keys: 12 in all
    assert work_decoder.span_flops(SMALL, 2, 5, 2) == \
        2 * blocks * 3 + 4 * 8 * 3 * 12 + 2 * 1600
    assert work_decoder.span_flops(SMALL, 5, 5, 0) == 0
    assert work_decoder.kv_bytes_per_token(SMALL) == 2 * 3 * 8 * 2
    assert work_decoder.weight_bytes(SMALL) == (blocks + 800) * 2
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xglm-1.7b.json")) as f:
        cfg = json.load(f)
    # ISSUE 28's count: 1.208 B in the blocks, 3.47 GB, 196,608 B a token
    assert work_decoder.block_matmul_params(cfg) == 1_207_959_552
    assert work_decoder.weight_bytes(cfg) == 3_464_527_872
    assert work_decoder.kv_bytes_per_token(cfg) == 196_608


def _stat(boundary, prefilled, seconds=0.01, **over):
    return {"boundary": boundary, "seconds": seconds, "tokens": 1,
            "prefilled": prefilled, "blocks_used": 4.0,
            "decode_tokens_total": 10 * boundary,
            "decode_steps_total": 5 * boundary, **over}


def test_needed_work_of_a_hand_made_timeline():
    """One sequence: prompt 6, 4 new tokens, finished at boundary 12, so
    prefilled at 10 and decoding at 10, 11, 12 (positions 6, 7, 8)."""
    stats = [_stat(b, int(b == 10)) for b in range(9, 14)]
    ctx = {"cfg": SMALL, "stats": stats, "driver_ctx": {"finished": [
        {"boundary": 12, "prompt_len": 6, "max_new": 4}]}}
    need = generate_stats.needed(ctx, stats)
    assert need["flops"] == work_decoder.span_flops(SMALL, 0, 6, 1) \
        + work_decoder.span_flops(SMALL, 6, 9, 3)
    kv = work_decoder.kv_bytes_per_token(SMALL)
    assert need["kernel_bytes"] == kv * (6 + 7 + 8 + 9)
    assert need["bytes"] == need["kernel_bytes"] \
        + 4 * work_decoder.weight_bytes(SMALL)    # 3 decode calls, 1 prefill
    assert need["seconds"] == pytest.approx(0.05)
    # a window that opens after the prefill counts the decode steps alone
    late = generate_stats.needed(ctx, stats[3:])
    assert late["flops"] == work_decoder.span_flops(SMALL, 8, 9, 1)
    assert late["kernel_bytes"] == kv * 9
    assert generate_stats.needed(ctx, []) is None


def test_traced_boundaries_are_the_stretch_the_profiler_covered():
    stats = [_stat(b, 0, seconds=1.0) for b in range(10)]
    ctx = {"stats": stats, "trace": {"window_s": 4.0005}}
    got = generate_stats.traced_boundaries(ctx)
    assert [s["boundary"] for s in got] == [1, 2, 3, 4]
    assert generate_stats.traced_boundaries({"stats": stats,
                                             "trace": None}) == []


# ---------------------------------------------------------- the readers
@pytest.fixture(scope="module")
def reader_ctx():
    """A tiny run of the cell's own driver, handed to the readers the way
    ``run_cell`` hands it over after a traced run on the chip (the trace's
    numbers made up: a CPU has no device plane)."""
    driver, cfg, params = _tiny()
    ctx = driver.setup(cfg, params, 2_147_484_001)
    driver.warm(ctx)
    t0 = sum(1 for _ in range(30) if driver.step(ctx) >= 0)
    assert t0 == 30
    driver.after_window(ctx, True)
    stats = ctx["stats"]
    elapsed = sum(s["seconds"] for s in stats)
    traced = sum(s["seconds"] for s in stats[1:21]) + 1e-6
    return {"cfg": cfg, "params": params, "stats": stats, "driver_ctx": ctx,
            "on_chip": True, "elapsed_s": elapsed, "operations": len(stats),
            "rate": sum(s["tokens"] for s in stats) / elapsed,
            "peaks": {"flops_per_s_bf16": 1e9, "hbm_bytes_per_s": 1e9},
            "trace": {"window_s": traced, "busy_s": 0.6 * traced,
                      "idle_share": 0.4, "kernel_s": 0.3 * traced,
                      "kernel_calls": 40}}


@pytest.mark.parametrize("name", READERS)
def test_reader_reports_a_positive_number(name, reader_ctx):
    value = run._load_module("layer_metrics", name).read(reader_ctx)
    assert value is not None and value > 0
    if name == "generate.batch_occupancy":
        assert 50 < value <= 100
    if name in ("generate.pool_used_share", "generate.prefill_time_share",
                "device_idle.generate", "paged_attn_kernel_share"):
        assert value < 100


@pytest.mark.parametrize("name", READERS)
def test_reader_reports_nothing_where_there_is_nothing_to_read(name,
                                                               reader_ctx):
    reader = run._load_module("layer_metrics", name)
    empty = {**reader_ctx, "stats": [], "trace": None,
             "driver_ctx": {"finished": []}}
    assert reader.read(empty) is None
    if name not in ("generate.decode_step_ms", "generate.prefill_step_ms"):
        # no share of a peak, of the device's time or of the pool from a
        # run that was not on the chip
        assert reader.read({**reader_ctx, "on_chip": False,
                            "trace": None}) is None


def test_the_benchmark_lists_the_ten_readers_for_the_cell():
    bench = run.load_bench()
    listed = {m["name"]: m for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert sorted(listed) == sorted(READERS)
    for m in listed.values():
        assert m["moves"] == "tokens_per_s" and m["workloads"] == [CELL]
        assert m["source"] != "program_span"
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "tokens_per_s")
    assert rate["workloads"] == [CELL] and rate["unit"] == "tok/s"


def test_driver_counts_every_generated_token_once(reader_ctx):
    ctx = reader_ctx["driver_ctx"]
    done = ctx["finished"]
    assert done and not ctx["live"]               # the drain emptied it
    for f in done:
        assert len(f["tokens"]) == f["prompt_len"] + f["max_new"]
    # every token the engine committed since start-up was counted at
    # some boundary: decode's counter plus one first token a prefill
    decoded, prefills = ctx["counted"]
    assert prefills == ctx["next"]
    assert decoded + prefills == sum(ctx["stream"].size(k)[1]
                                     for k in range(ctx["next"]))
    assert reader_ctx["stats"][-1]["dense_gather_bytes"] == 0


# ------------------------------------------- correct can come out false
def test_program_passes_and_each_control_fails_at_the_tiny_size():
    driver, cfg, params = _tiny()
    got = {}
    for name, value, limit in driver.control_checks(cfg, params, 5):
        label, number = name.split(".", 1)
        got.setdefault(label, {})[number] = (value, limit)
    assert set(got) == {"program", "e4m3"} | set(ref.FAULTS)
    program = got.pop("program")
    assert all(v <= lim for v, lim in program.values()), program
    for label, numbers in got.items():
        assert any(v > lim for v, lim in numbers.values()), (label, numbers)


@pytest.mark.parametrize("fault", ["token_altered", "decode_state_unchanged",
                                   "prefill_state_unchanged",
                                   "prompt_not_echoed"])
def test_generate_fault_under_the_harness(monkeypatch, fault):
    from mmlspark_tpu.serving import llm

    if fault == "token_altered":
        real = llm.DecodeExecutor.step

        def broken(self):
            out = real(self)
            for slot, (toks, n_acc) in out.items():
                if self.ptr[slot] % 5 == 0:     # now and then, one token
                    toks[-1] = 2 + (toks[-1] + 7) % 500
                    self.last[slot] = toks[-1]
            return out
        monkeypatch.setattr(llm.DecodeExecutor, "step", broken)
    elif fault == "decode_state_unchanged":
        real = llm.DecodeExecutor.step

        def broken(self):
            keep = self.pools.target        # not donated off the chip
            out = real(self)
            self.pools.target = keep
            return out
        monkeypatch.setattr(llm.DecodeExecutor, "step", broken)
    elif fault == "prefill_state_unchanged":
        real = llm.PrefillExecutor.prefill

        def broken(self, jobs):
            keep = self.pools.target
            out = real(self, jobs)
            self.pools.target = keep
            return out
        monkeypatch.setattr(llm.PrefillExecutor, "prefill", broken)
    else:
        real = llm.LLMEngine._finish

        def broken(self, seq_id):
            out = np.array(real(self, seq_id))
            out[0] = 2 + (out[0] + 1) % 500
            return out
        monkeypatch.setattr(llm.LLMEngine, "_finish", broken)
    rc, result, _, err = run_tiny(CELL)
    assert rc == 0 and result["correct"] is False, err
    assert "NOT CORRECT" in err


def test_margins_are_nought_where_the_reference_itself_served():
    """The comparison's own arithmetic: tokens the reference puts first
    read 0 at every position; another token reads the gap to the best."""
    _, cfg, _ = _tiny()
    w = ref.make_weights(cfg, 3)
    prompt = np.arange(2, 30, dtype=np.int32)
    served = []
    for _ in range(5):
        tokens = np.concatenate([prompt, np.asarray(served, np.int32)])
        logits = np.asarray(ref.forward(w, cfg, tokens, [len(tokens) - 1]))
        served.append(int(logits[0].argmax()))
    margins, gaps = ref.sample_margins(w, cfg, prompt, served)
    assert margins.shape == (5,) and np.all(margins == 0)
    assert gaps.shape == (5,) and np.all(gaps > 0)
    wrong = list(served)
    wrong[2] = 2 + (served[2] + 1) % 500
    margins, _ = ref.sample_margins(w, cfg, prompt, wrong)
    assert margins[2] > 0 and np.all(margins[:2] == 0)
    assert math.isfinite(margins.max())
