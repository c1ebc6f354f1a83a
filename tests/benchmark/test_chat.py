"""The chat cell's own pieces: its controls (that ``correct`` can come out
false), the one program a boundary its driver records, the needed work its
roofline and mfu metrics are computed from, what it reads of a trace, and
what ``BENCHMARK.json`` lists for it."""

import io
import json

import numpy as np
import pytest

from benchmark import q3n_stats, run, work_gated_delta_moe
from benchmark.references import qwen3_next as ref

CELL = "qwen3-next-80b-a3b.chat"


@pytest.fixture(scope="module")
def tiny():
    _, wl, cfg, params = run.load_cell(CELL, run.load_bench(), tiny=True)
    return run._load_module("drivers", wl["driver"]), cfg, params


@pytest.fixture(scope="module")
def controls(tiny):
    driver, cfg, params = tiny
    got = {}
    for name, value, limit in driver.control_checks(cfg, params, 3):
        label, number = name.split(".", 1)
        got.setdefault(label, {})[number] = (value, limit)
    return got


def test_the_tiny_cell_through_run_py_reads_correct():
    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell(CELL, 2_147_483_777, 0.2, False, tiny=True, out=out,
                      err=err)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True, err.getvalue()
    assert set(result["checks"]) == set(ref.NUMBERS)
    assert result["metrics"]["tokens_per_s"]["value"] > 0


def test_the_program_passes_its_own_comparison(controls):
    assert all(v <= lim for v, lim in controls["program"].values()), \
        controls["program"]


@pytest.mark.parametrize("control", ("e4m3",) + ref.FAULTS)
def test_control_in_the_programs_place_is_not_correct(controls, control):
    """The reference one precision below bfloat16, and each fault of the
    path planted in the reference: no decay, no delta term, the state zero
    where the last window starts, the tail zero there, no attention gate,
    one held expert's term missing, no restore."""
    assert any(v > lim for v, lim in controls[control].values()), \
        controls[control]


# --------------------------------------------- what a boundary records
@pytest.fixture(scope="module")
def boundaries(tiny):
    driver, cfg, params = tiny
    ctx = driver.setup(cfg, params, 5)
    driver.warm(ctx)
    for _ in range(40):
        driver.step(ctx)
    driver.after_window(ctx, False)
    stats = list(ctx["stats"])
    out = driver.outputs_for_check(ctx)
    return ctx, stats, out, cfg, params


def test_a_boundary_is_one_program_by_the_rows_it_computed(boundaries):
    """The rows the driver reads off the executors are the rows the
    engine's own counters moved by: prompt rows (riding or alone), a
    decode row a committed token, DeltaNet rows by kernel."""
    ctx, stats, _, cfg, params = boundaries
    assert len(stats) == 40 and all("calls" in s for s in stats)
    gdn_layers = cfg["layer_types"].count("linear_attention")
    rode = 0
    for s in stats:
        windows = sum(n for _, n in s["calls"] if n > 1)
        singles = sum(1 for _, n in s["calls"] if n == 1)
        # prompt rows: one-row windows are told from decode rows by the
        # counters (a turn's last window may hold one row)
        prompt_rows = s["ride_rows"] + s["alone_rows"]
        assert s["gdn_step_rows"] + s["gdn_chunk_rows"] \
            == gdn_layers * (windows + singles)
        assert windows <= prompt_rows <= windows + singles
        assert s["tokens"] - s["prefilled"] == windows + singles \
            - prompt_rows
        assert s["logit_rows"] >= s["tokens"] - s["prefilled"]
        rode += s["ride_rows"]
    # ten slots: turns ride with the decoding rows once eight decode
    assert rode > 0
    assert all(first >= s["doc_len"] for s in stats
               for first, _ in s["calls"])
    restores = sum(s["state_restores"] for s in stats)
    assert restores == sum(s["prefilled"] for s in stats) > 0
    # the index counts a hit when a request is admitted, the restore is
    # made when its first window is fed: a boundary or two apart
    hits, rem = divmod(sum(s["prefix_reused"] for s in stats),
                       params["inputs"]["system_prompt"])
    assert rem == 0 and abs(hits - restores) <= 2


def test_the_samples_are_the_first_the_last_and_the_longest_turn(boundaries):
    ctx, _, out, _, params = boundaries
    assert len(out["samples"]) == params["check_sequences"]
    assert "engine" not in ctx and "variables" not in ctx
    for s in out["samples"]:
        assert s["doc_len"] == params["inputs"]["system_prompt"]
        assert s["doc_len"] <= s["window_start"] < len(s["prompt"])


def test_the_last_window_starts_after_the_snapshots_cut(tiny):
    driver = tiny[0]
    # system prompt 1,024, blocks of 512, windows of 512
    assert driver.last_window_start(1024 + 100, 1024, 512, 512) == 1024
    assert driver.last_window_start(1024 + 700, 1024, 512, 512) == 1536
    # blocks of 256: the cut at 2,304 ends a 256-row window
    assert driver.last_window_start(1024 + 1500, 1024, 256, 512) == 2304
    # a prompt that ends on a block: no second stretch
    assert driver.last_window_start(2048, 1024, 512, 512) == 1536


# ------------------------------------------------------------ needed work
CFG = {"hidden_size": 8, "vocab_size": 10, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 4, "linear_num_key_heads": 1,
       "linear_num_value_heads": 2, "linear_key_head_dim": 4,
       "linear_value_head_dim": 4, "linear_conv_kernel_dim": 4,
       "num_experts": 8, "experts_held": [0, 2], "moe_intermediate_size": 6,
       "shared_expert_intermediate_size": 6,
       "layer_types": ["linear_attention", "linear_attention",
                       "full_attention"],
       "param_dtype": "bfloat16", "cache_dtype": "bfloat16"}


def test_kernel_work_counts_states_once_in_and_out_a_row_or_a_window():
    # two slots decode at 40 and 41 over one 32-token prompt; a third
    # brings a window of 5 rows from position 32
    call = [(0, 32, 40, 1), (0, 32, 41, 1), (0, 32, 32, 5)]
    k = work_gated_delta_moe.kernel_work(CFG, call)
    state, rule, io_row = 2 * 4 * 4 * 4, 7 * 4 * 4 * 2, (2 * 4 + 2 * 4) * 2 * 2
    assert k["gdn_step"] == {"flops": 2 * rule * 2,
                             "bytes": 2 * (2 * 2 * state + 2 * io_row)}
    assert k["gdn_chunk"] == {"flops": 2 * rule * 5,
                              "bytes": 2 * (1 * 2 * state + 5 * io_row)}
    attended = 41 + 42 + sum(range(33, 38))
    reach = 32 + 9 + 10 + 5          # the prompt once, then each one's own
    assert k["gqa_attn"]["flops"] == 1 * 4 * 4 * 4 * attended
    assert k["gqa_attn"]["bytes"] == 1 * (2 * 2 * 4 * 2 * reach
                                          + 7 * 2 * 4 * 4 * 2)


def test_step_work_reads_the_weights_once_a_program():
    linear = 8 * (2 * 4 + 2 * 8) + 8 * 4 + 8 * 8
    full = 8 * 4 * 8 + 2 * 8 * 8 + 16 * 8
    every = 8 * 8 + 3 * 8 * 6 + 8
    assert work_gated_delta_moe.mixer_params(CFG, "linear_attention") \
        == linear
    assert work_gated_delta_moe.mixer_params(CFG, "full_attention") == full
    assert work_gated_delta_moe.token_params(CFG) \
        == 2 * linear + full + 3 * every
    call = [(0, 32, 40, 1), (0, 32, 32, 5)]
    kernels = work_gated_delta_moe.kernel_work(CFG, call)
    got = work_gated_delta_moe.step_work(CFG, call, held_pairs=7,
                                         experts_touched=3, logit_rows=2)
    conv = 2 * 4 * (2 * 4 + 8)
    assert got["flops"] == 2 * (2 * linear + full + 3 * every) * 6 \
        + 2 * 3 * 8 * 6 * 7 + 2 * conv * 6 + 2 * 80 * 2 \
        + sum(k["flops"] for k in kernels.values())
    tail = 3 * 16 * 2
    assert got["bytes"] == (2 * linear + full + 3 * every + 80
                            + 3 * 8 * 6 * 3) * 2 + 2 * 2 * 2 * tail \
        + sum(k["bytes"] for k in kernels.values())
    none = work_gated_delta_moe.step_work(CFG, call, held_pairs=7,
                                          experts_touched=3, logit_rows=0)
    assert got["bytes"] - none["bytes"] == 80 * 2


def test_needed_sums_the_boundaries_programs(boundaries):
    _, stats, _, cfg, params = boundaries
    ctx = {"cfg": cfg, "params": params, "stats": stats, "on_chip": False,
           "driver_ctx": {}, "trace": None}
    need = q3n_stats.needed(ctx, q3n_stats.window(ctx))
    assert need["flops"] > 0 and need["bytes"] > 0
    assert need["flops"] > sum(need[f"{k}_flops"] for k in q3n_stats.KERNELS)
    one = q3n_stats.needed(ctx, stats[:1])
    assert 0 < one["bytes"] < need["bytes"]
    quiet = q3n_stats.decode_only(ctx)
    assert all(s["ride_rows"] == 0 for s in quiet) and len(quiet) < len(stats)


def test_kernel_seconds_sum_each_kernel_on_the_first_device(tiny):
    driver = tiny[0]
    trace = {"/device:TPU:0": {"XLA Ops": [
        ("gated_delta_step.3", 0.0, 2e6), ("gated_delta_step.4", 0.0, 2e6),
        ("gated_delta_chunk.1", 0.0, 1e6), ("paged_gqa_attn.7", 0.0, 3e6),
        ("lightning_step.7", 0.0, 3e6), ("fusion.12", 0.0, 9e6)]},
        "/host:CPU": {"python": [("gated_delta_chunk", 0.0, 5e6)]}}
    got = driver.kernel_seconds(trace)
    assert got["gdn_step"] == {"seconds": 4e-3, "calls": 2}
    assert got["gdn_chunk"] == {"seconds": 1e-3, "calls": 1}
    assert got["gqa_attn"] == {"seconds": 3e-3, "calls": 1}
    assert driver.kernel_seconds({"/host:CPU": {}}) == {}


def test_shares_of_the_device_are_not_read_off_the_chip():
    ctx = {"on_chip": False, "trace": None, "stats": [],
           "driver_ctx": {"kernels": {}}}
    assert q3n_stats.kernel_seconds(ctx, "gdn_step") is None
    assert q3n_stats.kernel_share(ctx, "gdn_step", "gdn_chunk") is None
    assert q3n_stats.roofline(ctx, "gqa_attn") is None
    assert q3n_stats.whole_step_share(ctx, "flops",
                                      "flops_per_s_bf16") is None


# ------------------------------------------- what BENCHMARK.json lists
READERS = ["q3n.generate_mfu", "q3n.generate_mfu_hbm",
           "gated_delta_kernel_share", "gated_delta_step_hbm_roofline",
           "gated_delta_chunk_roofline", "gqa_attn_kernel_share",
           "gqa_attn_roofline", "device_idle.q3n", "q3n.decode_step_ms",
           "q3n.prefill_time_share", "q3n.ride_rows_per_boundary",
           "q3n.batch_occupancy", "q3n.pool_used_share",
           "q3n.prefix_reused_share", "q3n.state_restores_per_request",
           "q3n.held_pairs_per_token", "q3n.expert_load_max_over_mean",
           "q3n.experts_touched_share"]


def test_the_benchmark_lists_the_cell_and_its_readers():
    bench = run.load_bench()
    listed = {m["name"]: m for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(READERS) == set(listed)
    for name in READERS:
        assert listed[name]["moves"] == "tokens_per_s"
        assert listed[name]["workloads"] == [CELL]
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "tokens_per_s")
    assert rate["unit"] == "tok/s" and CELL in rate["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"], cell["traffic"]) \
        == ("qwen3-next-80b-a3b", 1, "chat")


def test_the_traffic_is_the_issues(tiny):
    driver = tiny[0]
    _, _, cfg, params = run.load_cell(CELL, run.load_bench())
    inputs = params["inputs"]
    assert inputs["system_prompt"] == 1024 and inputs["table"] == 64
    assert inputs["turn"] == {"median": 1536, "sigma": 0.8, "min": 128,
                              "max": 8192}
    assert inputs["output"] == {"median": 384, "sigma": 0.6, "min": 64,
                                "max": 1024}
    assert (inputs["token_low"], inputs["token_high"]) \
        == (1, cfg["vocab_size"])
    assert params["callers"] == params["engine"]["slots"] == 128
    assert params["warm_requests"] == 128
    assert params["engine"]["max_seq_len"] == 10240
    assert inputs["system_prompt"] % params["engine"]["block_len"] == 0
    assert params["prefill_chunk"] in (256, 512, 1024)
    stream = driver.Stream(inputs, 2_147_483_777)
    table = stream.table.reshape(8, 8, 3)
    assert (table[..., 0] == 0).all()
    assert table[..., 1].min() >= 128 and table[..., 1].max() <= 8192
    assert table[..., 2].min() >= 64 and table[..., 2].max() <= 1024
    # eight balanced rounds: a round's turns within a tenth of the mean's
    rounds = table[..., 1].sum(axis=1)
    assert rounds.max() / rounds.min() < 1.25
    # every seed the same sizes in another order
    other = driver.Stream(inputs, 5)
    a = sorted(stream.size(k) for k in range(64))
    assert a == sorted(other.size(k) for k in range(64))
    assert [stream.size(k) for k in range(64)] \
        != [other.size(k) for k in range(64)]
    prompt, max_new = stream.request(7)
    _, turn, want_new = stream.size(7)
    assert len(prompt) == 1024 + turn and max_new == want_new
    assert prompt.min() >= 1 and prompt.max() < cfg["vocab_size"]
    np.testing.assert_array_equal(prompt[:1024], stream.document(0))
    np.testing.assert_array_equal(other.request(3)[0][:8],
                                  other.document(0)[:8])


def test_the_configuration_keeps_every_published_width():
    _, _, cfg, _ = run.load_cell(CELL, run.load_bench())
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows
               if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "num_experts_held": 512,
                                "vocab_size": 151936}
    assert cfg["num_experts"] == 512 and cfg["experts_held"] == [0, 128]
    assert cfg["vocab_size"] * 4 == 151936
    # two whole periods of the published 3 : 1
    assert tuple(cfg["layer_types"]) == ref.layer_types(
        {**cfg, "layer_types": None})
    assert cfg["layer_types"].count("linear_attention") == 6


def test_parameter_count_is_the_deployments():
    _, _, cfg, _ = run.load_cell(CELL, run.load_bench())
    n = ref.parameter_count(cfg)
    assert abs(n - 3667.2e6) < 0.1e6
    assert work_gated_delta_moe.state_bytes(cfg) == 2 << 20
    assert work_gated_delta_moe.tail_bytes(cfg) == 48 << 10
