"""The long-reasoning cell's own pieces: its controls (that ``correct`` can
come out false), the aged population its driver brings in set-up, the one
program a boundary it records, the needed work its roofline and mfu
metrics are computed from, what it reads of a trace, and what
``BENCHMARK.json`` lists for it."""

import io
import json

import numpy as np
import pytest

from benchmark import kimi_stats, run, work_kda_mla_moe
from benchmark.references import kimi_linear as ref

CELL = "kimi-linear-48b-a3b.long-reasoning"
SOURCE = ("https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
          "blob/main/config.json")


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
    assert set(result["metrics"]) == {"setup_s", "tokens_per_s"}
    assert result["metrics"]["tokens_per_s"]["value"] > 0


def test_the_program_passes_its_own_comparison(controls):
    assert all(v <= lim for v, lim in controls["program"].values()), \
        controls["program"]


@pytest.mark.parametrize("control", ("e4m3",) + ref.FAULTS)
def test_control_in_the_programs_place_is_not_correct(controls, control):
    """The reference one precision below bfloat16, and each fault of the
    path planted in the reference: the decay averaged over a head's
    channels, no delta term, no output gate, the tail zero where the last
    window starts, one held expert's term missing, the choosing bias left
    out."""
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
    live = {sid: (len(m.prompt), len(m.generated))
            for sid, m in ctx["engine"]._meta.items()}
    out = driver.outputs_for_check(ctx)
    return ctx, stats, out, cfg, params, live


def test_a_boundary_is_one_program_by_the_rows_it_computed(boundaries):
    """The rows the driver reads off the executors are the rows the
    engine's own counters moved by: prompt rows (riding or alone), a
    decode row a committed token, KDA rows by kind, the latent positions
    the decoding rows read, and the pairs of the expert layers."""
    ctx, stats, _, cfg, _, _ = boundaries
    assert len(stats) == 40 and all("calls" in s for s in stats)
    kda_layers, experts = 4, cfg["num_experts_per_token"]
    for s in stats:
        windows = sum(n for _, n in s["calls"] if n > 1)
        singles = [first for first, n in s["calls"] if n == 1]
        prompt_rows = s["ride_rows"] + s["alone_rows"]
        assert s["kda_step_rows"] + s["kda_chunk_rows"] \
            == kda_layers * (windows + len(singles))
        assert s["moe_held"] + s["moe_absent"] == (
            cfg["num_hidden_layers"] - 1) * experts * (windows + len(singles))
        assert windows <= prompt_rows <= windows + len(singles)
        if s["alone_rows"] == 0 and not any(n > 1 for _, n in s["calls"]):
            assert s["latent_positions"] == sum(f + 1 for f in singles)
    assert sum(s["ride_rows"] for s in stats) > 0


def test_the_callers_first_requests_come_aged(tiny):
    """Each caller's first request carries a share of its answer in its
    prompt, spread over the answer, and leaves the rest to decode, the
    same tokens in all for every seed; a later request is fresh."""
    driver, _, params = tiny
    _, _, _, full = run.load_cell(CELL, run.load_bench())
    stream = driver.Stream(full["inputs"], 2_147_483_777)
    sizes = [stream.size(k) for k in range(full["callers"])]
    contexts = np.asarray([n for _, n, _ in sizes])
    assert 1.0e6 < contexts.sum() < 1.5e6
    # another seed orders the same work
    other = driver.Stream(full["inputs"], 5)
    assert sum(other.size(k)[1] for k in range(full["callers"])) \
        == contexts.sum()
    assert contexts.min() >= 512 and contexts.max() <= 2048 + 32768
    for k in range(3):
        table_prompt, answer = stream.table[stream._orders[0][k]]
        _, sent, left = stream.size(k)
        assert sent - table_prompt == answer - left >= 0
        prompt, max_new = stream.request(k)
        assert len(prompt) == sent and max_new == left
    _, sent, left = stream.size(full["callers"])
    assert sent <= 2048 and 8192 <= left <= 32768


def test_the_samples_hold_the_two_longest_contexts(boundaries):
    ctx, _, out, _, params, live = boundaries
    assert len(out["samples"]) == params["check_sequences"]
    assert "engine" not in ctx and "variables" not in ctx
    contexts = sorted(p + g + 1 for p, g in live.values() if g)
    got = sorted(len(s["prompt"]) + len(s["served"]) for s in out["samples"])
    assert got[-1] >= contexts[-1]
    for s in out["samples"]:
        assert 0 <= s["window_start"] < len(s["prompt"])


def test_the_check_scores_every_served_row_from_the_first():
    """The first decoded rows, right after the set-up's prefill, are
    scored with the rest: the rows of served tokens 0, 1, ... up to
    ``most``."""
    assert ref.compared_rows(100, 5, 64).tolist() == [99, 100, 101, 102, 103]
    assert ref.compared_rows(100, 500, 64).tolist() == list(range(99, 163))


def test_the_choosing_bias_spreads_the_load_over_every_expert(tiny):
    """``make_weights`` sets each expert layer's choosing bias so that its
    load is even, as training sets it: on tokens it was not set on, the
    busiest expert holds under twice the mean."""
    _, cfg, _ = tiny
    d = ref.dims(cfg)
    drawn = ref.balance_router_bias
    try:
        ref.balance_router_bias = lambda w, c, s: w
        raw = ref.make_weights(cfg, 9)
    finally:
        ref.balance_router_bias = drawn
    balanced = ref.make_weights(cfg, 9)
    tok = np.random.default_rng(4).integers(1, d["V"], 1024)
    x = balanced["embed"][tok].astype(np.float32)
    layer = d["dense"]                  # the first expert layer
    for lw in balanced["layers"][:layer]:
        x = ref._dense(ref._kda_rows(
            x, 0, np.zeros((d["H"], d["d"], d["d"]), np.float32),
            np.zeros((d["taps"] - 1, 3 * d["H"] * d["d"]), np.float32), lw,
            -1, consts=ref.mixer_consts(d), round_fn=ref._identity,
            round_cache=ref._cache_round(cfg["cache_dtype"]),
            fault=None)[0], lw, eps=d["eps"], round_fn=ref._identity)
    loads = []
    for w in (raw, balanced):
        lw = w["layers"][layer]
        _, weights = ref._route(x, lw, eps=d["eps"], top_k=d["K"],
                                renorm=d["renorm"], scale=d["scale"],
                                round_fn=ref._identity, biased=True)
        load = np.asarray(weights > 0).sum(0)
        loads.append(load.max() / load.mean())
    assert loads[1] < 2.0 and loads[1] < loads[0], loads
    assert not np.array_equal(np.asarray(raw["layers"][layer]["router_bias"]),
                              np.asarray(lw["router_bias"]))


def test_the_missing_term_is_the_busiest_held_experts(tiny):
    """The planted ``expert_term_missing`` leaves out the held expert with
    the most pairs among the rows, and only it."""
    _, cfg, _ = tiny
    d = ref.dims(cfg)
    lw = ref.make_weights(cfg, 3)["layers"][d["dense"]]
    h = np.random.default_rng(0).normal(size=(64, d["D"])).astype(np.float32)
    _, weights = ref._route(h, lw, eps=d["eps"], top_k=d["K"],
                            renorm=d["renorm"], scale=d["scale"],
                            round_fn=ref._identity, biased=True)
    pairs = np.asarray(weights[:, d["lo"]:d["hi"]] > 0).sum(0)
    busiest = d["lo"] + int(np.argmax(pairs))
    got = ref.moe_layer(h, lw, d, fault="expert_term_missing")
    want = ref.moe_layer(h, lw, d, held=(d["lo"], d["hi"]))
    u, _ = ref._route(h, lw, eps=d["eps"], top_k=d["K"],
                      renorm=d["renorm"], scale=d["scale"],
                      round_fn=ref._identity, biased=True)
    e = busiest - d["lo"]
    alone = ref._expert_term(u, weights[:, busiest], lw["exp_gate"][e],
                             lw["exp_up"][e], lw["exp_down"][e],
                             round_fn=ref._identity)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want - alone),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ needed work
CFG = {"hidden_size": 8, "vocab_size": 10, "num_hidden_layers": 3,
       "linear_attn_config": {"full_attn_layers": [3, 6],
                              "kda_layers": [1, 2], "num_heads": 2,
                              "head_dim": 4, "short_conv_kernel_size": 3},
       "num_attention_heads": 2, "kv_lora_rank": 6, "qk_rope_head_dim": 2,
       "qk_nope_head_dim": 4, "v_head_dim": 4, "intermediate_size": 12,
       "moe_intermediate_size": 6, "num_shared_experts": 1,
       "num_experts": 8, "experts_held": [0, 4], "first_k_dense_replace": 1,
       "param_dtype": "bfloat16", "cache_dtype": "bfloat16"}


def test_kernel_work_counts_the_cache_and_the_states_once():
    # two slots decode at 40 and 41; a third brings a window of 5 rows
    # from position 32
    call = [(None, 0, 40, 1), (None, 0, 41, 1), (None, 0, 32, 5)]
    k = work_kda_mla_moe.kernel_work(CFG, call)
    attended = 41 + 42 + sum(range(33, 38))
    reach = 41 + 42 + 37             # each one's own positions
    assert k["latent_attn"]["flops"] == 2 * 2 * (6 + 2 + 6) * attended
    assert k["latent_attn"]["bytes"] == (6 + 2) * 2 * reach \
        + 7 * 2 * (2 * 6 + 2) * 2
    state = 2 * 4 * 4 * 4
    row = 4 * 4 * 2 * 2 + 5 * 2 * 4
    assert k["kda_step"]["flops"] == 2 * 7 * 4 * 4 * 2 * 2
    assert k["kda_step"]["bytes"] == 2 * (2 * 2 * state + 2 * row)
    assert k["kda_chunk"]["bytes"] == 2 * (1 * 2 * state + 5 * row)


def test_step_work_reads_the_weights_once_a_program():
    kda = 3 * 8 * 8 + 8 * 2 + 2 * (8 * 4 + 4 * 8) + 8 * 8
    mla = 8 * 2 * 6 + 8 * 8 + 6 * 2 * 8 + 2 * 4 * 8
    assert work_kda_mla_moe.mixer_params(CFG, "kda") == kda
    assert work_kda_mla_moe.mixer_params(CFG, "mla") == mla
    token = 2 * kda + mla + 3 * 8 * 12 + 2 * (8 * 8 + 3 * 8 * 6)
    assert work_kda_mla_moe.token_params(CFG) == token
    call = [(None, 0, 40, 1), (None, 0, 32, 5)]
    got = work_kda_mla_moe.step_work(CFG, call, held_pairs=7,
                                     experts_touched=3, logit_rows=2)
    kernels = got["kernels"]
    assert got["flops"] == 2 * token * 6 + 2 * 3 * 8 * 6 * 7 \
        + 2 * 2 * 3 * 3 * 8 * 6 + 2 * 80 * 2 \
        + sum(k["flops"] for k in kernels.values())
    tail = 2 * 3 * 8 * 2
    assert got["bytes"] == (token + 80 + 3 * 8 * 6 * 3) * 2 \
        + 2 * 2 * 2 * tail + sum(k["bytes"] for k in kernels.values())


def test_needed_sums_the_boundaries_programs(boundaries):
    _, stats, _, cfg, params, _ = boundaries
    ctx = {"cfg": cfg, "params": params, "stats": stats, "on_chip": False,
           "driver_ctx": {}, "trace": None}
    need = kimi_stats.needed(ctx, kimi_stats.window(ctx))
    assert need["flops"] > 0 and need["bytes"] > 0
    assert need["kda_step_bytes"] > 0 and need["latent_attn_flops"] > 0
    one = kimi_stats.needed(ctx, stats[:1])
    assert 0 < one["bytes"] < need["bytes"]


@pytest.mark.parametrize("name,low,high", [
    ("kimi.pool_used_share", 0.0, 100.0),
    ("kimi.batch_occupancy", 0.0, 100.0),
    ("kimi.expert_load_max_over_mean", 1.0, 4 * 4.0),
    ("kimi.context_tokens_mean", 1.0, 96.0),
    ("kimi.decode_step_ms", 0.0, float("inf"))])
def test_the_counter_readers_read_the_tiny_record(boundaries, name, low,
                                                  high):
    ctx, stats, _, cfg, params, _ = boundaries
    read = run._load_module("layer_metrics", name).read
    got = read({"cfg": cfg, "params": params, "stats": stats,
                "on_chip": True, "driver_ctx": ctx, "trace": None})
    assert low < got <= high, got


def test_half_the_pairs_land_on_held_experts(boundaries):
    """Four of eight experts held, two a token: about one held pair a
    token a layer."""
    ctx, stats, _, cfg, params, _ = boundaries
    read = run._load_module("layer_metrics", "kimi.held_pairs_per_token").read
    got = read({"cfg": cfg, "params": params, "stats": stats,
                "on_chip": True, "driver_ctx": ctx})
    assert 0.5 < got < 1.5


def test_kernel_seconds_sum_each_kernel_on_the_first_device(tiny):
    driver = tiny[0]
    trace = {"/device:TPU:0": {"XLA Ops": [
        ("kda_step.3", 0.0, 2e6), ("kda_chunk.4", 0.0, 5e6),
        ("paged_latent.7", 0.0, 3e6), ("ragged-dot-gate-up.2", 0.0, 4e6),
        ("ragged-dot-down.2", 0.0, 1e6), ("fusion.12", 0.0, 9e6)]},
        "/host:CPU": {"python": [("kda_step", 0.0, 5e6)]}}
    got = driver.kernel_seconds(trace)
    assert got["kda_step"] == {"seconds": 2e-3, "calls": 1}
    assert got["kda_chunk"] == {"seconds": 5e-3, "calls": 1}
    assert got["latent_attn"] == {"seconds": 3e-3, "calls": 1}
    assert got["expert"] == {"seconds": 5e-3, "calls": 2}
    assert driver.kernel_seconds({"/host:CPU": {}}) == {}


def test_the_readers_find_nothing_in_a_parents_record():
    """A record without this driver's keys (another cell's, or a
    program without the counters) leaves every metric out."""
    ctx = {"cfg": {}, "params": {"engine": {"slots": 1}},
           "stats": [{"seconds": 1.0}], "on_chip": True, "trace": None,
           "driver_ctx": {}, "elapsed_s": 1.0, "peaks": None}
    for name in READERS:
        if name == "kimi.host_ms":
            continue
        assert run._load_module("layer_metrics", name).read(ctx) is None, \
            name


# ------------------------------------------- what BENCHMARK.json lists
READERS = ["kimi.generate_mfu", "kimi.generate_mfu_hbm", "device_idle.kimi",
           "kda_kernel_share", "kda_step_hbm_roofline", "kda_chunk_roofline",
           "kimi.latent_attn_kernel_share", "kimi.latent_attn_roofline",
           "kimi.expert_kernel_share", "kimi.decode_step_ms", "kimi.host_ms",
           "kimi.held_pairs_per_token", "kimi.expert_load_max_over_mean",
           "kimi.batch_occupancy", "kimi.pool_used_share",
           "kimi.context_tokens_mean"]


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
        == ("kimi-linear-48b-a3b", 1, "long-reasoning")


def test_the_traffic_is_the_cells():
    _, wl, cfg, params = run.load_cell(CELL, run.load_bench())
    inputs = params["inputs"]
    assert wl["kernel_pattern"] == "kda_"
    assert inputs["prompt"]["median"] == 1024
    assert (inputs["prompt"]["min"], inputs["prompt"]["max"]) == (512, 2048)
    assert inputs["output"] == {"min": 8192, "max": 32768}
    assert (inputs["token_low"], inputs["token_high"]) \
        == (1, cfg["vocab_size"])
    assert params["callers"] == params["engine"]["slots"] \
        == inputs["aged"] == 128
    assert params["engine"]["max_seq_len"] == 2048 + 32768


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's config is in the file under its key,
    and what is changed is listed in ``reduced`` with its published
    value."""
    _, _, cfg, _ = run.load_cell(CELL, run.load_bench())
    assert cfg["source"] == SOURCE
    assert cfg["reduced"] == ["num_hidden_layers", "linear_attn_config",
                              "num_experts_held", "vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 27
    assert cfg["published"]["vocab_size"] == 163840
    assert cfg["published"]["num_experts_held"] == 256
    lin, pub = cfg["linear_attn_config"], \
        cfg["published"]["linear_attn_config"]
    assert {k: v for k, v in lin.items() if "layers" not in k} \
        == {k: v for k, v in pub.items() if "layers" not in k}
    assert lin["kda_layers"] == [i for i in pub["kda_layers"] if i <= 5]
    assert lin["full_attn_layers"] == [4]
    assert (cfg["hidden_size"], cfg["num_experts"],
            cfg["num_experts_per_token"], cfg["moe_intermediate_size"],
            cfg["intermediate_size"]) == (2304, 256, 8, 1024, 9216)
    assert cfg["experts_held"] == [0, 64] and cfg["vocab_size"] == 40960
    assert cfg["q_lora_rank"] is None and cfg["mla_use_nope"] is True


def test_parameter_count_is_the_deployments():
    _, _, cfg, _ = run.load_cell(CELL, run.load_bench())
    assert abs(ref.parameter_count(cfg) - 2282.27e6) < 0.01e6
    assert work_kda_mla_moe.state_bytes(cfg) == 2 << 20
    assert work_kda_mla_moe.tail_bytes(cfg) == 72 << 10
