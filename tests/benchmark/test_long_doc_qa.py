"""The long-document question-answering cell's own pieces: its controls
(that ``correct`` can come out false), the needed work its roofline and
mfu metrics are computed from, what it reads of a trace, and what
``BENCHMARK.json`` lists for it."""

import numpy as np
import pytest

from benchmark import run, sala_stats, work_sparse_linear

CELL = "minicpm-sala.long-doc-qa"


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


def test_the_program_passes_its_own_comparison(controls):
    assert all(v <= lim for v, lim in controls["program"].values()), \
        controls["program"]


@pytest.mark.parametrize("control", [
    "e4m3",          # the reference one precision below bfloat16
    "no_topk",       # the chosen blocks cut to the first and the window
    "no_restore",    # the state zero at the document's end
    "no_decay"])     # the decay left out of the recurrence
def test_control_in_the_programs_place_is_not_correct(controls, control):
    assert any(v > lim for v, lim in controls[control].values()), \
        controls[control]


def test_the_samples_are_one_long_document_and_short_ones(tiny):
    driver, cfg, params = tiny
    ctx = driver.setup(cfg, params, 5)
    driver.warm(ctx)
    for _ in range(30):
        driver.step(ctx)
    driver.after_window(ctx, False)
    out = driver.outputs_for_check(ctx)
    docs = [s["doc_len"] for s in out["samples"]]
    assert len(docs) == params["check_sequences"]
    assert docs.count(max(params["inputs"]["documents"])) == 1
    assert all(n <= params["check_short_document_max"] for n in docs[1:])
    assert "engine" not in ctx and "variables" not in ctx


# ------------------------------------------------------------ needed work
CFG = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 4, "lightning_nh": 2,
       "lightning_head_dim": 4, "vocab_size": 10,
       "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn"],
       "sparse_config": {"kernel_size": 4, "kernel_stride": 2,
                         "block_size": 4, "topk": 3, "init_blocks": 1,
                         "window_size": 4, "dense_len": 8},
       "param_dtype": "bfloat16", "cache_dtype": "bfloat16"}


def test_a_row_attends_all_below_dense_len_and_topk_blocks_past_it():
    assert work_sparse_linear.attended(CFG, 0, 8) == sum(range(1, 9))
    # position 8: three blocks, the third (its own) seen one token deep
    assert work_sparse_linear.attended(CFG, 8, 1) == 2 * 4 + 1
    assert work_sparse_linear.attended(CFG, 11, 1) == 3 * 4
    assert work_sparse_linear.attended(CFG, 40, 2) == (2 * 4 + 1) + (2 * 4 + 2)
    # compressed keys: one every 2 tokens, whole by the row, none while dense
    assert work_sparse_linear.compressed_keys(CFG, 0, 8) == 0
    assert work_sparse_linear.compressed_keys(CFG, 8, 2) == 3 + 4


def test_kernel_work_counts_states_once_in_and_out_and_blocks_by_reach():
    # two slots decode at position 40 over one 32-token document
    call = [("a", 32, 40, 1), ("a", 32, 41, 1)]
    k = work_sparse_linear.kernel_work(CFG, call)
    att = (2 * 4 + 1) + (2 * 4 + 2)
    assert k["sparse_attn"]["flops"] == 1 * 4 * 4 * 4 * att
    # chosen tokens (19) lie under what the call can reach (32 + 9 + 10)
    assert k["sparse_attn"]["bytes"] == 2 * (2 * 4 * 2) * att \
        + 2 * 2 * 4 * 4 * 2
    state = 2 * 4 * 4 * 4
    assert k["lightning"]["bytes"] == 2 * (2 * 2 * state + 2 * 4 * 2 * 4 * 2)
    assert k["lightning"]["flops"] == 2 * 4 * 2 * 4 * 4 * 2
    keys = (41 // 2 - 1) + (42 // 2 - 1)
    assert k["sparse_select"]["flops"] == 2 * 4 * 4 * keys
    # sixteen slots on one short document cannot need more key bytes than
    # the document and their own tokens hold
    many = [("a", 32, 33, 1)] * 16
    reach = 32 + 16 * 2
    got = work_sparse_linear.kernel_work(CFG, many)["sparse_attn"]["bytes"]
    assert got == 2 * (2 * 4 * 2) * reach + 16 * 2 * 4 * 4 * 2


def test_step_work_reads_the_weights_once_and_the_head_with_a_logit_row():
    sparse = 3 * 8 * 16 + 2 * 8 * 8 + 3 * 8 * 16
    light = 5 * 8 * 8 + 3 * 8 * 16
    assert work_sparse_linear.layer_params(CFG, "minicpm4") == sparse
    assert work_sparse_linear.layer_params(CFG, "lightning-attn") == light
    assert work_sparse_linear.token_params(CFG) == sparse + 2 * light
    call = [("a", 32, 40, 1), ("a", 32, 41, 1)]
    kernels = work_sparse_linear.kernel_work(CFG, call)
    with_head = work_sparse_linear.step_work(CFG, call, logit_rows=2)
    assert with_head["flops"] == 2 * (sparse + 2 * light) * 2 \
        + 2 * 80 * 2 + sum(k["flops"] for k in kernels.values())
    assert with_head["bytes"] == (sparse + 2 * light + 80) * 2 \
        + sum(k["bytes"] for k in kernels.values())
    no_head = work_sparse_linear.step_work(CFG, call, logit_rows=0)
    assert with_head["bytes"] - no_head["bytes"] == 80 * 2


def test_calls_give_a_logit_row_where_a_prompt_ends():
    ctx = {"params": {"engine": {"prefill_batch": 1}, "prefill_chunk": 8}}
    reqs = [(5, 0, 32, 32 + 11, 4), (3, 1, 64, 64 + 6, 9)]
    got = sala_stats.calls(ctx, 5, reqs)
    # the decode call: the first request's first step (it falls at the
    # boundary of its prefill) and the second's third, prefilled at 3
    assert got[0] == ([(0, 32, 43, 1), (1, 64, 70 + 2, 1)], 2)
    # the first request's suffix of 11 in two chunks, the head in the last
    assert got[1:] == [([(0, 32, 32, 8)], 0), ([(0, 32, 40, 3)], 1)]


def test_kernel_seconds_sum_each_kernel_on_the_first_device(tiny):
    driver = tiny[0]
    trace = {"/device:TPU:0": {"XLA Ops": [
        ("paged_sparse_attn.3", 0.0, 2e6), ("paged_sparse_select.1", 0.0, 1e6),
        ("lightning_step.7", 0.0, 3e6), ("lightning_step.8", 0.0, 3e6),
        ("fusion.12", 0.0, 9e6)]},
        "/host:CPU": {"python": [("lightning_chunk", 0.0, 5e6)]}}
    got = driver.kernel_seconds(trace)
    assert got["sparse_attn"] == {"seconds": 2e-3, "calls": 1}
    assert got["lightning_step"] == {"seconds": 6e-3, "calls": 2}
    assert got["lightning_chunk"] == {"seconds": 0.0, "calls": 0}
    assert driver.kernel_seconds({"/host:CPU": {}}) == {}


def test_shares_of_the_device_are_not_read_off_the_chip():
    ctx = {"on_chip": False, "trace": None, "stats": [],
           "driver_ctx": {"kernels": {}}}
    assert sala_stats.kernel_seconds(ctx, "sparse_select") is None
    assert sala_stats.kernel_share(ctx, "lightning_step") is None
    assert sala_stats.hbm_roofline(ctx, "lightning", "lightning_step") is None


# ------------------------------------------- what BENCHMARK.json lists
READERS = ["sala.generate_mfu", "sala.generate_mfu_hbm", "device_idle.sala",
           "sparse_attn_kernel_share", "sparse_attn_hbm_roofline",
           "sparse_select_kernel_share", "lightning_kernel_share",
           "lightning_hbm_roofline", "sparse.blocks_chosen_share",
           "sala.decode_step_ms", "sala.prefill_time_share",
           "sala.prefix_reused_share", "sala.state_restores_per_request",
           "sala.batch_occupancy", "sala.pool_used_share"]


def test_the_benchmark_lists_the_cell_and_its_readers():
    bench = run.load_bench()
    listed = {m["name"]: m for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(READERS) <= set(listed)
    for name in READERS:
        assert listed[name]["moves"] == "tokens_per_s"
        assert listed[name]["workloads"] == [CELL]
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "tokens_per_s")
    assert rate["unit"] == "tok/s" and CELL in rate["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == ("minicpm-sala", 1)


def test_the_traffic_is_the_issues():
    _, _, cfg, params = run.load_cell(CELL, run.load_bench())
    inputs = params["inputs"]
    assert inputs["documents"] == [16384, 24576, 32768, 32768, 49152, 65536]
    assert (inputs["suffix"]["min"], inputs["suffix"]["max"]) == (32, 192)
    assert (inputs["output"]["min"], inputs["output"]["max"]) == (64, 768)
    assert (inputs["token_low"], inputs["token_high"]) == (1, 73448)
    assert params["callers"] == params["engine"]["slots"] == 128
    bl = params["engine"]["block_len"]
    assert bl % 64 == 0 and all(n % bl == 0 for n in inputs["documents"])
    assert all(n > cfg["sparse_config"]["dense_len"]
               for n in inputs["documents"])


def test_the_stream_holds_every_document_once_a_round_at_six_documents(tiny):
    """``traffic_docqa``'s own table refuses a count that is a multiple of
    3; the driver's lays the same sizes in steps of 5."""
    driver = tiny[0]
    _, _, _, params = run.load_cell(CELL, run.load_bench())
    stream = driver.Stream(params["inputs"], 2_147_483_777)
    table = stream.table.reshape(6, 6, 3)
    for r in range(6):
        assert sorted(table[r, :, 0]) == list(range(6))
    for k in range(6):                       # a g-tile of the suffixes
        assert sorted(table[:, k, 0]) == list(range(6))
    assert table[..., 1].min() >= 32 and table[..., 1].max() <= 192
    assert table[..., 2].min() >= 64 and table[..., 2].max() <= 768
    seen = [stream.size(k)[0] for k in range(36)]
    assert sorted(seen) == sorted(list(range(6)) * 6)
    prompt, max_new = stream.request(7)
    doc, suffix_len, want_new = stream.size(7)
    assert len(prompt) == stream.doc_lens[doc] + suffix_len
    assert max_new == want_new and prompt.min() >= 1
    np.testing.assert_array_equal(prompt[:stream.doc_lens[doc]],
                                  stream.document(doc))
    # the tiny table (two documents) is traffic_docqa's own
    from benchmark import traffic_docqa
    small = tiny[2]["inputs"]
    np.testing.assert_array_equal(driver.Stream(small, 3).table,
                                  traffic_docqa.size_table(small))


def test_the_configuration_keeps_every_published_width():
    import json
    _, _, cfg, _ = run.load_cell(CELL, run.load_bench())
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"] == "MiniCPM-SALA")["config"]
    for key, value in published.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert cfg["published"]["mixer_types"] == published["mixer_types"]
    lo = cfg["layer_offset"]
    assert cfg["mixer_types"] == published["mixer_types"][lo:lo + 12]
    assert cfg["mixer_types"].count("minicpm4") * 3 \
        == cfg["mixer_types"].count("lightning-attn")


def test_parameter_count_is_the_deployments():
    from benchmark.references import minicpm_sala as ref
    _, _, cfg, _ = run.load_cell(CELL, run.load_bench())
    n = ref.parameter_count(cfg)
    assert abs(n - 3929.97e6) < 0.01e6
    np.testing.assert_allclose(ref.slopes(cfg, 11)[-1], 2.0 ** -8
                               * (1 - 27 / 31 + 1e-5), rtol=1e-6)
