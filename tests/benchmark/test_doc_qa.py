"""The document question-answering cell's own pieces: its controls (that
``correct`` can come out false), its traffic table, and the needed work
its roofline metrics are computed from."""

import numpy as np
import pytest

from benchmark import run, traffic_docqa, work_mla_moe

CELL = "deepseek-v2.doc-qa"


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
    "e4m3",                    # the reference one precision below bfloat16
    "expert_term_missing",     # one held expert's term left out
    "no_group_limit",          # plain top-k over all the experts
    "last_chunk_missing"])     # the suffix never reached the latent cache
def test_control_in_the_programs_place_is_not_correct(controls, control):
    assert any(v > lim for v, lim in controls[control].values()), \
        controls[control]


def test_table_holds_every_document_once_a_round():
    spec = run.load_cell(CELL, run.load_bench())[3]["inputs"]
    table = traffic_docqa.size_table(spec)
    g = len(spec["documents"])
    assert table.shape == (g * g, 3)
    rounds = table.reshape(g, g, 3)
    for r in rounds:
        assert sorted(r[:, 0]) == list(range(g))
    # every round is nearly the same work
    for col in (1, 2):
        sums = rounds[:, :, col].sum(axis=1)
        assert sums.max() <= 1.1 * sums.min()
    assert table[:, 1].min() >= spec["suffix"]["min"]
    assert table[:, 2].max() <= spec["output"]["max"]
    # the longest request fits the engine
    eng = run.load_cell(CELL, run.load_bench())[3]["engine"]
    assert max(spec["documents"]) + spec["suffix"]["max"] \
        + spec["output"]["max"] <= eng["max_seq_len"]


def test_stream_is_the_table_in_a_seeded_order_and_shares_documents():
    spec = run.load_cell(CELL, run.load_bench(), tiny=True)[3]["inputs"]
    a = traffic_docqa.DocQAStream(spec, 2_147_483_777)
    b = traffic_docqa.DocQAStream(spec, 2_147_483_777)
    c = traffic_docqa.DocQAStream(spec, 5)
    n = len(a.table)
    sizes = sorted(a.size(k) for k in range(n))
    assert sizes == sorted(tuple(int(v) for v in row) for row in a.table)
    assert sizes == sorted(c.size(k) for k in range(n))
    for k in (0, 3, n + 1):
        pa, ma = a.request(k)
        pb, mb = b.request(k)
        assert ma == mb and np.array_equal(pa, pb)
        doc, suffix_len, _ = a.size(k)
        assert len(pa) == a.doc_lens[doc] + suffix_len
        assert np.array_equal(pa[:a.doc_lens[doc]], a.document(doc))
    assert not np.array_equal(a.document(0), c.document(0))


CFG = {"hidden_size": 8, "num_attention_heads": 2, "kv_lora_rank": 6,
       "qk_rope_head_dim": 2, "qk_nope_head_dim": 4, "v_head_dim": 4,
       "q_lora_rank": 5, "intermediate_size": 16,
       "moe_intermediate_size": 3, "n_routed_experts": 8,
       "experts_held": [0, 2], "n_shared_experts": 2,
       "num_hidden_layers": 3, "first_k_dense_replace": 1,
       "vocab_size": 10, "param_dtype": "bfloat16",
       "cache_dtype": "bfloat16"}


def test_needed_work_counts_a_shared_document_once_in_bytes():
    """Three slots decode over one 100-token document and one over none:
    FLOPs are per slot, bytes count the document once."""
    shared = [("a", 100, 110, 1), ("a", 100, 103, 1), ("a", 100, 100, 1)]
    alone = [(None, 0, 50, 1)]
    per_pos = 2 * 2 * (6 + 2 + 6)                 # heads * (C + R + C) * 2
    assert work_mla_moe.position_flops(CFG) == per_pos
    assert work_mla_moe.position_bytes(CFG) == (6 + 2) * 2
    assert work_mla_moe.attended(shared) == 111 + 104 + 101
    assert work_mla_moe.distinct_positions(shared) == 100 + 11 + 4 + 1
    assert work_mla_moe.distinct_positions(shared + alone) == 116 + 51
    k = work_mla_moe.kernel_work(CFG, shared)
    assert k["flops"] == 3 * per_pos * 316
    assert k["bytes"] == 3 * (16 * 116 + 3 * 2 * (6 + 2 + 6) * 2)
    # the same slots with nothing shared read every position per slot
    unshared = [(None, 0, first, rows) for _, _, first, rows in shared]
    assert work_mla_moe.kernel_work(CFG, unshared)["flops"] == k["flops"]
    assert work_mla_moe.distinct_positions(unshared) == 316
    # a prefill window of 4 rows after the document
    assert work_mla_moe.attended([("a", 100, 100, 4)]) \
        == 101 + 102 + 103 + 104


def test_step_work_counts_weights_once_and_experts_by_pair():
    attn = 8 * 5 + 5 * 2 * 6 + 8 * 8 + 6 * 2 * 8 + 2 * 4 * 8
    assert work_mla_moe.attention_params(CFG) == attn
    every = 3 * attn + 3 * 8 * 16 + 2 * (8 * 8 + 3 * 8 * 6)
    assert work_mla_moe.token_params(CFG) == every
    call = [("a", 10, 12, 1), ("a", 10, 10, 1)]
    got = work_mla_moe.step_work(CFG, call, held_pairs=5,
                                 experts_touched=3, logit_rows=2)
    assert got["flops"] == (2 * every * 2 + 2 * (3 * 8 * 3) * 5
                            + 3 * work_mla_moe.position_flops(CFG) * 24
                            + 2 * 80 * 2)
    assert got["bytes"] == ((every + 80 + 72 * 3) * 2
                            + 3 * 16 * (10 + 3 + 1))


# ------------------------------------------- what BENCHMARK.json lists
# Membership and subset checks only: a later PR may append a cell to a
# metric's ``workloads`` and add per-layer entries, and these must hold
# then too.
READERS = ["dsv2.generate_mfu", "dsv2.generate_mfu_hbm",
           "mla_attn_kernel_share", "mla_attn_roofline", "device_idle.dsv2",
           "dsv2.decode_step_ms", "dsv2.prefill_step_ms",
           "dsv2.prefill_time_share", "dsv2.decode_span_ms",
           "dsv2.batch_occupancy", "dsv2.pool_used_share",
           "moe.held_pairs_per_token", "moe.expert_load_max_over_mean",
           "dsv2.prefix_reused_share"]
SPAN_READERS = ("dsv2.prefill_time_share", "dsv2.decode_span_ms")


def test_the_benchmark_lists_the_cell_and_its_readers():
    bench = run.load_bench()
    listed = {m["name"]: m for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(READERS) <= set(listed)
    for name in READERS:
        assert listed[name]["moves"] == "tokens_per_s"
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "tokens_per_s")
    assert rate["unit"] == "tok/s" and CELL in rate["workloads"]


def test_the_span_readers_are_listed_as_read_from_program_spans():
    listed = {m["name"]: m for m in run.load_bench()["per_layer"]}
    for name in SPAN_READERS:
        m = listed[name]
        assert m["source"] == "program_span" and CELL in m["workloads"]
        assert m["layer"] == "host dispatch"


def test_span_readers_read_the_engines_spans_in_a_tiny_traced_run():
    """``llm.step`` / ``llm.decode`` reach the readers through
    ``span_metrics``: a positive number of milliseconds on the CPU (the
    share, a percentage, is left out there like every share)."""
    import io
    import json
    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell(CELL, 2_147_483_777, 0.2, True, tiny=True, out=out,
                      err=err)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True, err.getvalue()
    got = result["metrics"]["dsv2.decode_span_ms"]
    assert got["unit"] == "ms" and got["value"] > 0
    assert "dsv2.prefill_time_share" not in result["metrics"]
    assert 1.0 <= result["metrics"]["moe.held_pairs_per_token"]["value"] \
        <= 2.0
