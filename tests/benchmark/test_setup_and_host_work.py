"""The readers of ISSUE 37: set-up by phase from the program's build
ledger (every cell), and a boundary's host work from the engine's
``llm.step`` / ``llm.fetch`` spans (the three engine cells that list
it). Each finds a positive number in a tiny traced run of its
cell and nothing, never 0, where there is nothing to read."""

import io
import json
import time

import pytest

from benchmark import engine_spans, run, setup_ledger
from mmlspark_tpu.obs import tracer

BENCH = run.load_bench()
CELLS = [w["name"] for w in BENCH["workloads"]]
SETUP = ("setup.trace_s", "setup.backend_s", "setup.programs_built")
ENGINE = {"xglm-1.7b.generate": "generate", "deepseek-v2.doc-qa": "dsv2",
         "minicpm-sala.long-doc-qa": "sala"}


@pytest.fixture(scope="module")
def traced():
    """One tiny traced run a cell: ``{cell: (result line, info line,
    programs the ledger held before the run, the window's boundaries as
    the readers saw them, the ring as they saw it)}``."""
    out = {}
    seen = {}
    real = engine_spans.boundaries

    def boundaries(ctx):
        seen["last"], seen["ring"] = real(ctx), tracer.recent()
        return seen["last"]

    engine_spans.boundaries = boundaries
    try:
        for cell in CELLS:
            seen.clear()
            built = setup_ledger.total("compiled", "loaded") or 0
            buf, err = io.StringIO(), io.StringIO()
            rc = run.run_cell(cell, 2_147_484_037, 0.2, True, tiny=True,
                              bench=BENCH, out=buf, err=err)
            assert rc == 0, err.getvalue()
            lines = buf.getvalue().strip().splitlines()
            out[cell] = (json.loads(lines[-1]),
                         json.loads(lines[-2])["info"], built,
                         seen.get("last"), seen.get("ring"))
    finally:
        engine_spans.boundaries = real
    return out


def test_the_benchmark_lists_the_six():
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    for name in SETUP:
        m = listed[name]
        assert "workloads" not in m           # every cell reports setup_s
        assert (m["moves"], m["layer"], m["source"], m["better"]) == (
            "setup_s", "programs", "program_counter", "lower")
    for cell, short in ENGINE.items():
        m = listed[f"{short}.host_ms"]
        assert m["workloads"] == [cell] and m["unit"] == "ms"
        assert (m["moves"], m["layer"], m["source"], m["better"]) == (
            "tokens_per_s", "host dispatch", "program_span", "lower")


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_its_set_up_by_phase(cell, traced):
    result, info, built_before, _, _ = traced[cell]
    assert result["correct"] is True
    metrics = result["metrics"]
    for name in SETUP:
        assert metrics[name]["value"] > 0
    assert metrics["setup.trace_s"]["unit"] == "s"
    assert metrics["setup.programs_built"]["unit"] == "programs"
    # the ledger is the process's: what this run built is what it holds
    # more than before, and is what the harness counted
    assert metrics["setup.programs_built"]["value"] - built_before \
        == info["compiles_in_setup"] + info["compiles_in_window"]


@pytest.mark.parametrize("cell", sorted(ENGINE))
def test_an_engine_cell_reports_its_host_work(cell, traced):
    result, info, _, boundaries, ring = traced[cell]
    host = result["metrics"][f"{ENGINE[cell]}.host_ms"]
    assert host["unit"] == "ms" and host["value"] > 0
    # every boundary of the window, and its fetch a part of it: on the
    # CPU a boundary fetches its own program
    assert len(boundaries) >= info["operations"]
    for step_s, fetch_s in boundaries:
        assert 0 < fetch_s < step_s
    # a fetch lies inside the boundary it is taken from
    by_id = {s.span_id: s for s in ring}
    fetches = [s for s in ring if s.name == "llm.fetch"]
    assert len(fetches) >= len(boundaries)
    for fetch in fetches:
        root = by_id[fetch.parent_id]
        if root.name == "llm.decode":
            root = by_id[root.parent_id]
        assert root.name == "llm.step"
        assert root.start_ns <= fetch.start_ns <= fetch.end_ns <= root.end_ns


def test_the_chat_cell_lists_none(traced):
    """``test_chat.py`` holds the chat cell's readers to an exact set:
    its ``q3n.host_ms`` waits for the benchmark issue that loosens it."""
    metrics = traced["qwen3-next-80b-a3b.chat"][0]["metrics"]
    assert not [n for n in metrics if n.endswith("host_ms")]


# -- hand-made rings ----------------------------------------------------------

def _boundary(tr, steps, fetch_s=0.0, late=False):
    """One boundary's tree as the engine leaves it: ``llm.step`` saying
    ``steps``, ``llm.decode`` beneath it and the fetch beneath that, which
    waits ``fetch_s``; ``late``: the boundary first brought home what
    still flew, under the root. Returns ``(root, its fetches)``."""
    fetches = []

    def fetch(parent):
        with tr.span("llm.fetch", parent=parent) as span:
            time.sleep(fetch_s)
        fetches.append(span)

    with tr.span("llm.step") as root:
        if late:
            fetch(root)
        with tr.span("llm.decode", parent=root) as decode:
            fetch(decode)
        root.set_attr("steps", steps)
    return root, fetches


def _stats(first, last):
    return [{"decode_steps_total": k} for k in range(first, last + 1)]


READERS = [f"{short}.host_ms" for short in ENGINE.values()]


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_windows_boundaries_alone(name, monkeypatch):
    """The warm-up before the window and the drain after it fetched ten
    times as long: a reader that took them in would read too much."""
    tr = type(tracer)()
    monkeypatch.setattr("mmlspark_tpu.obs.tracer", tr)
    for k in range(1, 6):
        _boundary(tr, k, fetch_s=0.005)                 # the warm-up
    window = [_boundary(tr, k, fetch_s=0.0005, late=k == 7)
              for k in range(6, 11)]
    for k in range(11, 14):
        _boundary(tr, k, fetch_s=0.005)                 # the drain
    ctx = {"stats": _stats(6, 10)}
    # each boundary with every fetch beneath it, wherever it hangs
    want = [(root.seconds, sum(f.seconds for f in fetches))
            for root, fetches in window]
    assert [len(fetches) for _, fetches in window] == [1, 2, 1, 1, 1]
    assert engine_spans.boundaries(ctx) == pytest.approx(want)
    got = run._load_module("layer_metrics", name).read(ctx)
    assert got == pytest.approx(1e3 * sorted(s - f for s, f in want)[2])
    assert got > 0


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_from_a_ring_that_kept_too_few(name,
                                                           monkeypatch):
    """A window of 300 boundaries of which a wrapped ring still holds
    under a hundred whole: a median over those would pass for the
    window's. Nothing, not 0."""
    monkeypatch.setattr("mmlspark_tpu.obs.tracing.RING_SIZE", 3 * 99 + 2)
    tr = type(tracer)()
    monkeypatch.setattr("mmlspark_tpu.obs.tracer", tr)
    reader = run._load_module("layer_metrics", name)
    for k in range(1, 301):
        _boundary(tr, k)
    ctx = {"stats": _stats(1, 300)}
    # 99 trees and two spans of the hundredth: that one is not whole
    assert len(tr.recent()) == 3 * 99 + 2
    assert engine_spans.boundaries(ctx) == []
    assert reader.read(ctx) is None
    # the same ring against a window of its last 99 boundaries: all there
    assert len(engine_spans.boundaries({"stats": _stats(202, 300)})) == 99
    assert reader.read({"stats": _stats(202, 300)}) is not None
    # one tree more in the ring and a hundred are whole: enough
    monkeypatch.setattr("mmlspark_tpu.obs.tracing.RING_SIZE", 3 * 100 + 1)
    tr = type(tracer)()
    monkeypatch.setattr("mmlspark_tpu.obs.tracer", tr)
    for k in range(1, 301):
        _boundary(tr, k)
    assert len(engine_spans.boundaries(ctx)) == 100
    assert reader.read(ctx) is not None


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_where_there_is_nothing_to_read(name,
                                                             monkeypatch):
    reader = run._load_module("layer_metrics", name)
    tr = type(tracer)()
    monkeypatch.setattr("mmlspark_tpu.obs.tracer", tr)
    assert reader.read({"stats": _stats(1, 5)}) is None     # an empty ring
    # the parent commit's spans say no ``steps``
    for _ in range(5):
        with tr.span("llm.step") as root:
            with tr.span("llm.decode", parent=root) as decode:
                with tr.span("llm.fetch", parent=decode):
                    pass
    assert reader.read({"stats": _stats(1, 5)}) is None
    # a driver that recorded no boundary, or no such counter
    _boundary(tr, 1)
    assert reader.read({"stats": []}) is None
    assert reader.read({"stats": [{"seconds": 0.1}]}) is None


@pytest.mark.parametrize("name", SETUP)
def test_setup_reader_reads_nothing_without_a_ledger(name, monkeypatch):
    """The parent commit's tracker keeps no ledger; an empty one sums to
    nothing, not 0."""
    from mmlspark_tpu.obs import profile
    reader = run._load_module("layer_metrics", name)
    monkeypatch.setattr(profile, "compile_tracker", object())
    assert reader.read({}) is None
    monkeypatch.setattr(profile, "compile_tracker",
                        profile.CompileTracker(registry=type(
                            profile._registry)()))
    assert reader.read({}) is None
