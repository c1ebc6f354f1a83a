"""The per-layer readers of the program's spans (ISSUE 26): each finds a
positive number in a tiny traced run of its cell, reads only the window's
operations, and reads nothing where there is no such span or the ring
no longer holds the window whole."""

import io
import json

import pytest

from benchmark import run, span_metrics
from mmlspark_tpu.obs import tracer

READERS = {
    "featurize.stage_ms": ("resnet50.featurize", "tpu_model.transform",
                           "tpu_model.stage"),
    "featurize.put_ms": ("resnet50.featurize", "tpu_model.transform",
                         "tpu_model.put"),
    "featurize.launch_ms": ("resnet50.featurize", "tpu_model.transform",
                            "tpu_model.launch"),
    "featurize.host_work_ms": ("resnet50.featurize", "tpu_model.transform",
                               None),
    "finetune.put_ms": ("resnet50.finetune", "train.epoch", "train.put"),
    "finetune.launch_ms": ("resnet50.finetune", "train.epoch",
                           "train.launch"),
    "finetune.fetch_ms": ("resnet50.finetune", "train.epoch",
                          "train.fetch"),
}


@pytest.fixture(scope="module")
def traced():
    """One tiny traced run a cell: ``{cell: (result line, info line)}``."""
    out = {}
    for cell in sorted({cell for cell, _, _ in READERS.values()}):
        buf, err = io.StringIO(), io.StringIO()
        rc = run.run_cell(cell, 2_147_483_999, 0.2, True, tiny=True,
                          out=buf, err=err)
        assert rc == 0, err.getvalue()
        lines = buf.getvalue().strip().splitlines()
        out[cell] = (json.loads(lines[-1]), json.loads(lines[-2])["info"])
    return out


def test_the_benchmark_lists_every_reader_for_its_cell():
    bench = run.load_bench()
    listed = {m["name"]: m for m in bench["per_layer"]
              if m["source"] == "program_span"}
    assert set(listed) == set(READERS)
    for name, (cell, _, _) in READERS.items():
        m = listed[name]
        assert m["workloads"] == [cell]
        assert (m["unit"], m["better"], m["layer"]) == \
            ("ms", "lower", "host dispatch")


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reports_a_positive_number_in_a_tiny_traced_run(name, traced):
    result, _ = traced[READERS[name][0]]
    assert result["correct"] is True
    got = result["metrics"][name]
    assert got["unit"] == "ms" and got["value"] > 0


def test_stage_put_launch_add_up_to_dispatch_ms(traced):
    metrics = traced["resnet50.featurize"][0]["metrics"]
    parts = sum(metrics[f"featurize.{p}_ms"]["value"]
                for p in ("stage", "put", "launch"))
    assert parts == pytest.approx(metrics["featurize.dispatch_ms"]["value"],
                                  rel=0.02)
    # a transform's host work holds at least its three minibatches'
    # stage, put and launch (tiny: 20 rows in minibatches of 8)
    assert metrics["featurize.host_work_ms"]["value"] > 3 * parts


def _tree(tr, root_name, child_name, child_s, kids=2):
    with tr.span(root_name) as root:
        for _ in range(kids):
            tr.emit_span(child_name, parent=root, seconds=child_s)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_only_the_windows_operations(name, monkeypatch):
    """Warm-ups left spans ten times as long before the window's: a
    reader that took them in would read several times too much."""
    _, root_name, child_name = READERS[name]
    tr = type(tracer)()
    monkeypatch.setattr("mmlspark_tpu.obs.tracer", tr)
    child = child_name or "tpu_model.drain"
    for _ in range(3):                              # the warm-ups
        _tree(tr, root_name, child, 0.050)
    for _ in range(2):                              # the window
        _tree(tr, root_name, child, 0.005)
    reader = run._load_module("layer_metrics", name)
    got = reader.read({"operations": 2})
    if child_name is None:
        # the root less its drains: what is left is the loop's own time,
        # far under the 100 ms of drains a warm-up's tree holds
        roots = tr.recent(name=root_name, last=2)
        want = 1e3 * sum(r.seconds - 0.010 for r in roots) / 2
        assert got == pytest.approx(want, abs=1e-6)
    else:
        assert got == pytest.approx(5.0, rel=1e-6)
    trees = span_metrics.window_trees({"operations": 2}, root_name)
    assert [len(kids) for _, kids in trees] == [2, 2]


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_once_the_ring_dropped_part_of_the_window(
        name, monkeypatch):
    """A mean over what a wrapped ring still holds would pass for the
    window's: fewer roots than operations, or a root whose first
    children are gone, is nothing to read."""
    _, root_name, child_name = READERS[name]
    child = child_name or "tpu_model.drain"
    monkeypatch.setattr("mmlspark_tpu.obs.tracing.RING_SIZE", 8)
    tr = type(tracer)()
    monkeypatch.setattr("mmlspark_tpu.obs.tracer", tr)
    reader = run._load_module("layer_metrics", name)
    for _ in range(2):
        _tree(tr, root_name, child, 0.005, kids=2)      # 6 spans of 8
    assert reader.read({"operations": 2}) is not None
    assert reader.read({"operations": 3}) is None       # a root short
    _tree(tr, root_name, child, 0.005, kids=3)          # 10: two dropped
    assert len(tr.recent()) == 8
    assert reader.read({"operations": 2}) is not None   # both still whole
    assert reader.read({"operations": 3}) is None       # the oldest is cut
    _tree(tr, root_name, child, 0.005, kids=8)          # its first child gone
    assert reader.read({"operations": 1}) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_where_there_is_no_span(name, monkeypatch):
    reader = run._load_module("layer_metrics", name)
    empty = type(tracer)()
    monkeypatch.setattr("mmlspark_tpu.obs.tracer", empty)
    assert reader.read({"operations": 5}) is None
    # spans of another loop are not this reader's
    _tree(empty, "some.other", "some.child", 0.001)
    assert reader.read({"operations": 5}) is None
    # a program from before the ring (the parent commit) has no
    # ``recent``: nothing to read, and no error
    monkeypatch.setattr("mmlspark_tpu.obs.tracer", object())
    assert reader.read({"operations": 5}) is None
