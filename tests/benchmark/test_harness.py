"""The harness end to end at tiny sizes on the CPU, through ``run.py``'s
own code path, and the data-driven layout: every name in
``BENCHMARK.json`` resolves to a file."""

import io
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import run  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    COMMITTED = json.load(_f)
# the cells of BENCHMARK.json and the one built, measured and kept out
# (benchmark/proposed/): the harness has to carry both
BENCH = run.load_bench("higgs.fit")
CELLS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]


def run_tiny(cell, trace=False, seed=2_147_483_777, **override):
    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell(cell, seed, 0.2, trace, tiny=True, bench=BENCH,
                      params_override=override or None, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), lines, err.getvalue()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_tiny_and_prints_the_contract_line(cell):
    rc, result, lines, err = run_tiny(cell)
    assert rc == 0
    assert list(result) == RESULT_KEYS               # checks come last
    assert result["correct"] is True, err
    assert result["attempted"] >= 1 and result["failed"] == 0
    end_to_end, _ = run.cell_metrics(BENCH, cell)
    assert set(result["metrics"]) == {m["name"] for m in end_to_end}
    for m in end_to_end:
        got = result["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    for chk in result["checks"].values():
        assert chk["value"] <= chk["limit"]
    # each number compared stands beside its limit on standard error too
    assert err.count("check ") == len(result["checks"])
    info = json.loads(lines[-2])["info"]
    assert info["platform"] == result["device"]["platform"]
    assert info["compiles_in_window"] == 0 and info["steady"]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_only_what_its_readers_find(cell):
    rc, result, _, err = run_tiny(cell, trace=True)
    assert rc == 0 and result["correct"] is True, err
    _, per_layer = run.cell_metrics(BENCH, cell)
    names = {m["name"] for m in per_layer}
    assert set(result["metrics"]) <= names
    # a CPU trace has no device plane: no share of a peak, a roofline or
    # the device's time may be reported from it, not even as 0
    for m in per_layer:
        if m["unit"] == "%":
            assert m["name"] not in result["metrics"]
    if any(m["unit"] != "%" for m in per_layer):
        assert result["metrics"], "host-side readers still read counters"


def test_no_chip_means_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", COMMITTED["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def _exists(*parts):
    return os.path.isfile(os.path.join(ROOT, *parts))


def test_committed_cells_are_a_subset_with_their_configs():
    names = {w["name"] for w in COMMITTED["workloads"]}
    assert names and names <= set(CELLS)
    used = {w["config"] for w in COMMITTED["workloads"]}
    assert used == {c["name"] for c in COMMITTED["configs"]}
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in COMMITTED["end_to_end"])


def test_every_name_resolves_to_a_file():
    paths = BENCH["paths"]
    for cfg in BENCH["configs"]:
        assert _exists(cfg["file"]), cfg["file"]
        assert any(cfg["file"].startswith(p + "/") for p in paths)
        with open(os.path.join(ROOT, cfg["file"])) as f:
            body = json.load(f)
        assert body["source"] == cfg["source"]
        assert body["reduced"] == cfg["reduced"]
    config_names = {c["name"] for c in BENCH["configs"]}
    for cell in BENCH["workloads"]:
        assert cell["config"] in config_names
        assert _exists("benchmark", "workloads", cell["name"] + ".json")
        with open(os.path.join(ROOT, "benchmark", "workloads",
                               cell["name"] + ".json")) as f:
            wl = json.load(f)
        assert _exists("benchmark", "drivers", wl["driver"] + ".py")
        end_to_end, per_layer = run.cell_metrics(BENCH, cell["name"])
        names = {m["name"] for m in end_to_end}
        assert wl["rate_metric"] in names and "setup_s" in names
        assert per_layer, "every cell reports a per-layer metric"
        driver = run._load_module("drivers", wl["driver"])
        for fn in ("setup", "warm", "step", "outputs_for_check", "check"):
            assert callable(getattr(driver, fn))
    for m in BENCH["per_layer"]:
        assert _exists("benchmark", "layer_metrics", m["name"] + ".py")
        assert callable(run._load_module("layer_metrics", m["name"]).read)


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)
    # one layer name, letter for letter, per layer
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert layers <= {"entry points", "host dispatch", "programs", "kernels",
                      "device"}
