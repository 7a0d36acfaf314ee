"""The harness end to end on the CPU at 64 nodes, and the command's refusals.

These drive every phase of a run except the look for a chip: set-up, warm-up
on the cell's traffic, the window, the read-back and the check."""
import json
import os
import shutil
import subprocess
import sys

import pytest
import tiny

ROOT = tiny.harness.ROOT


CELLS = [w["name"] for w in tiny.harness.load_json("BENCHMARK.json")
         ["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(cell):
    r = tiny.run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {m["name"] for m in
                                 tiny.definition(cell)["end_to_end"]}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_program_side_metrics(cell):
    r = tiny.run(cell, trace=True)
    assert r["correct"], r["checks"]
    want = {m["name"] for m in tiny.definition(cell)["per_layer"]
            if m["source"] != "device_trace"}
    # the CPU has no device plane: device_trace metrics stay silent
    assert set(r["metrics"]) == want
    assert r["device"]["window_s"] > 0


def _run_command(cwd):
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "fleet1k.rollout",
         "--seed", str(2**31 + 17), "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_command_refuses_a_cpu():
    p = _run_command(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip().endswith("}")


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_command(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "traffic", f"{w['traffic']}.json"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(tiny.harness.load_reader(m["name"]))


def test_reader_of_a_split_metric_is_found_by_its_quantity():
    read = tiny.harness.load_reader("device_idle_share.deploy")
    assert read is not None
    with pytest.raises(tiny.harness.BenchError):
        tiny.harness.load_reader("no_such_metric.rollout")
