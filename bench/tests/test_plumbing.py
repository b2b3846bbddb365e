"""Every cell's files are found by name, and the harness's own plumbing runs
end to end at a tiny size on the CPU: set-up, the timed window with no
compile in it, the metrics, and the check against the reference."""
import json
import subprocess
import sys
import time

import pytest

import _paths
from tiny import OPEN_CELLS, run_tiny, shrink

import harness

SPEC = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_every_entry_has_its_files():
    for c in SPEC["configs"]:
        assert (_paths.ROOT / c["file"]).is_file()
    for w in SPEC["workloads"]:
        assert (_paths.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (_paths.BENCH / "limits" / f"{w['name']}.json").is_file()
        cell = harness.load_cell(w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"rounds_per_s",
                                                        "setup_s"}
    for m in SPEC["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


@pytest.mark.parametrize("name", CELLS + sorted(OPEN_CELLS))
def test_cell_runs_tiny(name):
    res = run_tiny(name)
    assert res["correct"], res["checks"]
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert {"rounds_per_s", "setup_s"} <= set(res["metrics"])
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]


def test_traced_run_reads_the_span_metrics():
    res = run_tiny("mnist_t2.paper", trace=True)
    assert res["correct"]
    for name in ("driver_ms", "assemble_wait_ms", "assemble_ms", "step_ms",
                 "fetch_select_ms", "eval_ms"):
        assert name in res["metrics"], name
    assert "window_s" in res["device"]


def test_a_new_cell_is_files_and_an_entry(tmp_path):
    root = tmp_path
    bench = root / "bench"
    for sub in ("kinds", "configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True)
    (bench / "kinds" / "cnn.py").write_text(
        (_paths.BENCH / "kinds" / "cnn.py").read_text())
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "mnist_t2.honest", "config": "mnist_t2",
                              "traffic": "honest", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "configs" / "mnist_t2.json").write_text(
        (_paths.BENCH / "configs" / "mnist_t2.json").read_text())
    tr = json.loads((_paths.BENCH / "traffic" / "paper_label_flip.json")
                    .read_text())
    tr["jobs"] = [{"seed_offset": 0, "malicious": "none", "attack": "none"}]
    (bench / "traffic" / "honest.json").write_text(json.dumps(tr))
    (bench / "limits" / "mnist_t2.honest.json").write_text(
        (_paths.BENCH / "limits" / "mnist_t2.paper.json").read_text())
    cell = harness.load_cell("mnist_t2.honest", root=root)
    assert cell.traffic["jobs"][0]["attack"] == "none"
    assert [m["name"] for m in cell.end_to_end] == ["rounds_per_s",
                                                    "setup_s"]


def test_a_new_kind_is_one_file(tmp_path):
    """A model kind that exists only in a temporary root, with its config,
    traffic, limits and entry there, runs a tiny cell end to end, with no
    edit to the benchmark's own files."""
    root = tmp_path
    bench = root / "bench"
    for sub in ("kinds", "configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True)
    (bench / "kinds" / "cnn_copy.py").write_text(
        (_paths.BENCH / "kinds" / "cnn.py").read_text())
    cfg = json.loads((_paths.BENCH / "configs" / "mnist_t2.json").read_text())
    cfg["model"]["kind"] = "cnn_copy"
    (bench / "configs" / "mnist_copy.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "flip.json").write_text(
        (_paths.BENCH / "traffic" / "paper_label_flip.json").read_text())
    (bench / "limits" / "mnist_copy.flip.json").write_text(
        (_paths.BENCH / "limits" / "mnist_t2.paper.json").read_text())
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "mnist_copy.flip",
                              "config": "mnist_copy", "traffic": "flip",
                              "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = shrink(harness.load_cell("mnist_copy.flip", root=root))
    assert cell.kind.__file__ == str(
        (bench / "kinds" / "cnn_copy.py").resolve())
    assert cell.kind is not harness.load_kind("cnn")
    seed = 2**31 + 11
    res = harness.run_cell(cell, seed, 1.0, False,
                           t_process=time.perf_counter(), require_chip=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert res["checks"] == run_tiny("mnist_t2.paper", seed)["checks"]


def test_no_tpu_exits_before_any_phase():
    proc = subprocess.run(
        [sys.executable, str(_paths.BENCH / "run.py"), "--workload",
         "mnist_t2.paper", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""
