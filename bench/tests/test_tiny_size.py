"""Every CPU test runs a cell at the tiny size of ``tiny.shrink``, which
cuts the model through its kind's ``tiny_model`` and refuses a tiny round
over ``tiny.MAX_TINY_ROUND_FLOPS``: no cell may bring a model at published
widths into the CPU tests (each cell's own tests go through ``shrink``).  A
decoder LM kind that exists only in a temporary root (``lm_kind.py``),
declared far too large for the CPU, runs a tiny cell end to end through
the harness."""
import json
import math
import time

import pytest

import _paths
from tiny import MAX_TINY_ROUND_FLOPS, round_flops, shrink

import harness

SPEC = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
ATTACKS = {"label_flip": "paper_label_flip", "activation": "paper_activation"}

# a made-up decoder LM, large enough that the CPU cannot run a round of it
LM_CONFIG = {
    "name": "decoder_lm",
    "model": {"kind": "decoder", "d_model": 2048, "n_heads": 16,
              "d_ff": 8192, "seq_len": 2048, "vocab": 12800},
    "M": 4, "N": 1, "E": 4, "B": 4, "d_m": 64, "D_o": 32, "n_test": 32,
    "lr": 0.1, "eval_batch": 500, "tamper_tol": 0.0001,
}
LM_LIMITS = {"val_loss_rel": 1e-5, "train_loss_rel": 1e-5,
             "select_excess": 1e-5, "cascade_diff": 0, "test_acc_gap": 1}


def lm_root(root):
    """A root that holds the benchmark's entries plus the decoder kind, its
    configuration, both paper traffic mixes, limits and one cell for each."""
    bench = root / "bench"
    for sub in ("kinds", "configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True)
    (bench / "kinds" / "decoder.py").write_text(
        (_paths.BENCH / "tests" / "lm_kind.py").read_text())
    (bench / "configs" / "decoder_lm.json").write_text(json.dumps(LM_CONFIG))
    spec = json.loads(json.dumps(SPEC))
    for attack, traffic in ATTACKS.items():
        (bench / "traffic" / f"{traffic}.json").write_text(
            (_paths.BENCH / "traffic" / f"{traffic}.json").read_text())
        (bench / "limits" / f"decoder_lm.{attack}.json").write_text(
            json.dumps({"limits": LM_LIMITS}))
        spec["workloads"].append({"name": f"decoder_lm.{attack}",
                                  "config": "decoder_lm", "traffic": traffic,
                                  "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_a_model_left_at_published_widths_is_refused(tmp_path, monkeypatch):
    cell = harness.load_cell("decoder_lm.label_flip", root=lm_root(tmp_path))
    monkeypatch.setattr(cell.kind, "tiny_model", lambda model: model)
    with pytest.raises(ValueError, match="tiny_model"):
        shrink(cell)
    assert round_flops(cell) > 1000 * MAX_TINY_ROUND_FLOPS


@pytest.mark.parametrize("attack", sorted(ATTACKS))
def test_a_published_width_lm_runs_tiny(attack, tmp_path, monkeypatch):
    cell = harness.load_cell(f"decoder_lm.{attack}", root=lm_root(tmp_path))
    kind = cell.kind
    seen = {}

    def build_module(cfg):
        seen["module"] = module = kind_build(cfg)
        return module

    def eval_units(data):
        seen["units"] = units = kind_units(data)
        return units

    kind_build, kind_units = kind.build_module, kind.eval_units
    monkeypatch.setattr(kind, "build_module", build_module)
    monkeypatch.setattr(kind, "eval_units", eval_units)
    shrink(cell)
    res = harness.run_cell(cell, 2**31 + 23, 1.0, False,
                           t_process=time.perf_counter(), require_chip=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["compiles_in_window"]["value"] == 0

    # the module the harness built is the tiny model: embeddings, one
    # layer on each side of the cut, the final norm and the head
    import jax
    d, ff, s, v = 64, 256, 16, 128
    layer = 2 * d + 4 * d * d + 2 * d * ff
    shapes = jax.eval_shape(seen["module"].init, jax.random.PRNGKey(0))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) \
        == v * d + s * d + 2 * layer + d + d * v
    # the eval counts tokens, and the test accuracy is compared in them
    assert seen["units"] == cell.cfg["n_test"] * s
    assert "test_acc_gap" in res["checks"]
