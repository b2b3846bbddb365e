"""The output check fails what it must: the control (the reference in
bfloat16 put in the program's place) and each fault planted in the timed
path underneath a whole run of the harness: a round that returns its state
unchanged, half of every mini-batch left out, one validation loss altered
where the round program produces it, and the worst candidate selected.  All
at a small size on the CPU, against the cell's own limits."""
import json

import pytest

import _paths
from tiny import OPEN_CELLS, run_tiny, tiny_cell

import calibrate
import check

FAULT_LR = 0.05     # large enough that one round moves the tiny model
CELLS = [w["name"] for w in json.loads(
    (_paths.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    # the configuration's own learning rate, and enough steps that a round
    # moves the model by more than bfloat16 can hold
    cell = tiny_cell(name)
    cell.cfg.update(E=20, B=32, d_m=200, D_o=100, n_test=200)
    for seed in (3, 4, 2**31 + 5):
        got = calibrate.readings_for(cell, seed, ["control"], 3)
        assert not check.verdict(got["control"], cell.limits), got


def _unchanged(monkeypatch):
    from repro.core.runner import RoundRunner
    orig = RoundRunner._accept_vmap

    def accept(self, params, inputs, val):
        return params, orig(self, params, inputs, val)[1]

    monkeypatch.setattr(RoundRunner, "_accept_vmap", accept)


def _half_batch(monkeypatch):
    from repro.core import split
    orig = split._sl_exchange

    def exchange(module, gamma, phi, x, y, key, *a, **k):
        n = x.shape[0] // 2
        return orig(module, gamma, phi, x[:n], y[:n], key, *a, **k)

    monkeypatch.setattr(split, "_sl_exchange", exchange)


def _altered(monkeypatch):
    import repro.selection as sel
    orig = sel.pack_fetch

    def pack(vlosses, *rest):
        return orig(vlosses.at[0].multiply(1.01), *rest)

    monkeypatch.setattr(sel, "pack_fetch", pack)


def _wrong_pick(monkeypatch):
    import repro.selection as sel
    orig = sel.masked_first_accept

    def accept(scores, eligible, passed):
        return orig(-scores, eligible, passed)

    monkeypatch.setattr(sel, "masked_first_accept", accept)


@pytest.mark.parametrize("fault",
                         [_unchanged, _half_batch, _altered, _wrong_pick],
                         ids=["unchanged", "half_batch", "altered",
                              "wrong_pick"])
@pytest.mark.parametrize("name", CELLS + sorted(OPEN_CELLS))
def test_fault_in_the_timed_path_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    res = run_tiny(name, lr=FAULT_LR)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_tamper_kernel_returning_zero_goes_unseen(name, monkeypatch):
    """A gap of the check, kept as a record, not a goal.  The fused path
    hands the tamper-check kernel the validation activations as both of
    its operands, so its distance is 0 by construction, and neither
    committed mix tampers the hand-off: a kernel that always returns 0
    leaves the History as it was, and the run still reads correct.
    Comparing the kernel's distance needs the program to report it."""
    import jax.numpy as jnp
    from repro.kernels import ops
    calls = []

    def zero(ref, recv, **kw):
        calls.append(ref.shape)
        return jnp.zeros((), jnp.float32)

    monkeypatch.setattr(ops, "tamper_distance", zero)
    res = run_tiny(name, lr=FAULT_LR)
    assert calls, "the round program did not call the planted kernel"
    assert res["correct"], res["checks"]
