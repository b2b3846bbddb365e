"""The operation counts against counts made by hand."""
import json

import _paths  # noqa: F401

import flops
import harness

CNN = harness.load_kind("cnn")
CFG = {name: json.loads((_paths.BENCH / "configs" / f"{name}.json").read_text())
       for name in ("mnist_t2", "cifar10_t2")}


def test_mnist_layer_macs_by_hand():
    client, ap = CNN.layer_macs(CFG["mnist_t2"]["model"])
    # conv 5x5 1->2 on 28x28 (pad 2), pool, conv 5x5 2->4 on 14x14, pool,
    # FC 7*7*4 -> 32 (the cut), FC 32 -> 10
    assert client == [28 * 28 * 2 * 25 * 1, 14 * 14 * 4 * 25 * 2, 196 * 32]
    assert ap == [32 * 10]


def test_cifar_layer_macs_by_hand():
    client, ap = CNN.layer_macs(CFG["cifar10_t2"]["model"])
    assert client == [32 * 32 * 32 * 9 * 3, 16 * 16 * 64 * 9 * 32,
                      8 * 8 * 128 * 9 * 64, 4 * 4 * 128 * 256]
    assert ap == [256 * 128, 128 * 64, 64 * 10]
    assert CNN.forward_flops(CFG["cifar10_t2"]["model"])[1] == 21_775_616


def test_round_totals():
    # Table II rounds with the eval and one handoff re-check: 34.7 GFLOP
    # (MNIST) and 3.89 TFLOP (CIFAR-10)
    mn = flops.round_flops(CNN, CFG["mnist_t2"], eval_round=True,
                           recheck_visits=1)
    cf = flops.round_flops(CNN, CFG["cifar10_t2"], eval_round=True,
                           recheck_visits=1)
    assert round(mn / 1e9, 1) == 34.7
    assert round(cf / 1e12, 2) == 3.89
    # training alone: M*E*B samples, 3 forwards each
    assert flops.round_flops(CNN, CFG["cifar10_t2"], eval_round=False,
                             recheck_visits=0) == (
        20 * 40 * 64 * 3 * 21_775_616 + 5 * 3000 * 21_775_616)

