"""The benchmark's yardstick, pinned: the ``cnn`` kind's data and the
reference's records of two rounds (float32, and the bfloat16 control) at a
tiny size of each paper cell, at one seed, against values read from the
benchmark before the model moved into ``kinds/cnn.py``.  And the
reference's activation attack against the program's own."""
import hashlib

import numpy as np
import pytest

import _paths  # noqa: F401
from tiny import tiny_cell

import gen
import reference

SEED = 2**31 + 11
LR = 0.05           # large enough that two rounds move the tiny model
FIELDS = ("x", "y", "x0", "y0", "x_test", "y_test")

DATA_SHA256 = {
    "mnist_t2":
        "7864b570fa5b9d789b926e4fc9d3ac11c189d94374fbdcdeff98d081af481987",
    "cifar10_t2":
        "90f16edbc2589c46c614889f16cac88f339b176c8f47c40368839c004db4cb5f",
}
RECORDS = {
    ("mnist_t2", "float32"): [
        dict(round=0,
             val_losses=[
                 2.2923550605773926, 2.2930538654327393, 2.277357339859009,
                 2.2979917526245117],
             train_losses=[
                 2.319697618484497, 2.325420618057251, 2.3048136234283447,
                 2.3081291913986206],
             selected=2, accepted=True, detections=0,
             select_excess=0.0, test_acc=0.2),
        dict(round=1,
             val_losses=[
                 2.2667367458343506, 2.2479147911071777, 2.2499043941497803,
                 2.2822928428649902],
             train_losses=[
                 2.2885289192199707, 2.267729640007019, 2.274761199951172,
                 2.3168879747390747],
             selected=1, accepted=True, detections=0,
             select_excess=0.0, test_acc=0.25),
    ],
    ("mnist_t2", "bfloat16"): [
        dict(round=0,
             val_losses=[
                 2.2923614978790283, 2.293355941772461, 2.2778308391571045,
                 2.297853708267212],
             train_losses=[
                 2.319623589515686, 2.3255213499069214, 2.3050915002822876,
                 2.308274507522583],
             selected=2, accepted=True, detections=0,
             select_excess=0.0, test_acc=0.2),
        dict(round=1,
             val_losses=[
                 2.2672677040100098, 2.2495923042297363, 2.251192092895508,
                 2.281404733657837],
             train_losses=[
                 2.288954734802246, 2.269315242767334, 2.276121973991394,
                 2.317177653312683],
             selected=1, accepted=True, detections=0,
             select_excess=0.0, test_acc=0.25),
    ],
    ("cifar10_t2", "float32"): [
        dict(round=0,
             val_losses=[
                 2.300600528717041, 2.286832332611084, 2.294212579727173,
                 2.2885947227478027, 2.2738966941833496],
             train_losses=[
                 2.2949156761169434, 2.2974231243133545, 2.2998749017715454,
                 2.3054200410842896, 2.2918976545333862],
             selected=4, accepted=True, detections=0,
             select_excess=0.0, test_acc=0.15),
        dict(round=1,
             val_losses=[
                 2.2723324298858643, 2.2773051261901855, 2.2835440635681152,
                 2.2690069675445557, 2.268826961517334],
             train_losses=[
                 2.277098298072815, 2.306856870651245, 2.308024287223816,
                 2.2661616802215576, 2.290697693824768],
             selected=4, accepted=True, detections=0,
             select_excess=0.0, test_acc=0.24),
    ],
    ("cifar10_t2", "bfloat16"): [
        dict(round=0,
             val_losses=[
                 2.298891305923462, 2.288121223449707, 2.2953073978424072,
                 2.288975238800049, 2.2757997512817383],
             train_losses=[
                 2.294844388961792, 2.297735095024109, 2.3015780448913574,
                 2.3061254024505615, 2.2923296689987183],
             selected=4, accepted=True, detections=0,
             select_excess=0.0, test_acc=0.16),
        dict(round=1,
             val_losses=[
                 2.2746002674102783, 2.279513359069824, 2.2852118015289307,
                 2.271392345428467, 2.2726387977600098],
             train_losses=[
                 2.280233860015869, 2.3070242404937744, 2.3091238737106323,
                 2.2670435905456543, 2.292032480239868],
             selected=3, accepted=True, detections=0,
             select_excess=0.0, test_acc=0.14),
    ],
}


def _digest(data) -> str:
    h = hashlib.sha256()
    for f in FIELDS:
        a = getattr(data, f)
        h.update(f"{f}{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(DATA_SHA256))
def test_data_is_pinned(name):
    cell = tiny_cell(f"{name}.paper", lr=LR)
    assert _digest(gen.make_data(cell.kind, cell.cfg, SEED)) \
        == DATA_SHA256[name]


@pytest.mark.parametrize("name,dtype", sorted(RECORDS),
                         ids=["-".join(k) for k in sorted(RECORDS)])
def test_reference_records_are_pinned(name, dtype):
    cell = tiny_cell(f"{name}.paper", lr=LR)
    data = gen.make_data(cell.kind, cell.cfg, SEED)
    (job,) = gen.make_jobs(cell.cfg, cell.traffic, SEED)
    got = reference.run(cell.kind, cell.cfg, data, job, 2, 1, dtype=dtype)
    assert got == RECORDS[name, dtype]


@pytest.mark.parametrize("shape", [(64, 32), (4, 16, 24)],
                         ids=["batch-width", "batch-seq-width"])
def test_noise_blend_is_the_programs(shape):
    """The same key gives the program's activation blend bit for bit, for a
    CNN's cut activation and for a sequence model's (B, S, d)."""
    import jax
    from repro.adversary.families import _noise_blend
    acts = jax.random.normal(jax.random.PRNGKey(7), shape)
    key = jax.random.PRNGKey(SEED % 2**32)
    ref = reference.noise_blend(acts, key, reference.ACT_KEEP)
    prog = _noise_blend(acts, key, reference.ACT_KEEP)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(prog))
