"""Compile-only guards for the TPU v5e, at zero chip time.

The main path's Pallas kernels and the fused round program are compiled (not
run) for a described ``v5e:2x2`` topology at the paper's real widths, with
the kernels lowered to Mosaic (``interpret=False``).  This catches what the
interpret-mode oracle tests cannot: block shapes the TPU tiling refuses,
scalar stores to vector memory, layouts XLA and Mosaic disagree on.

The topology is described inside a module fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# MNIST Table II (benchmarks/common.py): M=12, N=3 (R=4), E=79, B=64, D_o=3000
M, R, E, B, D_O = 12, 4, 79, 64, 3000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs outside
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_compile(one_chip, monkeypatch):
    """Compile ``fn`` at ``shapes`` for one described v5e chip and return
    the compiled text.  The persistent cache is off around the compile (an
    entry written for a described chip cannot be read back without one),
    and the kernels' default-interpret rule is steered to Mosaic, since
    ``jax.default_backend()`` still reports the CPU."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.kernels import ops
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()          # no CPU-interpreted trace may be reused

    def compile_(fn, *shapes):
        specs = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            shapes)
        return jax.jit(fn).lower(*specs).compile().as_text()

    yield compile_
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


@pytest.mark.parametrize("n,d", [(3000, 32), (3000, 256)])
def test_tamper_check_compiles_for_v5e(tpu_compile, n, d):
    """The kernel alone, and vmapped over the R candidates as the fused
    acceptance cascade calls it."""
    from repro.kernels import ops
    from repro.kernels.tamper_check import tamper_check_sums

    txt = tpu_compile(lambda a, b: tamper_check_sums(a, b), _f32(n, d),
                      _f32(n, d))
    assert "tpu_custom_call" in txt
    txt = tpu_compile(jax.vmap(ops.tamper_distance), _f32(R + 1, n, d),
                      _f32(R + 1, n, d))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("n,d", [(64, 256), (3000, 256)])
@pytest.mark.parametrize("stats", [False, True])
def test_quant_exchange_compiles_for_v5e(tpu_compile, n, d, stats):
    from repro.kernels.quant_exchange import quant_dequant, quant_dequant_stats

    kernel = quant_dequant_stats if stats else quant_dequant
    txt = tpu_compile(lambda x: kernel(x, "int8"), _f32(n, d))
    assert "tpu_custom_call" in txt


def test_accept_program_compiles_for_v5e(tpu_compile):
    """The vmap ``accept`` round program (R clusters x the M/R-client chain
    x E steps, shared-set validation, tamper-check cascade) at MNIST Table
    II shapes."""
    from repro.adversary import resolve_threat_model
    from repro.core import HONEST, ProtocolConfig, from_cnn
    from repro.core.clustering import make_clusters
    from repro.core.engine import assemble_round
    from repro.core.runner import protocol_accept_runner
    from repro.data import build_image_task
    from repro.selection import resolve_policy

    data, cfg = build_image_task("mnist", m_clients=M, d_m=B, d_o=8,
                                 n_test=8, seed=0)
    module = from_cnn(cfg)
    pcfg = ProtocolConfig(M=M, N=R - 1, E=1, B=B, lr=1e-3)
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    _, (xs, ys, avec, keys) = assemble_round(
        rng, key, data, make_clusters(rng, M, R), pcfg,
        resolve_threat_model(None, HONEST, None), 0)
    # the payload at E=1, widened to Table II's E steps per client turn
    xs = jax.ShapeDtypeStruct(xs.shape[:2] + (E,) + xs.shape[3:], xs.dtype)
    ys = jax.ShapeDtypeStruct(ys.shape[:2] + (E,) + ys.shape[3:], ys.dtype)
    theta = jax.eval_shape(module.init, key)
    val = (_f32(D_O, *data.x0.shape[1:]),
           jax.ShapeDtypeStruct((D_O,), data.y0.dtype))
    runner = protocol_accept_runner(module, pcfg.lr, "vmap",
                                    resolve_policy("argmin"), True,
                                    pcfg.tamper_tol)
    txt = tpu_compile(runner.audit_body("accept"), theta,
                      (xs, ys, avec, keys), val)
    assert "tpu_custom_call" in txt
