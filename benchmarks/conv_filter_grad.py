"""Conv filter gradient: XLA's own against the flat patch rows.

For each conv layer of the paper's two CNNs, at its benchmark cell's shapes
(B=64, vmapped over the R clusters of ``bench/configs``), time the filter
gradient fused with the SGD update ``w - lr*dw``, both ways:

* ``xla``: the VJP of ``conv_general_dilated`` with respect to the filter;
* ``patch``: ``models/cnn.py::_conv_patch_dw``, whose filter gradient sums
  the products of flat, lane-dense patch rows and dy on the vector unit.

Each way runs ``STEPS`` updates in one ``lax.scan``, fenced with
``block_until_ready``; the input moves with the step counter, so XLA cannot
hoist the gradient out of the loop.  ``models/cnn.py::takes_patch_filter_grad``
is set from what this prints on the chip; on the CPU the times say nothing
about the TPU.  Run: ``PYTHONPATH=src python -m benchmarks.conv_filter_grad``.
"""
from __future__ import annotations

import json
import statistics
import time

import jax
import jax.numpy as jnp

from repro.models import cnn

STEPS = 200
REPEATS = 5
B = 64
# name, R, H (= W), k, C_in, C_out, padding
LAYERS = (("mnist.conv1", 4, 28, 5, 1, 2, 2),
          ("mnist.conv2", 4, 14, 5, 2, 4, 2),
          ("cifar10.conv1", 5, 32, 3, 3, 32, 1),
          ("cifar10.conv2", 5, 16, 3, 32, 64, 1),
          ("cifar10.conv3", 5, 8, 3, 64, 128, 1))
WAYS = {"xla": cnn._conv_xla, "patch": cnn._conv_patch_dw}


def _updates(conv, padding, steps):
    def dw(x, w, dy):
        _, vjp_w = jax.vjp(lambda w_: conv(x, w_, padding), w)
        return vjp_w(dy)[0]

    def loop(x, w, dy):
        def body(w, i):
            return w - 1e-3 * jax.vmap(dw)(x + i * 1e-3, w, dy), None
        return jax.lax.scan(body, w, jnp.arange(steps, dtype=x.dtype))[0]

    return jax.jit(loop)


def measure(steps: int = STEPS, repeats: int = REPEATS):
    rows = []
    for name, r, h, k, c_in, c_out, pad in LAYERS:
        kx, kw, kd = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(kx, (r, B, h, h, c_in))
        w = jax.random.normal(kw, (r, k, k, c_in, c_out))
        dy = jax.random.normal(kd, (r, B, h, h, c_out))
        row = {"layer": name, "R": r, "k": k, "c_in": c_in, "c_out": c_out,
               "rule_takes_patch": cnn.takes_patch_filter_grad(k, c_in, c_out)}
        for way, conv in WAYS.items():
            fn = _updates(conv, pad, steps)
            jax.block_until_ready(fn(x, w, dy))          # compile + warm
            times = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x, w, dy))
                times.append((time.perf_counter() - t0) / steps * 1e6)
            row[f"{way}_us"] = statistics.median(times)
            row[f"{way}_us_all"] = times
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


if __name__ == "__main__":
    from .common import main
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    main(measure)
