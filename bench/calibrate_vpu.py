"""Measure the VPU's float32 element-op rate of one chip, once.

    python3 bench/calibrate_vpu.py

Runs a Pallas kernel of independent compare/select/add chains on
(8, 128) float32 tiles held in registers and prints one JSON line with the
highest rate reached over a few chain counts.  Each step of a chain is
counted as four element ops (a compare, an add, a subtract and a select),
the most the step can issue, so the rate errs high: it is an upper bound
for any kernel's VPU work, which is what ``peaks.json`` needs.  The result
is copied into ``peaks.json`` by hand; no benchmark run measures it again.
"""
from __future__ import annotations

import datetime
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

OPS_PER_STEP = 4
UNROLL = 8


def _kernel(x_ref, o_ref, *, chains: int, steps: int):
    b = jnp.full((8, 128), 0.5, jnp.float32)
    s = jnp.full((8, 128), 1e-3, jnp.float32)
    acc = tuple(x_ref[8 * c:8 * (c + 1), :] for c in range(chains))

    def body(_, acc):
        for _ in range(UNROLL):
            acc = tuple(jnp.where(a < b, a + s, a - s) for a in acc)
        return acc

    acc = jax.lax.fori_loop(0, steps // UNROLL, body, acc)
    for c in range(chains):
        o_ref[8 * c:8 * (c + 1), :] = acc[c]


def rate(chains: int, steps: int) -> float:
    shape = (8 * chains, 128)
    fn = jax.jit(pl.pallas_call(
        functools.partial(_kernel, chains=chains, steps=steps),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32)))
    x = jax.random.uniform(jax.random.key(0), shape, jnp.float32)
    fn(x).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return OPS_PER_STEP * steps * 8 * chains * 128 / best


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate_vpu.py: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 2
    rates = {c: rate(c, 1 << 20) for c in (4, 8, 16, 32)}
    print(json.dumps({
        "device_kind": dev.device_kind,
        "vpu_f32_ops_per_s": max(rates.values()),
        "by_chains": rates,
        "ops_per_step": OPS_PER_STEP,
        "date": datetime.date.today().isoformat(),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
