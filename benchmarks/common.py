"""Shared benchmark utilities: timing + compiled-cost extraction."""

from __future__ import annotations

import os
import time

import jax
import numpy as np


def force_host_devices(n: int = 8) -> None:
    """Under ``JAX_PLATFORMS=cpu``, ask XLA for ``n`` host devices so
    the mesh benchmarks have a mesh in this process.  Takes effect only
    before JAX creates its backend; on an accelerator the devices are
    the chips and nothing is set."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


def time_fn(fn, *args, iters=20, warmup=3):
    """Median wall-time (us) of a jitted callable on this host."""
    jitted = jax.jit(fn)
    out = jitted(*args)
    jax.block_until_ready(out)
    for _ in range(warmup):
        jax.block_until_ready(jitted(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(times))


def hlo_cost(fn, *args):
    """(flops, bytes accessed) from the compiled module (1 device)."""
    compiled = jax.jit(fn).lower(*args).compile()
    ca = compiled.cost_analysis() or {}
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def row(name, **cols):
    cells = ",".join(f"{k}={v}" for k, v in cols.items())
    print(f"{name},{cells}")
