"""Mesh-sharded plan execution: lane-parallel scaling + collective cost.

Two measurements of ``repro.dist.mesh_exec`` on the process's devices
(under ``JAX_PLATFORMS=cpu``, 8 forced host-platform devices):

1. **Lane-parallel Keccak program scaling.**  The full 24-round
   Keccak-f[1600] plan program over B payload lanes, columns sharded
   S ways.  Sharded execution is proven *collective-free* (the compiled
   HLO is scanned for collective ops) and *bit-exact* vs one device, so
   each device's work is exactly the single-device program at B/S
   lanes.  Scaling is therefore reported two ways, honestly labelled:

   * ``modeled_device_parallel``: B / t_shard(B/S) hashes/sec, where
     t_shard is the measured wall time of the per-shard executable on
     one device — what S *physical* devices run concurrently.  This is
     the number the acceptance criterion gates on (>= 4x at S=8).
   * ``measured_wall_1core``: the actual wall time of the S-way sharded
     program on THIS host.  The benchmark host exposes 8 XLA host
     devices on ``host_cores`` physical core(s) — device parallelism is
     time-sliced, so this number cannot show the speedup and is
     recorded to keep the JSON honest, not to claim it.

2. **Cross-shard MoE dispatch: occupancy-derived schedule vs naive
   all-gather.**  A locality-skewed MoE routing (most tokens stay on
   their own shard's experts) gives a block-banded shard connectivity;
   ``collective_schedule`` moves only the blocks that carry traffic in
   a couple of ppermute rounds, while the naive path all-gathers the
   full payload into every device.  Reported: scheduled vs naive block
   transfers and bytes on the wire, plus measured wall both ways, plus
   a uniform-random routing row where the connectivity is dense and the
   schedule's advantage honestly shrinks to ~nothing.

Results land in BENCH_mesh_sharded.json (quick:
BENCH_mesh_sharded_quick.json).

Usage: PYTHONPATH=src python -m benchmarks.bench_mesh_sharded [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from benchmarks.common import force_host_devices, row, time_fn
from repro.core import crossbar as xb
from repro.core import plan_algebra as pa
from repro.core import plan_program as pp
from repro.crypto import keccak as kk
from repro.dist import mesh_exec as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_JSON = os.path.join(REPO, "BENCH_mesh_sharded.json")
OUT_JSON_QUICK = os.path.join(REPO, "BENCH_mesh_sharded_quick.json")

_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
                "collective-permute", "reduce-scatter")


def _mesh(s: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:s]).reshape(s), ("data",))


# ---------------------------------------------------------------------------
# 1. Lane-parallel Keccak program scaling
# ---------------------------------------------------------------------------

def bench_keccak_scaling(b_total: int, s_values, *, iters, warmup):
    program = kk.megakernel_program()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 2, (kk.STATE_BITS, b_total)),
                    jnp.uint32)

    def run_local(xv):
        return pp.run_program(program, xv, backend="chained")

    # Reference: the whole batch on one device.
    t_full_us = time_fn(run_local, x, iters=iters, warmup=warmup)
    ref = jax.jit(run_local)(x)

    n_dev = len(jax.devices())
    records = []
    for s in s_values:
        b_loc = b_total // s
        # The per-shard executable, timed on one device: exactly what
        # each of S physical devices runs concurrently (collective-free
        # is asserted below, so there is no hidden cross-device term).
        t_shard_us = time_fn(run_local, x[:, :b_loc], iters=iters,
                             warmup=warmup)
        rec = {
            "sweep": "keccak_lane_parallel", "b_total": b_total,
            "n_shards": s, "b_per_shard": b_loc,
            "t_full_1dev_us": round(t_full_us, 1),
            "t_per_shard_us": round(t_shard_us, 1),
            "modeled_device_parallel": {
                "hashes_per_s": round(b_total / (t_shard_us * 1e-6), 1),
                "speedup_vs_1dev": round(t_full_us / t_shard_us, 2),
            },
        }
        if s <= n_dev:
            mesh = _mesh(s)
            fn = mx.sharded_program_fn(program, mesh)
            out = fn(x)
            rec["bit_exact_vs_1dev"] = bool(np.array_equal(
                np.asarray(ref), np.asarray(out)))
            hlo = fn.lower(x).compile().as_text()
            rec["collectives_in_hlo"] = [c for c in _COLLECTIVES
                                         if c in hlo]
            t_wall = time_fn(lambda xv: fn(xv), x, iters=iters,
                             warmup=warmup)
            rec["measured_wall_1core_us"] = round(t_wall, 1)
        else:
            rec["bit_exact_vs_1dev"] = None
            rec["collectives_in_hlo"] = None
            rec["measured_wall_1core_us"] = None
        records.append(rec)
        row(f"mesh_keccak/S{s}",
            modeled_speedup=rec["modeled_device_parallel"]
            ["speedup_vs_1dev"],
            hashes_per_s=rec["modeled_device_parallel"]["hashes_per_s"],
            exact=rec["bit_exact_vs_1dev"])
    return records


# ---------------------------------------------------------------------------
# 2. Cross-shard MoE dispatch: schedule vs naive all-gather
# ---------------------------------------------------------------------------

def _moe_dispatch_plan(t_tokens, n_experts, capacity, s, *, locality,
                       seed):
    """A capacity-slotted MoE dispatch plan with tunable shard locality.

    ``locality`` is the probability a token routes to an expert on its
    own shard (1/S of the expert range); the rest go uniform-random.
    Slots fill FIFO per expert; overflow tokens DROP (standard capacity
    semantics), keeping the plan output-injective.
    """
    rng = np.random.default_rng(seed)
    tokens_per_shard = t_tokens // s
    experts_per_shard = n_experts // s
    dest = np.full((t_tokens,), pa.DROP, np.int32)
    fill = np.zeros((n_experts,), np.int32)
    for t in range(t_tokens):
        my_shard = t // tokens_per_shard
        if rng.random() < locality:
            e = my_shard * experts_per_shard + rng.integers(
                0, experts_per_shard)
        else:
            e = rng.integers(0, n_experts)
        if fill[e] < capacity:
            dest[t] = e * capacity + fill[e]
            fill[e] += 1
    return xb.scatter_plan(jnp.asarray(dest), n_experts * capacity)


def bench_moe_dispatch(s, *, t_tokens, n_experts, capacity, d_model,
                       locality, label, iters, warmup):
    plan = _moe_dispatch_plan(t_tokens, n_experts, capacity, s,
                              locality=locality, seed=7)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(t_tokens, d_model)), jnp.float32)

    conn = mx.shard_connectivity(plan, s)
    stats = mx.schedule_stats(conn)
    block_bytes = (t_tokens // s) * d_model * 4
    ref = xb.apply_plan(plan, x, backend="einsum")

    rec = {
        "sweep": "moe_dispatch", "routing": label, "n_shards": s,
        "t_tokens": t_tokens, "n_experts": n_experts,
        "capacity": capacity, "d_model": d_model,
        "locality": locality,
        "connectivity": stats,
        "bytes_on_wire": {
            "scheduled": stats["scheduled_block_transfers"] * block_bytes,
            "naive_all_gather": stats["naive_block_transfers"]
            * block_bytes,
        },
    }
    if s <= len(jax.devices()):
        mesh = _mesh(s)
        fn_sched = mx.sharded_apply_fn(plan, mesh)
        fn_naive = mx.sharded_apply_naive_fn(plan, mesh)
        rec["bit_exact_scheduled"] = bool(np.allclose(
            np.asarray(ref), np.asarray(fn_sched(x))))
        rec["bit_exact_naive"] = bool(np.allclose(
            np.asarray(ref), np.asarray(fn_naive(x))))
        rec["measured_wall_1core_us"] = {
            "scheduled": round(time_fn(
                lambda xv: fn_sched(xv), x, iters=iters, warmup=warmup),
                1),
            "naive_all_gather": round(time_fn(
                lambda xv: fn_naive(xv), x, iters=iters, warmup=warmup),
                1),
        }
    row(f"mesh_moe/{label}/S{s}",
        rounds=stats["schedule_rounds"],
        scheduled_transfers=stats["scheduled_block_transfers"],
        naive_transfers=stats["naive_block_transfers"],
        exact=rec.get("bit_exact_scheduled"))
    return rec


# ---------------------------------------------------------------------------

def run(quick: bool = False) -> dict:
    n_dev = len(jax.devices())
    if quick:
        keccak_rows = bench_keccak_scaling(64, (1, 8), iters=2, warmup=1)
        moe_rows = [bench_moe_dispatch(
            min(8, max(2, n_dev)), t_tokens=128, n_experts=8, capacity=32,
            d_model=32, locality=0.9, label="skewed", iters=2, warmup=1)]
        acceptance = None
    else:
        keccak_rows = bench_keccak_scaling(
            1024, (1, 2, 4, 8), iters=3, warmup=1)
        moe_rows = [
            bench_moe_dispatch(8, t_tokens=1024, n_experts=32,
                               capacity=64, d_model=128, locality=0.9,
                               label="skewed", iters=3, warmup=1),
            bench_moe_dispatch(8, t_tokens=1024, n_experts=32,
                               capacity=64, d_model=128, locality=0.0,
                               label="uniform", iters=3, warmup=1),
        ]
        by_s = {r["n_shards"]: r for r in keccak_rows}
        skewed = moe_rows[0]
        acceptance = {
            "criterion": "lane-parallel Keccak program: sharded execution "
                         "bit-exact + collective-free HLO at every "
                         "available S, and modeled device-parallel "
                         "throughput (B / measured per-shard wall on one "
                         "device) >= 4x the 1-device rate at S=8; "
                         "cross-shard MoE dispatch's occupancy-derived "
                         "ppermute schedule moves fewer blocks than "
                         "naive all-gather on locality-skewed routing, "
                         "bit-exact both ways.  Wall-clock on this host "
                         "is time-sliced across host_cores physical "
                         "core(s) and recorded as measured_wall_1core.",
            "host_cores": os.cpu_count(),
            "devices_available": n_dev,
            "modeled_speedup_8dev_lane_parallel_keccak":
                by_s[8]["modeled_device_parallel"]["speedup_vs_1dev"],
            "sharded_bit_exact_all": all(
                r["bit_exact_vs_1dev"] for r in keccak_rows
                if r["bit_exact_vs_1dev"] is not None),
            "collective_free_all": all(
                r["collectives_in_hlo"] == [] for r in keccak_rows
                if r["collectives_in_hlo"] is not None),
            "moe_skewed_scheduled_vs_naive_transfers": (
                skewed["connectivity"]["scheduled_block_transfers"],
                skewed["connectivity"]["naive_block_transfers"]),
            "moe_skewed_schedule_rounds":
                skewed["connectivity"]["schedule_rounds"],
            "moe_bit_exact": (skewed.get("bit_exact_scheduled", True)
                              and skewed.get("bit_exact_naive", True)),
            "pass": (
                by_s[8]["modeled_device_parallel"]["speedup_vs_1dev"]
                >= 4.0
                and all(r["bit_exact_vs_1dev"] for r in keccak_rows
                        if r["bit_exact_vs_1dev"] is not None)
                and all(r["collectives_in_hlo"] == [] for r in keccak_rows
                        if r["collectives_in_hlo"] is not None)
                and skewed["connectivity"]["scheduled_block_transfers"]
                < skewed["connectivity"]["naive_block_transfers"]
                and skewed.get("bit_exact_scheduled", True)
                and skewed.get("bit_exact_naive", True)),
        }

    report = {
        "benchmark": "mesh_sharded",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "jax_backend": jax.default_backend(),
        "devices": n_dev,
        "host_cores": os.cpu_count(),
        "quick": quick,
        "rows": keccak_rows + moe_rows,
    }
    if acceptance is not None:
        report["acceptance"] = acceptance
    out_path = OUT_JSON_QUICK if quick else OUT_JSON
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"# wrote {out_path}")
    if acceptance is not None:
        print(f"# acceptance pass: {acceptance['pass']}")
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes only (CI smoke)")
    args = ap.parse_args()
    force_host_devices()
    run(quick=args.quick)


if __name__ == "__main__":
    main()
