import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape x mesh) cell this lowers and
COMPILES the real step function — train_step for training cells, a full
forward for prefill cells, serve_step (one token against a primed cache)
for decode cells — against 256 (single-pod) or 512 (2-pod) placeholder
devices, then extracts:

  * ``compiled.memory_analysis()``  — per-device bytes (fits-in-HBM proof)
  * ``compiled.cost_analysis()``    — per-device HLO FLOPs & bytes
  * collective bytes                — parsed from ``compiled.as_text()``
    (ring-model traffic per op; see _collective_bytes)

and derives the three roofline terms (v5e: 197 bf16 TFLOP/s, 819 GB/s
HBM, ~50 GB/s/link ICI).  Results go to JSON for EXPERIMENTS.md.

Cost-measurement methodology (IMPORTANT): XLA's HloCostAnalysis counts a
while-loop body ONCE regardless of trip count, so the scanned layer
stacks would undercount FLOPs/bytes/collectives by ~num_layers.  The dry-
run therefore compiles each cell THREE times:

  1. full depth, scanned   — the deliverable artifact: proves lowering +
     compilation + per-device memory fit at the real configuration;
  2. depth d1, fully unrolled (scan_unroll=True)  — exact cost at d1;
  3. depth d2, fully unrolled                     — exact cost at d2;

and extrapolates linearly (cost is affine in depth: embed/head = the
intercept, per-layer = the slope):

    cost(L) = cost(d1) + (cost(d2) - cost(d1)) / (d2 - d1) * (L - d1)

This is exact for FLOPs/bytes (no approximation) and for collectives up
to GSPMD making different (better) fusion choices at full depth.

Usage:
    python -m repro.launch.dryrun --arch mixtral-8x22b --shape train_4k \
        [--multi-pod] [--grad-accum 1] [--out out.json]
    python -m repro.launch.dryrun --all [--multi-pod]   # every cell
"""

import argparse
import json
import re
import sys
import time
from collections import defaultdict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, cell_applicable, get_config, list_archs
from repro.dist import sharding as shd
from repro.dist.annotate import logical_axes
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models.model_zoo import build
from repro.train import TrainOptions, make_train_step
from repro.train.trainer import init_state

# v5e hardware constants (per the brief)
PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
ICI_BW = 50e9              # bytes/s / link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "s32": 4, "u32": 4, "f16": 2, "bf16": 2,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "s64": 8, "u64": 8, "c64": 8,
}

_COLL_RE = re.compile(
    r"=\s*(\w+)\[([\d,]*)\][^\s]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shape_bytes(dtype: str, dims: str) -> float:
    b = _DTYPE_BYTES.get(dtype, 4)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return float(n * b)


def _collective_bytes(hlo_text: str) -> tuple[float, dict]:
    """Per-device link traffic (ring model) summed over collective ops.

    R = result bytes per device, k = participants per group:
      all-gather          R * (k-1)/k      (device receives the other shards)
      all-reduce          2R * (k-1)/k     (reduce-scatter + all-gather)
      reduce-scatter      R * (k-1)        (input = R*k, sends (k-1)/k of it)
      all-to-all          R * (k-1)/k
      collective-permute  R                (single hop)
    """
    total = 0.0
    breakdown = defaultdict(float)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, op, _start = m.groups()
        r = _shape_bytes(dtype, dims)
        k = 1
        g = _GROUPS_RE.search(line)
        if g:
            k = int(g.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            if gl:
                k = len(gl.group(1).split(","))
        if k <= 1:
            continue
        frac = (k - 1) / k
        if op == "all-gather":
            traffic = r * frac
        elif op == "all-reduce":
            traffic = 2 * r * frac
        elif op == "reduce-scatter":
            traffic = r * (k - 1)
        elif op == "all-to-all":
            traffic = r * frac
        else:  # collective-permute
            traffic = r
        total += traffic
        breakdown[op] += traffic
    return total, dict(breakdown)


def _sds(tree):
    """eval_shape -> plain ShapeDtypeStruct tree (drop weak_type etc.)."""
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


def _state_shardings(state_shapes, mesh, cfg):
    params_sh = shd.param_shardings(state_shapes.params, mesh, cfg)
    repl = NamedSharding(mesh, P())
    from repro.optim import AdamWState
    from repro.train.trainer import TrainState
    master_sh = (jax.tree.map(lambda p: p, params_sh)
                 if state_shapes.opt.master is not None else None)
    return TrainState(
        params=params_sh,
        opt=AdamWState(step=repl,
                       mu=jax.tree.map(lambda p: p, params_sh),
                       nu=jax.tree.map(lambda p: p, params_sh),
                       master=master_sh),
        step=repl, rng=repl)


def lower_train(api, cfg, shape, mesh, *, grad_accum=1, forward_only=False,
                bf16_params=False):
    state_shapes = _sds(jax.eval_shape(
        lambda: init_state(api.init(jax.random.PRNGKey(0)),
                           jax.random.PRNGKey(0),
                           bf16_params=bf16_params)))
    batch_specs = api.batch_specs(shape.global_batch, shape.seq_len)
    state_sh = _state_shardings(state_shapes, mesh, cfg)
    batch_sh = shd.batch_shardings(batch_specs, mesh)

    if forward_only:
        fwd = lambda params, batch: api.loss_fn(params, batch)[0]
        with mesh, logical_axes(mesh):
            lowered = jax.jit(
                fwd,
                in_shardings=(state_sh.params, batch_sh),
            ).lower(state_shapes.params, batch_specs)
        return lowered

    step_fn = make_train_step(
        api.loss_fn, TrainOptions(grad_accum=grad_accum,
                                  schedule=cfg.lr_schedule,
                                  scan_unroll=cfg.scan_unroll))
    with mesh, logical_axes(mesh):
        lowered = jax.jit(
            step_fn,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        ).lower(state_shapes, batch_specs)
    return lowered


def lower_decode(api, cfg, shape, mesh):
    window = 4096 if shape.name == "long_500k" else 0
    params_shapes = _sds(jax.eval_shape(
        lambda: api.init(jax.random.PRNGKey(0))))
    cache_shapes = _sds(jax.eval_shape(
        lambda: api.init_caches(shape.global_batch, shape.seq_len,
                                jnp.bfloat16, window=window)))
    params_sh = shd.param_shardings(params_shapes, mesh, cfg)
    cache_sh = shd.cache_shardings(cache_shapes, mesh, cfg)

    baxes = shd.batch_axes(mesh)
    bsz = shd.mesh_axis_size(mesh, tuple(baxes))
    bspec = (baxes if len(baxes) > 1 else baxes[0]) \
        if shape.global_batch % bsz == 0 else None
    tok_sh = NamedSharding(mesh, P(bspec, None))
    pos_sh = NamedSharding(mesh, P())
    tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    with mesh, logical_axes(mesh):
        lowered = jax.jit(
            api.decode_fn,
            in_shardings=(params_sh, tok_sh, cache_sh, pos_sh),
            out_shardings=(None, cache_sh),
            donate_argnums=(2,),
        ).lower(params_shapes, tok, cache_shapes, pos)
    return lowered


def _lower_cell(api, cfg, shape, mesh, grad_accum, bf16_params=False):
    if shape.kind == "train":
        return lower_train(api, cfg, shape, mesh, grad_accum=grad_accum,
                           bf16_params=bf16_params)
    if shape.kind == "prefill":
        return lower_train(api, cfg, shape, mesh, forward_only=True)
    return lower_decode(api, cfg, shape, mesh)


def _cost_depths(cfg) -> tuple[int, int, float]:
    """(d1, d2, full_units) for the unrolled cost compiles."""
    if cfg.family == "hybrid":
        period = cfg.shared_attn_period or cfg.num_layers
        groups = cfg.num_layers // period
        return period, 2 * period, float(groups * period)
    return 1, 2, float(cfg.num_layers)


def _shallow_cfg(cfg, depth):
    import dataclasses as _dc
    kw = {"num_layers": depth, "scan_unroll": True}
    if cfg.family == "encdec":
        kw["encoder_layers"] = depth
    return _dc.replace(cfg, **kw)


def _cost_compile(cfg, shape, mesh, grad_accum, *, seq_override=None,
                  bf16_params=False):
    if seq_override is not None:
        import dataclasses as _dc
        shape = _dc.replace(shape, seq_len=seq_override)
    api = build(cfg)
    compiled = _lower_cell(api, cfg, shape, mesh, grad_accum,
                           bf16_params).compile()
    ca = compiled.cost_analysis() or {}
    coll, breakdown = _collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll, "coll_breakdown": breakdown}


def _cost_rwkv_bilinear(cfg, shape, mesh, grad_accum):
    """RWKV cost extraction: bilinear extrapolation over (layers, seq).

    The WKV inner scan is 16 tokens wide, so full unrolling at S=4096
    means 256 chunk bodies per layer (2048 at 32k) — CPU compile blows
    up.  RWKV is attention-free: every op's cost is exactly linear in S
    (and the optimizer part is S-independent), so
        cost(L, S) = alpha + beta*L + gamma*S + delta*L*S
    is exact and four shallow/short unrolled compiles determine it.
    """
    d1, d2, full_l = _cost_depths(cfg)
    s1, s2 = 64, 128
    grid = {}
    # grad_accum=1 for the COST compiles: unrolling the accum scan
    # multiplies the HLO by accum (prohibitive on top of the WKV chunk
    # unroll).  FLOPs/HLO-bytes are identical (same total tokens); the
    # collective term omits the (accum-1) extra FSDP weight re-gathers —
    # a mild lower bound, noted in the cell's cost_method.
    for d in (d1, d2):
        for s in (s1, s2):
            grid[(d, s)] = _cost_compile(_shallow_cfg(cfg, d), shape, mesh,
                                         1, seq_override=s)
    full_s = shape.seq_len
    out = {}
    for k in ("flops", "bytes", "coll"):
        c11, c12 = grid[(d1, s1)][k], grid[(d1, s2)][k]
        c21, c22 = grid[(d2, s1)][k], grid[(d2, s2)][k]
        delta = ((c22 - c21) - (c12 - c11)) / ((d2 - d1) * (s2 - s1))
        beta = ((c21 - c11) / (d2 - d1)) - delta * s1
        gamma = ((c12 - c11) / (s2 - s1)) - delta * d1
        alpha = c11 - beta * d1 - gamma * s1 - delta * d1 * s1
        out[k] = max(alpha + beta * full_l + gamma * full_s
                     + delta * full_l * full_s, 0.0)
    # collective breakdown: scale ops proportionally to the total
    tot1 = grid[(d1, s1)]["coll"]
    scale = out["coll"] / tot1 if tot1 else 0.0
    out["coll_breakdown"] = {op: v * scale for op, v in
                             grid[(d1, s1)]["coll_breakdown"].items()}
    return out


def _extrapolate(c1, c2, d1, d2, full):
    out = {}
    for k in ("flops", "bytes", "coll"):
        slope = (c2[k] - c1[k]) / (d2 - d1)
        out[k] = max(c1[k] + slope * (full - d1), 0.0)
    bk = {}
    for op in set(c1["coll_breakdown"]) | set(c2["coll_breakdown"]):
        a = c1["coll_breakdown"].get(op, 0.0)
        b = c2["coll_breakdown"].get(op, 0.0)
        bk[op] = max(a + (b - a) / (d2 - d1) * (full - d1), 0.0)
    out["coll_breakdown"] = bk
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod=False, grad_accum=0,
             verbose=True, skip_cost=False, bf16_params=False):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": why}

    api = build(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    if grad_accum == 0:
        # auto: one sequence per device per microbatch — bounds the
        # double-buffered remat stash that sets peak HBM on deep models.
        bsz = shd.mesh_axis_size(mesh, tuple(shd.batch_axes(mesh)))
        grad_accum = max(shape.global_batch // bsz, 1) \
            if shape.kind == "train" else 1

    # (1) full-depth scanned compile: the deliverable + memory proof
    t0 = time.time()
    lowered = _lower_cell(api, cfg, shape, mesh, grad_accum, bf16_params)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    ma = compiled.memory_analysis()

    # (2)+(3) shallow unrolled cost compiles -> exact extrapolated costs
    chips = mesh.devices.size
    if skip_cost:
        ca = compiled.cost_analysis() or {}
        coll_bytes, coll_breakdown = _collective_bytes(compiled.as_text())
        flops_dev = float(ca.get("flops", 0.0))
        bytes_dev = float(ca.get("bytes accessed", 0.0))
        cost_method = "full-compile (scan bodies counted once: LOWER BOUND)"
    elif cfg.family == "rwkv" and shape.kind != "decode":
        ext = _cost_rwkv_bilinear(cfg, shape, mesh, grad_accum)
        flops_dev, bytes_dev, coll_bytes = (ext["flops"], ext["bytes"],
                                            ext["coll"])
        coll_breakdown = ext["coll_breakdown"]
        cost_method = ("bilinear (layers x seq) extrapolation from 4 "
                       "short unrolled compiles at grad_accum=1 "
                       "(attention-free: exact for flops/bytes; "
                       "collective term omits per-microbatch re-gathers)")
    else:
        d1, d2, full = _cost_depths(cfg)
        # cost compiles cap the unrolled accumulation factor: FLOPs/bytes
        # are identical at grad_accum=1 (same total tokens); only the
        # per-microbatch FSDP re-gathers are then undercounted for deep
        # hybrids (see the rwkv note above).
        cost_accum = grad_accum if cfg.family != "hybrid" else 1
        c1 = _cost_compile(_shallow_cfg(cfg, d1), shape, mesh, cost_accum,
                           bf16_params=bf16_params)
        c2 = _cost_compile(_shallow_cfg(cfg, d2), shape, mesh, cost_accum,
                           bf16_params=bf16_params)
        ext = _extrapolate(c1, c2, d1, d2, full)
        flops_dev, bytes_dev, coll_bytes = (ext["flops"], ext["bytes"],
                                            ext["coll"])
        coll_breakdown = ext["coll_breakdown"]
        cost_method = (f"unrolled depth-{d1}/{d2} compiles, linear "
                       f"extrapolation to {int(full)} layers")

    # tokens processed by this step
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        flops_per_tok = 6  # fwd + bwd
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        flops_per_tok = 2
    else:
        tokens = shape.global_batch  # one new token per slot
        flops_per_tok = 2
    if cfg.family == "encdec" and shape.kind != "decode":
        # enc sees S/2 frames and dec S/2 tokens: each param stream
        # processes half the nominal positions.
        tokens //= 2
    n_active = cfg.active_param_count()
    model_flops = float(flops_per_tok * n_active * tokens)

    t_comp = flops_dev / PEAK_FLOPS
    t_mem = bytes_dev / HBM_BW
    t_coll = coll_bytes / ICI_BW
    terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll}
    dominant = max(terms, key=terms.get)

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "chips": int(chips),
        "cost_method": cost_method,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "per_device": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_hbm_gib": round(
                (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30,
                3),
            "flops": flops_dev,
            "hlo_bytes": bytes_dev,
            "collective_bytes": coll_bytes,
        },
        "collectives": coll_breakdown,
        "roofline": {
            **{k: float(f"{v:.6g}") for k, v in terms.items()},
            "dominant": dominant,
            "model_flops": model_flops,
            "hlo_flops_global": flops_dev * chips,
            "usefulness": (model_flops / (flops_dev * chips)
                           if flops_dev else 0.0),
            "step_time_bound_s": max(terms.values()),
        },
    }
    if verbose:
        print(json.dumps(result, indent=1))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=0,
                    help="0 = auto (one sequence per device per microbatch)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-cost", action="store_true",
                    help="skip the unrolled cost compiles (memory proof only)")
    ap.add_argument("--bf16-params", action="store_true",
                    help="bf16 live params + f32 master (perf iteration)")
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()
    use_compile_cache()

    cells = []
    if args.all:
        for arch in list_archs():
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]

    results = []
    failed = 0
    for arch, shape in cells:
        try:
            results.append(run_cell(arch, shape, multi_pod=args.multi_pod,
                                    grad_accum=args.grad_accum,
                                    skip_cost=args.skip_cost,
                                    bf16_params=args.bf16_params))
        except Exception as e:  # a failing cell is a bug in the system
            failed += 1
            results.append({"arch": arch, "shape": shape,
                            "mesh": "2x16x16" if args.multi_pod else "16x16",
                            "status": "FAILED", "error": repr(e)[:2000]})
            print(f"FAILED {arch} x {shape}: {e!r}", file=sys.stderr)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
