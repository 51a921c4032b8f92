"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

Builds the (reduced by default) model and serves a synthetic request
batch through the slot engine — the host-scale mirror of the decode
dry-run cells.
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, reduced
from repro.launch.compile_cache import use_compile_cache
from repro.models.model_zoo import build
from repro.serve import ServeOptions, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, required=True)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced(cfg)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))

    import jax.numpy as jnp
    engine = ServingEngine(
        api, ServeOptions(batch_slots=args.slots,
                          max_new_tokens=args.max_new_tokens,
                          temperature=args.temperature),
        max_seq=args.max_seq, cache_dtype=jnp.float32)
    prompts = [[(7 * i + j) % cfg.vocab_size for j in range(3 + i % 3)]
               for i in range(args.slots)]
    outs = engine.generate(params, prompts, key=jax.random.PRNGKey(1))
    for p, o in zip(prompts, outs):
        print(f"{p} -> {o}")


if __name__ == "__main__":
    main()
