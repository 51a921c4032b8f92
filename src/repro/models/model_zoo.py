"""Model zoo: one uniform API over every assigned architecture family.

``build(cfg)`` returns a ``ModelAPI`` whose members have identical
signatures across families:

    init(key)                          -> params
    loss_fn(params, batch)             -> (loss, metrics)
    batch_specs(batch, seq)            -> {name: ShapeDtypeStruct}  (train)
    make_batch(key, batch, seq)        -> real arrays, same tree    (smoke)
    init_caches(batch, max_seq, dtype, window=0) -> decode caches
    decode_fn(params, tokens1, caches, pos)      -> (logits, caches)

Family-specific decode context (enc-dec cross-attention K/V) is folded
*into* the caches pytree so that ``decode_fn`` stays uniform — the serving
engine and the dry-run treat caches as an opaque pytree.

Input-shape conventions for the assigned cells (see DESIGN.md §5):
  * dense / moe / rwkv / hybrid: tokens (B, S).
  * vlm: frontend patch prefix F=256 + text (B, S - F); total length = S.
  * encdec: frames (B, S/2, D) into the encoder + tokens (B, S/2) into the
    decoder; total processed length = S.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Array = jax.Array

VLM_PATCHES = 256  # InternVL2 patch prefix (stub frontend output length)


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    cfg: ModelConfig
    init: Callable
    loss_fn: Callable
    batch_specs: Callable
    make_batch: Callable
    init_caches: Callable
    decode_fn: Callable


def _token_specs(cfg, batch, seq):
    return {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}


def _token_batch(cfg, key, batch, seq):
    return {"tokens": jax.random.randint(key, (batch, seq), 0,
                                         cfg.vocab_size, dtype=jnp.int32)}


def build(cfg: ModelConfig) -> ModelAPI:
    """The family's ModelAPI, with ``init`` storing floating-point
    weights in ``cfg.param_dtype``."""
    api = _build(cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    if dtype == jnp.float32:
        return api

    def init(key):
        return jax.tree.map(
            lambda w: (w.astype(dtype)
                       if jnp.issubdtype(w.dtype, jnp.floating) else w),
            api.init(key))

    return dataclasses.replace(api, init=init)


def _build(cfg: ModelConfig) -> ModelAPI:
    fam = cfg.family
    if fam in ("dense",):
        from repro.models import transformer as M
        return ModelAPI(
            cfg=cfg,
            init=lambda key: M.lm_init(key, cfg),
            loss_fn=lambda p, b: M.lm_loss(p, b, cfg),
            batch_specs=lambda batch, seq: _token_specs(cfg, batch, seq),
            make_batch=lambda key, batch, seq: _token_batch(cfg, key, batch, seq),
            init_caches=lambda batch, max_seq, dtype=jnp.bfloat16, window=0:
                M.init_caches(cfg, batch, max_seq, dtype),
            decode_fn=lambda p, t1, c, pos: M.decode_step(p, t1, c, pos, cfg),
        )

    if fam == "moe":
        from repro.models import moe as M
        return ModelAPI(
            cfg=cfg,
            init=lambda key: M.lm_init(key, cfg),
            loss_fn=lambda p, b: M.lm_loss(p, b, cfg),
            batch_specs=lambda batch, seq: _token_specs(cfg, batch, seq),
            make_batch=lambda key, batch, seq: _token_batch(cfg, key, batch, seq),
            init_caches=lambda batch, max_seq, dtype=jnp.bfloat16, window=0:
                M.init_caches(cfg, batch, max_seq, dtype),
            decode_fn=lambda p, t1, c, pos: M.decode_step(p, t1, c, pos, cfg),
        )

    if fam == "rwkv":
        from repro.models import rwkv as M
        return ModelAPI(
            cfg=cfg,
            init=lambda key: M.lm_init(key, cfg),
            loss_fn=lambda p, b: M.lm_loss(p, b, cfg),
            batch_specs=lambda batch, seq: _token_specs(cfg, batch, seq),
            make_batch=lambda key, batch, seq: _token_batch(cfg, key, batch, seq),
            init_caches=lambda batch, max_seq, dtype=jnp.bfloat16, window=0:
                M.init_caches(cfg, batch, max_seq, dtype),
            decode_fn=lambda p, t1, c, pos: M.decode_step(p, t1, c, pos, cfg),
        )

    if fam == "hybrid":
        from repro.models import hybrid as M
        return ModelAPI(
            cfg=cfg,
            init=lambda key: M.lm_init(key, cfg),
            loss_fn=lambda p, b: M.lm_loss(p, b, cfg),
            batch_specs=lambda batch, seq: _token_specs(cfg, batch, seq),
            make_batch=lambda key, batch, seq: _token_batch(cfg, key, batch, seq),
            init_caches=lambda batch, max_seq, dtype=jnp.bfloat16, window=0:
                M.init_caches(cfg, batch, max_seq, dtype, window=window),
            decode_fn=lambda p, t1, c, pos: M.decode_step(p, t1, c, pos, cfg),
        )

    if fam == "vlm":
        from repro.models import vlm as M

        f = min(VLM_PATCHES, cfg.frontend_seq or VLM_PATCHES)

        def specs(batch, seq):
            s_text = max(seq - f, 8)
            return {
                "tokens": jax.ShapeDtypeStruct((batch, s_text), jnp.int32),
                "frontend_embeds": jax.ShapeDtypeStruct(
                    (batch, f, cfg.d_model), jnp.dtype(cfg.compute_dtype)),
                "patch_valid": jax.ShapeDtypeStruct((batch, f), jnp.bool_),
            }

        def mk(key, batch, seq):
            s_text = max(seq - f, 8)
            k1, k2, k3 = jax.random.split(key, 3)
            return {
                "tokens": jax.random.randint(k1, (batch, s_text), 0,
                                             cfg.vocab_size, dtype=jnp.int32),
                "frontend_embeds": jax.random.normal(
                    k2, (batch, f, cfg.d_model),
                    jnp.dtype(cfg.compute_dtype)) * 0.02,
                "patch_valid": jax.random.bernoulli(k3, 0.9, (batch, f)),
            }

        return ModelAPI(
            cfg=cfg,
            init=lambda key: M.lm_init(key, cfg),
            loss_fn=lambda p, b: M.lm_loss(p, b, cfg),
            batch_specs=specs,
            make_batch=mk,
            init_caches=lambda batch, max_seq, dtype=jnp.bfloat16, window=0:
                M.init_caches(cfg, batch, max_seq, dtype),
            decode_fn=lambda p, t1, c, pos: M.decode_step(p, t1, c, pos, cfg),
        )

    if fam == "encdec":
        from repro.models import encdec as M

        def specs(batch, seq):
            half = max(seq // 2, 8)
            return {
                "tokens": jax.ShapeDtypeStruct((batch, half), jnp.int32),
                "frontend_embeds": jax.ShapeDtypeStruct(
                    (batch, half, cfg.d_model), jnp.dtype(cfg.compute_dtype)),
            }

        def mk(key, batch, seq):
            half = max(seq // 2, 8)
            k1, k2 = jax.random.split(key)
            return {
                "tokens": jax.random.randint(k1, (batch, half), 0,
                                             cfg.vocab_size, dtype=jnp.int32),
                "frontend_embeds": jax.random.normal(
                    k2, (batch, half, cfg.d_model),
                    jnp.dtype(cfg.compute_dtype)) * 0.02,
            }

        def init_caches(batch, max_seq, dtype=jnp.bfloat16, window=0):
            # cross-attention K/V (from a max_seq//2-frame encoding) live in
            # the caches pytree so decode_fn stays uniform.
            caches = M.init_caches(cfg, batch, max_seq, dtype)
            f = max(max_seq // 2, 8)
            kv, hd = cfg.num_kv_heads, cfg.hd
            caches["cross"] = {
                "k": jnp.zeros((cfg.num_layers, batch, f, kv, hd), dtype),
                "v": jnp.zeros((cfg.num_layers, batch, f, kv, hd), dtype),
            }
            return caches

        def decode_fn(p, t1, c, pos):
            cross = c["cross"]
            logits, new_c = M.decode_step(p, t1, {"self": c["self"]}, pos,
                                          cfg, cross=cross)
            new_c["cross"] = cross
            return logits, new_c

        return ModelAPI(
            cfg=cfg,
            init=lambda key: M.lm_init(key, cfg),
            loss_fn=lambda p, b: M.lm_loss(p, b, cfg),
            batch_specs=specs,
            make_batch=mk,
            init_caches=init_caches,
            decode_fn=decode_fn,
        )

    raise ValueError(f"unknown family {fam!r}")
