"""AdamW with decoupled weight decay, global-norm clipping, cosine schedule.

Optimizer state is f32 regardless of param dtype (mixed precision); state
trees mirror the param tree so the same PartitionSpecs shard both.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


def cosine_schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(1.0, cfg.warmup_steps)
    decay_steps = jnp.maximum(1.0, cfg.total_steps - cfg.warmup_steps)
    frac = jnp.clip((step - cfg.warmup_steps) / decay_steps, 0.0, 1.0)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (
        1 + jnp.cos(jnp.pi * frac))
    return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, cos)


def adamw_init(params: Any) -> dict:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {"mu": zeros,
            "nu": jax.tree.map(jnp.copy, zeros),
            "step": jnp.zeros((), jnp.int32)}


def clip_by_global_norm(grads: Any, max_norm: float) -> tuple[Any, jax.Array]:
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in leaves))
    scale = jnp.minimum(1.0, max_norm / (gnorm + 1e-9))
    return jax.tree.map(lambda g: g * scale, grads), gnorm


def adamw_update(cfg: AdamWConfig, params: Any, grads: Any,
                 state: dict) -> tuple[Any, dict, dict]:
    """One AdamW step (clipping included), under the named scope
    ``adamw``."""
    with jax.named_scope("adamw"):
        return _adamw_update(cfg, params, grads, state)


def _adamw_update(cfg: AdamWConfig, params: Any, grads: Any,
                  state: dict) -> tuple[Any, dict, dict]:
    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
    step = state["step"] + 1
    lr = cosine_schedule(cfg, step)
    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

    mu = jax.tree.map(lambda m, g: cfg.b1 * m + (1 - cfg.b1) * g,
                      state["mu"], grads)
    nu = jax.tree.map(lambda v, g: cfg.b2 * v + (1 - cfg.b2) * g * g,
                      state["nu"], grads)

    def upd(p, m, v):
        mhat = m / b1c
        vhat = v / b2c
        step_ = mhat / (jnp.sqrt(vhat) + cfg.eps)
        newp = p.astype(jnp.float32) - lr * (
            step_ + cfg.weight_decay * p.astype(jnp.float32))
        return newp.astype(p.dtype)

    new_params = jax.tree.map(upd, params, mu, nu)
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_params, {"mu": mu, "nu": nu, "step": step}, metrics
