"""Optimizers from scratch: AdamW + Adafactor, with LR schedules.

Optimizer state is a pytree shaped like (or factored from) the param tree,
so under pjit the state inherits the params' PartitionSpecs.

Adafactor (Shazeer & Stern, 2018) keeps a FACTORED second moment — row and
column accumulators instead of a full [m, n] slot.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Schedules
# ---------------------------------------------------------------------------
def cosine_schedule(base_lr: float, warmup: int, total: int,
                    final_frac: float = 0.1) -> Callable:
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = base_lr * step / jnp.maximum(warmup, 1)
        prog = jnp.clip((step - warmup) / jnp.maximum(total - warmup, 1),
                        0.0, 1.0)
        cos = final_frac + (1 - final_frac) * 0.5 * (1 + jnp.cos(
            jnp.pi * prog))
        return jnp.where(step < warmup, warm, base_lr * cos)
    return lr


def constant_schedule(base_lr: float) -> Callable:
    return lambda step: jnp.asarray(base_lr, jnp.float32)


# ---------------------------------------------------------------------------
# Optimizer interface
# ---------------------------------------------------------------------------
@dataclass
class Optimizer:
    init: Callable[[Any], Any]
    update: Callable[..., Any]     # (params, grads, state) -> (params, state)


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def clip_by_global_norm(grads, max_norm: float):
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), gn


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------
def adamw(lr: Callable | float, b1: float = 0.9, b2: float = 0.95,
          eps: float = 1e-8, weight_decay: float = 0.1,
          clip_norm: Optional[float] = 1.0) -> Optimizer:
    lr_fn = lr if callable(lr) else constant_schedule(lr)

    def init(params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {
            "step": jnp.zeros((), jnp.int32),
            "m": jax.tree_util.tree_map(zeros, params),
            "v": jax.tree_util.tree_map(zeros, params),
        }

    def update(params, grads, state):
        step = state["step"] + 1
        if clip_norm is not None:
            grads, _ = clip_by_global_norm(grads, clip_norm)
        b1t = 1 - b1 ** step.astype(jnp.float32)
        b2t = 1 - b2 ** step.astype(jnp.float32)
        lr_t = lr_fn(step)

        def upd(p, g, m, v):
            g = g.astype(jnp.float32)
            m2 = b1 * m + (1 - b1) * g
            v2 = b2 * v + (1 - b2) * g * g
            mh = m2 / b1t
            vh = v2 / b2t
            delta = mh / (jnp.sqrt(vh) + eps) + weight_decay * \
                p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr_t * delta).astype(p.dtype), \
                m2, v2

        flat = jax.tree_util.tree_map(upd, params, grads, state["m"],
                                      state["v"])
        params2 = jax.tree_util.tree_map(lambda t: t[0], flat,
                                         is_leaf=lambda x: isinstance(x, tuple))
        m2 = jax.tree_util.tree_map(lambda t: t[1], flat,
                                    is_leaf=lambda x: isinstance(x, tuple))
        v2 = jax.tree_util.tree_map(lambda t: t[2], flat,
                                    is_leaf=lambda x: isinstance(x, tuple))
        return params2, {"step": step, "m": m2, "v": v2}

    return Optimizer(init=init, update=update)


# ---------------------------------------------------------------------------
# Adafactor (factored second moment; optional first moment off)
# ---------------------------------------------------------------------------
def adafactor(lr: Callable | float, decay: float = 0.8,
              eps: float = 1e-30, clip_threshold: float = 1.0,
              weight_decay: float = 0.0, min_dim_factored: int = 2
              ) -> Optimizer:
    lr_fn = lr if callable(lr) else constant_schedule(lr)

    def _factored(p) -> bool:
        return p.ndim >= min_dim_factored

    def init(params):
        def slot(p):
            if _factored(p):
                return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                        "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                        jnp.float32)}
            return {"v": jnp.zeros(p.shape, jnp.float32)}
        return {"step": jnp.zeros((), jnp.int32),
                "slots": jax.tree_util.tree_map(
                    slot, params, is_leaf=lambda x: hasattr(x, "shape"))}

    def update(params, grads, state):
        step = state["step"] + 1
        t = step.astype(jnp.float32)
        beta2 = 1.0 - t ** (-decay)
        lr_t = lr_fn(step)

        def upd(p, g, slot):
            g = g.astype(jnp.float32)
            g2 = g * g + eps
            if _factored(p):
                vr = beta2 * slot["vr"] + (1 - beta2) * jnp.mean(g2, -1)
                vc = beta2 * slot["vc"] + (1 - beta2) * jnp.mean(g2, -2)
                denom = jnp.maximum(jnp.mean(vr, -1, keepdims=True), eps)
                u = g * jax.lax.rsqrt(vr / denom)[..., None] \
                    * jax.lax.rsqrt(vc)[..., None, :]
                new_slot = {"vr": vr, "vc": vc}
            else:
                v = beta2 * slot["v"] + (1 - beta2) * g2
                u = g * jax.lax.rsqrt(v)
                new_slot = {"v": v}
            # update clipping (RMS(u) <= clip_threshold)
            rms_u = jnp.sqrt(jnp.mean(u * u) + 1e-12)
            u = u / jnp.maximum(1.0, rms_u / clip_threshold)
            delta = lr_t * u + weight_decay * lr_t * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - delta).astype(p.dtype), new_slot

        is_slot = lambda x: isinstance(x, dict) and ("v" in x or "vr" in x)
        pairs = jax.tree_util.tree_map(
            upd, params, grads, state["slots"],
            is_leaf=lambda x: hasattr(x, "shape"))
        params2 = jax.tree_util.tree_map(
            lambda t: t[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
        slots2 = jax.tree_util.tree_map(
            lambda t: t[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
        return params2, {"step": step, "slots": slots2}

    return Optimizer(init=init, update=update)


def make_optimizer(name: str, lr, **kw) -> Optimizer:
    if name == "adamw":
        return adamw(lr, **kw)
    if name == "adafactor":
        return adafactor(lr, **kw)
    raise ValueError(name)
