"""Adam / AdamW with mixed-precision state policy.

State layout is FSDP-friendly: moments inherit the parameter sharding
(same pytree structure), so ZeRO-style sharding of optimizer state falls
out of the parameter sharding rules for free.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class AdamState(NamedTuple):
    step: jax.Array
    mu: dict
    nu: dict


def adam(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8, state_dtype=jnp.float32):
    """Returns (init_fn, update_fn). ``lr`` may be a float or schedule fn."""

    sched = lr if callable(lr) else (lambda step: lr)

    def init(params):
        mu = jax.tree.map(lambda p: jnp.zeros(p.shape, state_dtype), params)
        nu = jax.tree.map(lambda p: jnp.zeros(p.shape, state_dtype), params)
        return AdamState(jnp.zeros((), jnp.int32), mu, nu)

    def update(grads, state, params):
        with jax.named_scope("optim.adam"):
            return _update(grads, state, params)

    def _update(grads, state, params):
        step = state.step + 1
        stepf = step.astype(jnp.float32)
        lr_t = sched(stepf)
        c1 = 1.0 - b1**stepf
        c2 = 1.0 - b2**stepf

        def upd(g, m, v, p):
            g32 = g.astype(state_dtype)
            m = b1 * m + (1 - b1) * g32
            v = b2 * v + (1 - b2) * (g32 * g32)
            mhat = m / c1
            vhat = v / c2
            new_p = p.astype(state_dtype) - lr_t * mhat / (jnp.sqrt(vhat) + eps)
            return new_p.astype(p.dtype), m, v

        out = jax.tree.map(upd, grads, state.mu, state.nu, params)
        new_params = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
        mu = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
        nu = jax.tree.map(lambda t: t[2], out, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, AdamState(step, mu, nu)

    return init, update


def adamw(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01, state_dtype=jnp.float32):
    sched = lr if callable(lr) else (lambda step: lr)
    init, _ = adam(lr, b1, b2, eps, state_dtype)

    def update(grads, state, params):
        with jax.named_scope("optim.adam"):
            return _update(grads, state, params)

    def _update(grads, state, params):
        step = state.step + 1
        stepf = step.astype(jnp.float32)
        lr_t = sched(stepf)
        c1 = 1.0 - b1**stepf
        c2 = 1.0 - b2**stepf

        def upd(g, m, v, p):
            g32 = g.astype(state_dtype)
            m = b1 * m + (1 - b1) * g32
            v = b2 * v + (1 - b2) * (g32 * g32)
            mhat = m / c1
            vhat = v / c2
            p32 = p.astype(state_dtype)
            new_p = p32 - lr_t * (mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p32)
            return new_p.astype(p.dtype), m, v

        out = jax.tree.map(upd, grads, state.mu, state.nu, params)
        new_params = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
        mu = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
        nu = jax.tree.map(lambda t: t[2], out, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, AdamState(step, mu, nu)

    return init, update
