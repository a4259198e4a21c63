"""Parameter init + hand-rolled Adam/SGD with the reference's decay schedule.

Reference: ``Parameter`` (core/NtsScheduler.hpp:639-791): Xavier-uniform init
with scale sqrt(6/(w+h)) (:669-672), L2 term folded into the gradient
(``W_g = W_gradient + weight_decay * W``, :747), Adam moment updates, and a
step-size schedule ``alpha_t *= decay_rate`` every ``decay_epoch`` epochs
(``next()``, :727-736). The reference's ``next()`` uses running *products* of
beta powers as the momentum coefficients — a quirk of its hand-written loop;
here we implement textbook Adam bias correction (which the alpha formula in
``next()`` approximates) while keeping the same decay schedule, L2 coupling,
and hyperparameter defaults, so convergence matches the toolkits.

Distributed model sync (``init_parameter`` broadcast + ``all_reduce_to_gradient``,
:716-722, comm/network.h:198-211) is not done here: under pjit/shard_map,
replicated parameters and psum'd gradients fall out of the sharding annotations
— see neutronstarlite_tpu.parallel.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def xavier_uniform(key: jax.Array, w: int, h: int, dtype=jnp.float32) -> jax.Array:
    """Xavier-uniform [-s, s] with s = sqrt(6/(w+h)) (NtsScheduler.hpp:669)."""
    scale = float(np.sqrt(6.0 / (w + h)))
    return jax.random.uniform(key, (w, h), dtype=dtype, minval=-scale, maxval=scale)


@dataclasses.dataclass(frozen=True)
class AdamConfig:
    alpha: float = 0.01
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-9
    weight_decay: float = 0.0001
    decay_rate: float = 0.97
    decay_epoch: int = 100  # -1 disables the schedule
    warmup_steps: int = 0  # > 0: alpha rises linearly from 0 over this many
    # updates (the token-sequence family's WARMUP_EPOCHS; 0, the default of
    # every other trainer, leaves the step size and the program as they were)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AdamState:
    m: PyTree
    v: PyTree
    step: jax.Array  # scalar int32, counts epochs/updates


def adam_init(params: PyTree) -> AdamState:
    zeros = jax.tree.map(jnp.zeros_like, params)
    return AdamState(
        m=zeros, v=jax.tree.map(jnp.zeros_like, params), step=jnp.zeros((), jnp.int32)
    )


def adam_update(
    params: PyTree, grads: PyTree, state: AdamState, cfg: AdamConfig
) -> Tuple[PyTree, AdamState]:
    """One Adam step with L2-coupled decay and the stepped-alpha schedule."""
    t = state.step + 1
    tf = t.astype(jnp.float32)
    if cfg.decay_epoch and cfg.decay_epoch > 0:
        n_decays = (t // cfg.decay_epoch).astype(jnp.float32)
        alpha = cfg.alpha * jnp.power(cfg.decay_rate, n_decays)
    else:
        alpha = jnp.asarray(cfg.alpha, jnp.float32)
    if cfg.warmup_steps > 0:
        alpha = alpha * jnp.minimum(1.0, tf / cfg.warmup_steps)
    bias1 = 1.0 - jnp.power(cfg.beta1, tf)
    bias2 = 1.0 - jnp.power(cfg.beta2, tf)
    lr_t = alpha * jnp.sqrt(bias2) / bias1

    def upd(p, g, m, v):
        g = g + cfg.weight_decay * p  # L2 folded into the gradient (:747)
        m_new = cfg.beta1 * m + (1.0 - cfg.beta1) * g
        v_new = cfg.beta2 * v + (1.0 - cfg.beta2) * g * g
        p_new = p - lr_t * m_new / (jnp.sqrt(v_new) + cfg.epsilon)
        return p_new, m_new, v_new

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.m)
    flat_v = treedef.flatten_up_to(state.v)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, AdamState(m=new_m, v=new_v, step=t)


def sgd_update(
    params: PyTree, grads: PyTree, lr: float, weight_decay: float
) -> PyTree:
    """learnC2C_with_decay_SGD (:750): W = (W - lr*g) * (1 - wd)."""
    return jax.tree.map(lambda p, g: (p - lr * g) * (1.0 - weight_decay), params, grads)
