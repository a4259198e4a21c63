"""Plain reference of the fixture's bigram model: numpy, float64
accumulation, nothing of the stub trainer's."""

from __future__ import annotations

import numpy as np


def logits(params: dict, tokens: np.ndarray) -> np.ndarray:
    return params["embed"].astype(np.float64)[tokens] @ params["out"].astype(np.float64)


def loss_and_grads(params: dict, tokens: np.ndarray):
    """(next-token loss, gradients in the layout of ``params``)."""
    x, y = tokens[:, :-1].reshape(-1), tokens[:, 1:].reshape(-1)
    embed, out = params["embed"].astype(np.float64), params["out"].astype(np.float64)
    h = embed[x]
    z = h @ out
    z -= z.max(axis=1, keepdims=True)
    p = np.exp(z)
    p /= p.sum(axis=1, keepdims=True)
    loss = -np.mean(np.log(p[np.arange(len(y)), y]))
    dz = p
    dz[np.arange(len(y)), y] -= 1.0
    dz /= len(y)
    d_embed = np.zeros_like(embed)
    np.add.at(d_embed, x, dz @ out.T)
    return float(loss), {"embed": d_embed, "out": h.T @ dz}
