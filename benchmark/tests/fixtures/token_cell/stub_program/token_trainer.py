"""The fixture's system under test: a trainer that is none of the program's
families and has nothing of theirs but the protocol kinds/train_epochs.py
states (``cfg.epochs``, ``run()``, one ``emit_epoch`` per epoch,
``loss_history``, ``metrics.counter_get``, ``params``).

A bigram model: ``logits[t] = embed[token[t]] @ out``, next-token loss,
plain gradient descent over all sequences at once. ``fault`` is added to one
output weight inside the forward, as a wrong kernel would: the weights the
check reads are sound and what the trainer computes with them is not.
"""

from __future__ import annotations

import time
import types

import jax
import jax.numpy as jnp
import numpy as np


class _Metrics:
    def counter_get(self, name: str) -> float:
        return 0.0


class TokenTrainer:
    def __init__(self, tokens: np.ndarray, vocab: int, width: int, learn_rate: float,
                 seed: int, fault: float = 0.0) -> None:
        self.cfg = types.SimpleNamespace(epochs=0)
        self.metrics = _Metrics()
        self.loss_history = []
        self.tokens = jnp.asarray(tokens)
        self.fault = float(fault)
        k_embed, k_out = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)))
        self.params = {
            "embed": jax.random.normal(k_embed, (vocab, width), jnp.float32),
            "out": jax.random.normal(k_out, (width, vocab), jnp.float32) / np.sqrt(width),
        }
        self._epoch = 0
        self.logits = jax.jit(self._logits)
        self.loss_and_grads = jax.jit(jax.value_and_grad(self._loss))

        def step(params, tokens):
            loss, grads = jax.value_and_grad(self._loss)(params, tokens)
            return jax.tree.map(lambda p, g: p - learn_rate * g, params, grads), loss

        self._step = jax.jit(step)

    def _logits(self, params, tokens):
        out = params["out"].at[0, 0].add(self.fault)
        return params["embed"][tokens] @ out

    def _loss(self, params, tokens):
        logp = jax.nn.log_softmax(self._logits(params, tokens[:, :-1]), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

    def run(self) -> None:
        for _ in range(int(self.cfg.epochs)):
            t = time.perf_counter()
            self.params, loss = self._step(self.params, self.tokens)
            loss = float(loss)  # waits for the device
            self.loss_history.append(loss)
            self.emit_epoch(self._epoch, time.perf_counter() - t, loss, stages=None)
            self._epoch += 1

    def emit_epoch(self, epoch, seconds, loss=None, stages=None, **extra) -> None:
        pass
