"""A stand-in client model for recording a cell's schedule on the CPU.

The schedule (selections, invocation times, cold starts, round closes) is
simulated on the host from the fleet, the clients' cardinalities and the
traffic's seed; it reads nothing of the client model. Softmax regression
on the flattened input trains in a fraction of a second per round, so a
whole schedule records in seconds.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


class SoftmaxRegression:
    def __init__(self, input_shape: tuple[int, ...], n_classes: int):
        self.input_shape = tuple(input_shape)
        self.n_classes = n_classes

    def init(self, rng):
        d = math.prod(self.input_shape)
        params = {"w": jnp.zeros((d, self.n_classes), jnp.float32),
                  "b": jnp.zeros((self.n_classes,), jnp.float32)}
        return params, {"w": (None, None), "b": (None,)}

    def predict(self, p, x):
        return x.reshape(x.shape[0], -1) @ p["w"] + p["b"]

    def loss(self, params, batch):
        z = self.predict(params, batch["x"])
        ce = jnp.mean(jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, batch["y"][:, None].astype(jnp.int32), -1)[:, 0])
        return ce, {"ce": ce}

    def accuracy(self, params, batch):
        z = self.predict(params, batch["x"])
        return jnp.mean((jnp.argmax(z, -1) == batch["y"]).astype(jnp.float32))
