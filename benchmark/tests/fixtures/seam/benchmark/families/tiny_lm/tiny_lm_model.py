"""The fixture's system under test: a next-token model over integer sequences,
trained a few SGD steps in one jitted program per embedding width."""
import functools

import jax
import jax.numpy as jnp


def loss_fn(weights, tokens):
    hidden = weights["embed"][tokens[:, :-1]].mean(axis=1)
    logp = jax.nn.log_softmax(hidden @ weights["out"])
    return -jnp.take_along_axis(logp, tokens[:, -1:], axis=1).mean()


@functools.partial(jax.jit, static_argnames=("steps",))
def train(weights, tokens, batches, lr, steps):
    def step(w, rows):
        grads = jax.grad(loss_fn)(w, tokens[rows])
        return jax.tree_util.tree_map(lambda a, g: a - lr * g, w, grads), None

    weights, _ = jax.lax.scan(step, weights, batches[:steps])
    return weights, loss_fn(weights, tokens)
