"""The plain reference of the two-line model: softmax regression on a
batch's rows, mean cross-entropy, SGD. float32 ``jax.numpy`` on one
device; imports nothing of the program."""

import jax
import jax.numpy as jnp


def init_layers(key, dims):
    fan_in, fan_out = dims
    return {"w": jax.random.normal(key, (fan_in, fan_out)) / jnp.sqrt(fan_in),
            "b": jnp.zeros((fan_out,), jnp.float32)}


def gather_rows(feat, n_id):
    return feat[n_id]


def forward(layers, x):
    return jnp.matmul(x, layers["w"], precision="highest") + layers["b"]


def loss_and_grads(layers, x, labels):
    def loss_of(p):
        logits = forward(p, x)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)
    return jax.value_and_grad(loss_of)(layers)


def sgd_update(layers, grads, lr: float):
    return jax.tree.map(lambda p, g: p - lr * g, layers, grads)
