"""Batch normalisation over the rows of a batch that are real.

A sampled block has a static number of target slots and a varying number
of nodes in them (``Adj.valid_targets``). ``BatchNorm1d``'s training-mode
statistics are over the batch's rows, so they have to leave the padding
out: the mean and the biased variance below are over the rows ``mask``
marks, and rows it does not mark come back zero. Stateless: the running
averages that ``BatchNorm1d`` keeps for evaluation are no part of a
training step (``parallel.train.TrainState`` carries no non-gradient
model state, and the step builders have no evaluation pass).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from .. import profiling


def masked_batch_norm(x: jax.Array, mask: jax.Array, scale: jax.Array,
                      bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    """``(x - mean) / sqrt(var + eps) * scale + bias`` with ``mean`` and
    the biased ``var`` over the rows where ``mask`` [rows] holds."""
    keep = mask[:, None].astype(x.dtype)
    n = jnp.maximum(jnp.sum(mask, dtype=x.dtype), 1.0)
    mean = jnp.sum(x * keep, axis=0) / n
    centred = (x - mean) * keep
    var = jnp.sum(centred * centred, axis=0) / n
    return (centred * jax.lax.rsqrt(var + eps) * scale + bias) * keep


class MaskedBatchNorm(nn.Module):
    """``masked_batch_norm`` with its affine pair (ones, zeros), under
    ``qt_norm``."""

    eps: float = 1e-5

    @nn.compact
    def __call__(self, x, mask):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        with profiling.scope(profiling.QT_NORM):
            return masked_batch_norm(x, mask, scale, bias, self.eps)
