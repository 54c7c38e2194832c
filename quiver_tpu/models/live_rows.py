"""A product over the rows of a block that something reads.

``live_rows_product(x, ws, live, block)`` is ``x @ w`` for each ``w`` of
``ws``, over a block whose rows past a count the step computes at run
time are read by nothing (a frontier's padding slots, the empty places
of a tile layout). It is a loop over row blocks whose trip count is
``ceil(live / block)``: a turn is one ``dynamic_slice`` of ``block``
rows, one product a weight at the configuration's precision and one
``dynamic_update_slice`` of the turn's block of each output, so the
blocks past that count are never
multiplied and their output rows stay zero. A ragged last block (``N``
not a multiple of ``block``) starts at ``N - block`` and computes again
rows an earlier block holds, with the same values.

A loop with a run-time trip count cannot be reverse-differentiated, so
the product has its own VJP, which runs the same blocks: ``dW`` is the
sum of ``x_b^T dY_b`` and ``dx`` is ``dY_b W^T`` into a zero block,
formed only where ``x`` is differentiated (JAX says so at trace time:
the gathered rows of a step's first layer carry no gradient, and no
``[N, in]`` cotangent is built for them).

With ``group`` (``[N // block]`` ints) the weight is a stack ``[R, in,
out]`` and block ``b`` is multiplied by ``w[group[b]]``, read inside its
turn; its ``dW`` goes into that weight's slice. The ``[blocks, in, out]``
weight block of a batched product and the scatter-add of its cotangent
are never formed.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.custom_derivatives import SymbolicZero


def live_rows_product(x, ws, live, block: int, group=None,
                      clean: bool = False):
    """``(ys, rows)``: for each weight ``w`` of the tuple ``ws``, ``y``
    [N, out] is ``x @ w`` on rows ``[0, rows)`` and zero past them (a turn
    reads its rows once for all the weights); ``rows = min(ceil(live /
    block) * block, N)`` the rows the loop multiplied (an int32 scalar,
    what the step's ``project_rows`` counter records). Rows of ``x`` at
    or past ``rows`` are never read, forward or backward. ``block`` is
    clipped to ``N``; with ``group``, ``N`` must be a multiple of it.
    ``clean`` takes the rows of a turn at or past ``live`` for zero rows,
    forward and backward, so that nothing they hold (a padding slot's
    NaN) reaches an output or a gradient."""
    n = x.shape[0]
    block = max(1, min(int(block), n))
    if group is not None and n % block:
        raise ValueError(f"a grouped product takes whole blocks: {n} rows "
                         f"in blocks of {block}")
    live = jnp.clip(jnp.asarray(live, jnp.int32), 0, n)
    turns = (live + block - 1) // block
    return (_product(x, tuple(ws), turns, group, live if clean else None,
                     block),
            jnp.minimum(turns * block, n))


def _start(b, block: int, n: int):
    return jnp.minimum(b * block, n - block)


def _rows(x, start, block: int, limit):
    """The turn's ``block`` rows from ``start``; with ``limit``, those at
    or past it read as zero."""
    xb = lax.dynamic_slice_in_dim(x, start, block)
    if limit is None:
        return xb
    keep = start + jnp.arange(block, dtype=jnp.int32) < limit
    return jnp.where(keep[:, None], xb, 0)


def _pick(w, group, b):
    if group is None:
        return w
    return lax.dynamic_index_in_dim(w, group[b], keepdims=False)


def _unstack(stack, n: int, block: int):
    """The ``[N, width]`` rows of a ``[blocks, block, width]`` stack whose
    block ``b`` holds the rows turn ``b`` read: a ragged last block holds
    rows ``[N - block, N)``, and its tail is theirs."""
    blocks = stack.shape[0]
    rows = stack.reshape(blocks * block, -1)
    if n % block:
        cut = (blocks - 1) * block
        rows = jnp.concatenate([rows[:cut], rows[blocks * block - (n - cut):]])
    return rows


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _product(x, ws, turns, group, limit, block):
    # the output is written a block at a time into a stack whose leading
    # index is the turn's: on the chip, a run-time row offset into the
    # [N, out] block kept that block beside a second one in the compiled
    # step's temporaries
    n = x.shape[0]

    def turn(b, ys):
        xb = _rows(x, _start(b, block, n), block, limit)
        return tuple(lax.dynamic_update_index_in_dim(
            y, jnp.dot(xb, _pick(w, group, b)).astype(y.dtype), b, 0)
            for w, y in zip(ws, ys))

    zeros = tuple(jnp.zeros((-(-n // block), block, w.shape[-1]),
                            jnp.result_type(x, w)) for w in ws)
    return tuple(_unstack(y, n, block)
                 for y in lax.fori_loop(0, turns, turn, zeros))


def _product_fwd(x, ws, turns, group, limit, block):
    # ``symbolic_zeros``: every primal comes with whether it is perturbed
    xv, wv = x.value, tuple(w.value for w in ws)
    turns = turns.value
    group = None if group is None else group.value
    limit = None if limit is None else limit.value
    return (_product(xv, wv, turns, group, limit, block),
            (xv, wv, turns, group, limit, x.perturbed))


def _product_bwd(block, res, gs):
    x, ws, turns, group, limit, want_dx = res
    n = x.shape[0]
    # a weight whose output nothing differentiates adds no work
    moved = [i for i, g in enumerate(gs) if not isinstance(g, SymbolicZero)]
    want_dx = want_dx and bool(moved)

    def turn(b, carry):
        dws, dx = carry
        start = _start(b, block, n)
        xb = _rows(x, start, block, limit)
        if n % block:
            # the clamped last turn reads again rows an earlier one summed
            fresh = start + jnp.arange(block) >= b * block
            xb = jnp.where(fresh[:, None], xb, 0)
        dws, part = list(dws), 0
        for j, i in enumerate(moved):
            gb = lax.dynamic_slice_in_dim(gs[i], start, block)
            grad = lax.dot_general(xb, gb, (((0,), (0,)), ((), ())))
            grad = grad.astype(dws[j].dtype)
            if group is None:
                dws[j] = dws[j] + grad
            else:
                r = group[b]
                dws[j] = lax.dynamic_update_index_in_dim(
                    dws[j], lax.dynamic_index_in_dim(dws[j], r, keepdims=False)
                    + grad, r, 0)
            if want_dx:
                part = part + jnp.dot(gb, _pick(ws[i], group, b).T)
        if want_dx:
            dx = lax.dynamic_update_slice_in_dim(dx, part.astype(dx.dtype),
                                                 start, 0)
        return tuple(dws), dx

    dws = tuple(jnp.zeros_like(ws[i]) for i in moved)
    dx = jnp.zeros_like(x) if want_dx else None
    dws, dx = lax.fori_loop(0, turns, turn, (dws, dx))
    grads = [None] * len(ws)
    for j, i in enumerate(moved):
        grads[i] = dws[j]
    return dx, tuple(grads), None, None, None


_product.defvjp(_product_fwd, _product_bwd, symbolic_zeros=True)
