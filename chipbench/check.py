"""The comparison that decides ``correct``.

The program draws its sample inside the timed program from the step's
key and does not hand it out. The draw is a pure integer function of the
graph, the seeds and the key, so ``sampler_replay`` asks the program's
own sampler for it again with the same arguments: that is the ONE thing
taken from the program, and ``reference.check_sample`` holds it against
the reference's reading of the graph before anything is computed from it.
Everything downstream (the rows gathered, the forward pass, the loss, the
gradients, Adam) is the reference's own float32 arithmetic.
"""

from __future__ import annotations

import functools

import numpy as np

from . import reference

TINY_GRADIENT = 1e-3        # of the median leaf's: such a leaf moves by round-off


@functools.lru_cache(maxsize=None)
def _replay_for(fanout: tuple):
    """``replay(indptr, indices, seeds, key) -> reference.Sample``: the
    sample the timed step drew for these seeds under this key."""
    import jax
    from quiver_tpu.ops import sample_multihop

    @jax.jit
    def replay(indptr, indices, seeds, key):
        _, layers = sample_multihop(indptr, indices, seeds, list(fanout), key,
                                    method="exact", seeds_dense=True)
        return reference.Sample(
            seeds, [reference.Hop(l.n_id, l.row, l.col) for l in layers])

    return replay


def sampler_replay(fanout):
    return _replay_for(tuple(fanout))


def graph_reader(indptr, indices):
    """What ``reference.check_sample`` reads the graph through: the host's
    copy of ``indptr`` and ``row_values(positions) -> indices[positions]``
    gathered on the device."""
    import jax
    import jax.numpy as jnp
    take = jax.jit(lambda idx, pos: idx[pos])
    return np.asarray(indptr), lambda pos: take(
        indices, jnp.asarray(pos.astype(np.int32)))


def serve_key(seed: int, dispatch: int):
    """The key the ``dispatch``-th call of a ``ServeEngine(seed=seed)``
    samples under (0 is the warm-up's): the engine threads one chain,
    ``key, sub = split(key)`` a dispatch, as ``build_serve_step`` says."""
    import jax
    return _key_chain()(jax.random.key(seed), dispatch + 1)


@functools.lru_cache(maxsize=None)
def _key_chain():
    import jax

    @jax.jit
    def chain(key, n):
        def link(_, carry):
            key, sub = jax.random.split(carry[0])
            return key, sub
        return jax.lax.fori_loop(0, n, link, (key, key))[1]

    return chain


def _norms(tree):
    import jax
    return np.array([float(np.linalg.norm(np.asarray(l, np.float64)))
                     for l in jax.tree.leaves(tree)])


def _worst_leaf(program, ref, keep=None):
    """The widest gap between the program's norm and the reference's, leaf
    by leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    p, r = _norms(program), _norms(ref)
    floor = np.maximum(r, np.median(r))
    gap = np.abs(p - r) / np.maximum(floor, 1e-30)
    if keep is not None:
        gap = gap[keep]
    return float(gap.max())


class SampleFacts:
    """What ``reference.check_sample`` counted, summed over the samples a
    run checks."""

    def __init__(self):
        self.bad = self.edges = self.position_n = 0
        self.position_sum = 0.0

    def add(self, facts: dict):
        self.bad += facts["bad"]
        self.edges += facts["edges"]
        self.position_sum += facts["position_sum"]
        self.position_n += facts["position_n"]

    @property
    def draw_skew(self) -> float:
        """How far the mean place of a pick in its row lies from the
        middle, 0.5, that a uniform draw gives; a draw that always takes a
        row's first ``k`` reads 0.25 or more. With nothing to read it is
        not a number, which `correct` takes as over the limit."""
        if not self.position_n:
            return float("nan")
        return abs(self.position_sum / self.position_n - 0.5)


def train_numbers(program: dict, ref: dict, sample: SampleFacts) -> dict:
    """``sample_bad``, ``draw_skew``: the sample check's (above).
    ``loss_gap``: the widest relative gap of the three losses.
    ``grad_gap``: the first gradient as the optimizer got it, by the worst
    leaf. ``update_gap``: the parameters' change over the three steps, by
    the worst leaf among those whose reference gradient is not nought to
    rounding (under a thousandth of the median leaf's)."""
    import jax
    lp, lr = np.array(program["losses"]), np.array(ref["losses"])
    delta = lambda d: jax.tree.map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        d["params3"], d["params0"])
    g = _norms(ref["grad1"])
    moving = g >= TINY_GRADIENT * np.median(g)
    return {
        "sample_bad": float(sample.bad),
        "draw_skew": sample.draw_skew,
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": _worst_leaf(program["grad1"], ref["grad1"]),
        "update_gap": _worst_leaf(delta(program), delta(ref), keep=moving),
        "facts": {"losses": lp.tolist(), "ref_losses": lr.tolist(),
                  "sample_edges": int(sample.edges),
                  "picks_placed": int(sample.position_n),
                  "leaves_left_out": int((~moving).sum())},
    }


def row_gap(rows, ref_rows) -> float:
    """Served rows against the reference's: the widest absolute gap of a
    logit against the largest logit of that row in the reference, or of
    the median row, whichever is larger."""
    rows = np.asarray(rows, np.float64)
    ref_rows = np.asarray(ref_rows, np.float64)
    scale = np.abs(ref_rows).max(axis=1)
    scale = np.maximum(scale, np.median(scale))
    return float((np.abs(rows - ref_rows).max(axis=1) / scale).max())
