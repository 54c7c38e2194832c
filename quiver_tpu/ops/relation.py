"""Typed graphs: node types as id ranges, and the relation of an edge.

A heterogeneous graph merged into ONE id space (OGB-LSC's MAG240M
baseline merges papers, authors and institutions so) keeps each node
type in a range of ids, and gives every edge the relation of its pair of
(target type, source type). Such a graph is an ordinary CSR whose
``indices`` come wrapped as ``TypedIndices``: the same edge array, and a
static ``NodeTypes`` saying where the types begin and which pair each
relation is. The topology itself says it is typed; no step builder takes
an option for it.

The relation of a sampled slot is then a function of the two global ids
the draw already holds (the seed and its pick): a few compares and
selects, no gather and no stored relation array.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class NodeTypes:
    """``starts[t - 1]`` is the first id of type ``t`` (type 0 begins at
    0); ``relations[r]`` is relation ``r``'s ``(target type, source
    type)``. An edge whose pair is no relation's gets -1. A pytree with
    no leaves: it crosses a jit boundary as static data."""
    starts: tuple
    relations: tuple

    def __post_init__(self):
        object.__setattr__(self, "starts", tuple(int(s) for s in self.starts))
        object.__setattr__(self, "relations", tuple(
            (int(a), int(b)) for a, b in self.relations))
        if list(self.starts) != sorted(self.starts):
            raise ValueError(f"type starts must ascend: {self.starts}")
        if len(set(self.relations)) != len(self.relations):
            raise ValueError("two relations name the same pair of types")

    @property
    def num_relations(self) -> int:
        return len(self.relations)

    def type_of(self, ids: jax.Array) -> jax.Array:
        """The type of each id (its range), int32."""
        t = jnp.zeros(ids.shape, jnp.int32)
        for s in self.starts:
            t = t + (ids >= s).astype(jnp.int32)
        return t

    def relation(self, targets: jax.Array, sources: jax.Array) -> jax.Array:
        """The relation of each edge ``source -> target`` (broadcast), -1
        where either end is -1 or the pair is no relation's."""
        tt, st = self.type_of(targets), self.type_of(sources)
        rel = jnp.full(jnp.broadcast_shapes(tt.shape, st.shape), -1,
                       jnp.int32)
        for r, (a, b) in enumerate(self.relations):
            rel = jnp.where((tt == a) & (st == b), r, rel)
        return jnp.where((targets >= 0) & (sources >= 0), rel, -1)


@jax.tree_util.register_pytree_node_class
class TypedIndices:
    """A CSR edge array over typed ids: ``indices`` (the one leaf) and its
    static ``types``. Pass it where a step takes ``indices``."""

    __slots__ = ("indices", "types")

    def __init__(self, indices, types: NodeTypes):
        self.indices = indices
        self.types = types

    def tree_flatten(self):
        return (self.indices,), self.types

    @classmethod
    def tree_unflatten(cls, types, leaves):
        return cls(leaves[0], types)


def split_typed(indices):
    """``(edge array, NodeTypes or None)`` of a step's ``indices``."""
    if isinstance(indices, TypedIndices):
        return indices.indices, indices.types
    return indices, None
