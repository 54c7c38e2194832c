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

A typed graph whose relations each keep their OWN neighbour lists
(heterogeneous samplers draw a fanout from each relation apart, as
PyG's ``NeighborLoader`` over ``HeteroData`` does) comes as
``RelationIndices``: one CSR a relation, over its target type's local
ids, whose entries are its source type's local ids. The walk then draws
every relation of a frontier's type on its own and keeps one frontier a
node type (``ops.sample_multihop.sample_typed_multihop``).
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

    @property
    def num_types(self) -> int:
        return len(self.starts) + 1

    def start(self, t: int) -> int:
        """The first global id of type ``t``."""
        return 0 if t == 0 else self.starts[t - 1]

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


@jax.tree_util.register_pytree_node_class
class RelationIndices:
    """A typed graph whose relations each have their own neighbour lists.
    Relation ``r = (t, s)`` of ``types`` is a CSR over type ``t``'s LOCAL
    ids (row ``i`` is the node ``types.start(t) + i``) whose entries are
    type ``s``'s local ids: the step's ``indptr`` is the tuple of the
    relations' row pointers, its ``indices`` this object, which holds
    the tuple of their entry arrays (the leaves) and, static:

    ``types``      the ``NodeTypes`` (id ranges and relation pairs);
    ``caps``       ``caps[t][l]``: the rows type ``t``'s frontier keeps
                   after hop ``l`` (the configuration's, sized from the
                   graph's own sample); None (all, a type's, a hop's)
                   keeps the union's whole static size. A pick past a full
                   cap is masked and counted (``metrics.TYPE_CAP_DROPS``),
                   never dropped unseen.

    A batch is of type 0, whose global ids are its local ids."""

    __slots__ = ("indices", "types", "caps")

    def __init__(self, indices, types: NodeTypes, caps=None):
        self.indices = tuple(indices)
        self.types = types
        self.caps = None if caps is None else tuple(
            None if c is None else tuple(None if x is None else int(x)
                                         for x in c) for c in caps)
        if len(self.indices) != types.num_relations:
            raise ValueError(f"{len(self.indices)} neighbour lists for "
                             f"{types.num_relations} relations")
        if self.caps is not None and len(self.caps) != types.num_types:
            raise ValueError(f"caps for {len(self.caps)} node types, the "
                             f"graph has {types.num_types}")

    def cap(self, t: int, hop: int):
        """Type ``t``'s cap after hop ``hop``, or None."""
        if self.caps is None or self.caps[t] is None:
            return None
        return self.caps[t][hop]

    def tree_flatten(self):
        return (self.indices,), (self.types, self.caps)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], *aux)
