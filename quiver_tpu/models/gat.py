"""GAT in flax over the masked layer format (BASELINE.json configs[4]:
"GAT on ogbn-products with attention-weighted neighbor sampling").

``GATConv`` is PyG's layer as the reference's MAG240M benchmark builds it
(``GATConv(in_channels: int, out, heads)``, train_quiver_multi_node.py):
ONE projection shared by sources and targets, sampled edges ``j -> i``
with ``j == i`` dropped and one self edge given to every VALID target, a
LeakyReLU'd additive logit, a softmax over each target's edges, a bias.

    H = X W                                  [S, heads x out]
    e_ij = LeakyReLU(<H_j, a_src> + <H_i, a_dst>)        per head
    alpha_ij = softmax_{j in N(i) u {i}} e_ij
    out_i = concat_h sum_j alpha_ij H_j + b  (or the heads' mean)

The softmax is masked: an empty (-1) edge slot and a target slot that
holds no node get no attention mass. Where the ``Adj`` states its slot
layout (``fanout``: the layers of ``parallel.train.layers_to_adjs``) the
softmax and the weighted sum run over the slot axis, the self edge as a
slot of its own; otherwise they are segment reductions over the target
ids. One path a layer, chosen at trace time from what the ``Adj`` states;
the slot form leaves ``qt_attention_slots`` in the program's names.

Over a typed graph (the ``Adj`` states each slot's ``relation``) the
layer is a relational one, OGB-LSC's R-GAT (``GATConv(..., heads,
add_self_loops=False)`` a relation, summed): every relation ``r`` has
its own ``W_r``, ``a_src_r``, ``a_dst_r`` and bias, no edge is added or
dropped, and the softmax runs over the slots of each (target, relation)
group:

    P_e = x_j W_r                          r = r(e), e = j -> i
    e_ij = LeakyReLU(x_j . (W_r a_src_r) + x_i . (W_r a_dst_r))  per head
    alpha_ij = softmax over j in N_r(i)
    out_i = sum_r concat_h sum_j alpha_ij P_e + sum of b_r over the
            relations with an edge in the block

Each slot's source row is projected by its own relation's weight alone:
a grouped product over the slots sorted by relation, in tiles, the
sort, the places and the row moves named ``qt_relation_route``. Both
logit terms are products with ``W_r a_r`` (``[in, heads]``): a slot's
source term one more product of the same tiles, a target's under every
relation. The existing layer is the case of one relation with its own self edge:
the same group softmax serves both.

Every projection of a layer's rows multiplies only the rows that
something reads (``models.live_rows``): the one-relation layer's rows up
to the largest source any slot names and the valid targets, the
relational layer's tiles up to the end of the last relation's run. The
rest of the output stays zero and no product runs for it; each such
product sows the rows it multiplied and the rows its shape holds
(``counters``: ``project_rows``, ``project_rows_cap``), which a step
that collects metrics adds to its device counters.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from .. import profiling
from .live_rows import live_rows_product

NEG_INF = -1e30


def segment_softmax(logits: jax.Array, segment_ids: jax.Array,
                    num_segments: int, valid: jax.Array) -> jax.Array:
    """Softmax over edges grouped by target segment, masked."""
    logits = jnp.where(valid, logits, NEG_INF)
    seg_max = jax.ops.segment_max(logits, segment_ids,
                                  num_segments=num_segments)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    shifted = jnp.where(valid, logits - seg_max[segment_ids], NEG_INF)
    expd = jnp.where(valid, jnp.exp(shifted), 0.0)
    denom = jax.ops.segment_sum(expd, segment_ids, num_segments=num_segments)
    return expd / jnp.maximum(denom[segment_ids], 1e-16)


def _group_softmax(logits, ok, slope: float, rel=None, relations: int = 1):
    """``alpha``, the shape of ``logits`` (slots first): each target's
    softmax of ``LeakyReLU(logits)`` over its ``ok`` slots, and with
    ``rel`` (a relation a slot) over the slots of each relation apart.
    No relation is one group, which is what one relation is."""
    logits = jnp.where(ok, nn.leaky_relu(logits, slope), NEG_INF)
    if rel is None:
        top = logits.max(axis=0)
        top = jnp.where(top > NEG_INF, top, 0.0)
        e = jnp.where(ok, jnp.exp(logits - top), 0.0)
        return e / jnp.maximum(e.sum(axis=0), 1e-16)
    groups = [rel == r for r in range(relations)]
    top = jnp.zeros_like(logits)
    for g in groups:
        m = jnp.where(g, logits, NEG_INF).max(axis=0)
        top = jnp.where(g, jnp.where(m > NEG_INF, m, 0.0), top)
    e = jnp.where(ok, jnp.exp(logits - top), 0.0)
    total = jnp.ones_like(logits)
    for g in groups:
        total = jnp.where(g, jnp.where(g, e, 0.0).sum(axis=0), total)
    return e / jnp.maximum(total, 1e-16)


def _attend_slots(h, att_src, att_dst, src, target_mask, fanout: int,
                  slope: float):
    """The slot form. ``src`` is row 0 of an ``edge_index`` whose slot
    ``e`` targets ``e // fanout``. Per head, with the targets on the
    minor axis: the neighbours' rows are gathered slot-major
    (``[fanout, T, out]``: the gather's own output under another name, as
    in ``models.sage.masked_mean_aggregate``), the logits are ``[fanout +
    1, T]`` with the self edge as the last slot, and the softmax and the
    weighted sum are dense reduces over that leading axis: no scatter.
    A source's logit term ``<H_j, a_src>`` is taken from the gathered row
    of each slot that holds it: a second gather of one number a slot
    costs more on the chip (+32 ms of a 171 ms step at the MAG240M
    shapes) than the row's 256 products."""
    heads, out = att_src.shape
    t = target_mask.shape[0]
    src = src.reshape(t, fanout).T                       # [k, T]
    ok = (src >= 0) & (src != jnp.arange(t, dtype=src.dtype)) & target_mask
    rows = h[jnp.where(ok, src, 0).reshape(-1)].reshape(fanout, t, -1)
    ok = jnp.concatenate([ok, target_mask[None]])        # [k + 1, T]
    own = h[:t]
    result = []
    for i in range(heads):
        lanes = slice(i * out, (i + 1) * out)
        nbr, me = rows[..., lanes], own[:, lanes]
        at_dst = (me * att_dst[i]).sum(-1)               # [T]
        logits = jnp.concatenate([(nbr * att_src[i]).sum(-1),
                                  (me * att_src[i]).sum(-1)[None]]) + at_dst
        alpha = _group_softmax(logits, ok, slope)        # [k + 1, T]
        result.append((nbr * alpha[:fanout, :, None]).sum(axis=0)
                      + me * alpha[fanout][:, None])
    return jnp.concatenate(result, axis=-1)              # [T, heads x out]


def _attend_segments(h, att_src, att_dst, edge_index, target_mask,
                     slope: float):
    """The general form: the sampled edges and the self edges as one list,
    a segment softmax and a segment sum a head."""
    heads, out = att_src.shape
    t = target_mask.shape[0]
    me = jnp.arange(t, dtype=edge_index.dtype)
    src = jnp.concatenate([edge_index[0], me])
    dst = jnp.concatenate([edge_index[1], me])
    ok = jnp.concatenate([
        (edge_index[0] >= 0) & (edge_index[1] >= 0)
        & (edge_index[0] != edge_index[1])
        & target_mask[jnp.clip(edge_index[1], 0)], target_mask])
    s, d = jnp.where(ok, src, 0), jnp.where(ok, dst, 0)
    h3 = h.reshape(h.shape[0], heads, out)
    at_src = (h3 * att_src).sum(-1)                      # [S, heads]
    at_dst = (h3[:t] * att_dst).sum(-1)                  # [T, heads]
    logits = nn.leaky_relu(at_src[s] + at_dst[d], slope)  # [E + T, heads]
    msgs = h3[s]
    result = []
    for i in range(heads):
        alpha = segment_softmax(logits[:, i], d, t, ok)
        result.append(jax.ops.segment_sum(msgs[:, i] * alpha[:, None], d,
                                          num_segments=t))
    return jnp.concatenate(result, axis=-1)


@jax.custom_vjp
def _take_rows(x, take, inverse):
    """``x[take]`` where ``take`` is one-to-one into ``x``'s rows and
    ``inverse[p]`` is the row of the result that row ``p`` of ``x`` went
    to (``len(take)`` where none did): the cotangent comes back through
    ``inverse``, a gather, and is not scattered."""
    return x[take]


def _take_rows_fwd(x, take, inverse):
    return x[take], inverse


def _take_rows_bwd(inverse, g):
    # ``len(take)`` reads 0, and ``g`` is not copied to hold a zero row
    return jnp.take(g, inverse, axis=0, mode="fill", fill_value=0), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _tile_rows(n: int) -> int:
    """Rows a tile of the grouped product, and a block of the one-relation
    projection: a power of two near ``n / 32``, from 128 to 2048."""
    return 1 << max(7, min(11, (max(n, 1) // 32).bit_length() - 1))


def live_sources(adj):
    """How many leading rows of a layer's sources something reads: every
    slot's source id lies below it, and so does every valid target (all
    of ``size[1]`` where the ``Adj`` states no count). An empty slot's -1
    moves nothing."""
    targets = adj.size[1] if adj.valid_targets is None else adj.valid_targets
    return jnp.maximum(jnp.max(adj.edge_index[0], initial=-1) + 1, targets)


def _sow_rows(module, rows, cap: int):
    """The rows a live-rows product multiplied and the rows its shape
    holds, sown where the caller asks for ``counters`` (not at init)."""
    if not module.is_initializing():
        module.sow("counters", "project_rows", rows)
        module.sow("counters", "project_rows_cap", jnp.int32(cap))


def relation_slots(adj):
    """``(src, rel, ok)`` of a typed ``adj``'s edge slots, each ``[E]``:
    slot-major (``[fanout, T]`` flattened) where the ``Adj`` states its
    ``fanout``, in its own order otherwise. ``ok``: a valid edge of a
    relation into a target slot that holds a node."""
    mask = adj.target_mask()
    src, dst = adj.edge_index
    rel = adj.relation
    if adj.fanout is not None:
        t, k = adj.size[1], adj.fanout
        src = src.reshape(t, k).T.reshape(-1)
        rel = rel.reshape(t, k).T.reshape(-1)
        ok = (src >= 0) & (rel >= 0) & jnp.tile(mask, k)
    else:
        ok = (src >= 0) & (dst >= 0) & (rel >= 0) & mask[jnp.clip(dst, 0)]
    return src, rel, ok


def project_by_relation(x, ws, src, rel, ok):
    """``([E, out] for each w of ws, rows, cap)``: each edge slot's
    source row ``x[src]`` by its own relation's weight ``w[rel]`` (``w``
    is ``[R, in, out]``, the ``out`` a ``w``'s own); a slot that is not
    ``ok`` gets a zero row, which nothing downstream weighs. ``rows`` of
    the layout's ``cap`` were multiplied.

    A grouped product in tiles of ``_tile_rows`` rows, each tile one
    relation's: ONE sort lays the slots out by relation (the others
    last), each relation's run followed by as many empty places as make
    it whole tiles. It sorts the slots together with ``tile`` spare
    entries a relation, of which the first ``tiles(r) * tile - size(r)``
    carry that relation and sort behind its slots, the rest sort last;
    so no place is gathered. The sources' rows are read in that layout,
    the tiles up to the end of the last relation's run are multiplied,
    each by its relation's weight picked inside the loop's turn
    (``live_rows_product``; the tiles past it hold only empty places and
    spare entries, and are not), and the products are read back in slot
    order: a slot's place is its relation's first tile plus its rank
    among the relation's slots (a cumsum a relation). The sort, the
    places and the row moves are ``qt_relation_route``, the products'
    loop, the weight picks in it included, ``qt_project``."""
    relations = ws[0].shape[0]
    n = src.shape[0]
    tile = _tile_rows(n)
    tiles = -(-n // tile) + relations
    spare = relations * tile
    with profiling.scope(profiling.QT_RELATION_ROUTE):
        key = jnp.where(ok, rel, relations)
        mine = [key == r for r in range(relations + 1)]
        sizes = jnp.stack([jnp.sum(m, dtype=jnp.int32) for m in mine])
        runs = -(-sizes[:relations] // tile)
        first = jnp.concatenate([jnp.cumsum(runs) - runs,
                                 jnp.sum(runs, keepdims=True)])
        # the spare entries: relation r's first runs(r) * tile - size(r)
        # pad its run, the others go last
        lane = jnp.arange(tile, dtype=jnp.int32)
        pad = runs * tile - sizes[:relations]
        spare_key = jnp.where(lane[None] < pad[:, None],
                              jnp.arange(relations, dtype=jnp.int32)[:, None],
                              relations + 1).reshape(-1)
        _, whose, sources = jax.lax.sort(
            (jnp.concatenate([key, spare_key]),
             jnp.arange(n + spare, dtype=jnp.int32),
             jnp.concatenate([jnp.where(ok, src, 0),
                              jnp.zeros((spare,), src.dtype)])),
            num_keys=2, is_stable=False)
        whose = jnp.minimum(whose, n)                  # a spare place: none
        back = jnp.zeros((n,), jnp.int32)
        for r, m in enumerate(mine):
            back = jnp.where(m, first[r] * tile + jnp.cumsum(
                m, dtype=jnp.int32) - 1, back)
        tail = tiles * tile - n - spare
        rows = x[jnp.pad(sources, (0, tail))]
        group = jnp.sum(jnp.arange(tiles, dtype=jnp.int32)[:, None]
                        >= (first[:relations] + runs)[None], axis=1,
                        dtype=jnp.int32)
        group = jnp.minimum(group, relations - 1)
    with profiling.scope(profiling.QT_PROJECT):
        products, used = live_rows_product(
            rows, ws, first[relations] * tile, tile, group)
    with profiling.scope(profiling.QT_RELATION_ROUTE):
        whose = jnp.pad(whose, (0, tail), constant_values=n)
        return ([_take_rows(m, back, whose) for m in products], used,
                tiles * tile)


def _attend_relations(p, src_term, dst_terms, rel, softmax, total):
    """One relational layer's attention, all heads at once. ``p``
    [slots..., heads x out] each slot's source row by its relation's
    weight and ``src_term`` [slots..., heads] its logit term;
    ``dst_terms`` [slots..., R, heads] (broadcast) the slot's target's
    logit term under each relation, of which the slot's relation picks
    one; ``softmax(logits) -> alpha`` within the (target, relation)
    groups, the heads on the minor axis; ``total`` sums the weighted rows
    into targets."""
    relations, heads = dst_terms.shape[-2:]
    nbr = p.reshape(p.shape[:-1] + (heads, -1))
    at_dst = dst_terms[..., 0, :]
    for r in range(1, relations):
        at_dst = jnp.where((rel == r)[..., None], dst_terms[..., r, :],
                           at_dst)
    y = total(nbr * softmax(src_term + at_dst)[..., None])
    return y.reshape(y.shape[:-2] + (-1,))


def _attend_typed(p, src_term, dst_terms, adj, slope: float):
    relations = dst_terms.shape[1]
    t = adj.size[1]
    if adj.fanout is not None:
        with profiling.scope(profiling.QT_ATTENTION_SLOTS):
            _, rel, ok = relation_slots(adj)
            shape = (adj.fanout, t)
            rel, ok = rel.reshape(shape), ok.reshape(shape)
            return _attend_relations(
                p.reshape(shape + p.shape[-1:]),
                src_term.reshape(shape + src_term.shape[-1:]),
                dst_terms[None], rel,
                lambda l: _group_softmax(l, ok[..., None], slope,
                                         rel[..., None], relations),
                lambda m: m.sum(axis=0))
    _, rel, ok = relation_slots(adj)
    d = jnp.where(ok, adj.edge_index[1], 0)
    safe = jnp.where(ok, rel, 0)
    return _attend_relations(
        p, src_term, dst_terms[d], rel,
        lambda l: segment_softmax(nn.leaky_relu(l, slope),
                                  d * relations + safe, t * relations,
                                  ok[:, None]),
        lambda m: jax.ops.segment_sum(m, d, num_segments=t))


def gat_attention(h, att_src, att_dst, adj, slope: float = 0.2):
    """``[T, heads x out]``: every valid target's attention-weighted sum
    of the projected rows ``h`` [S, heads x out] of its sampled neighbours
    and of itself (targets are the first ``adj.size[1]`` sources). Rows of
    target slots that hold no node come back zero.

    Over a typed ``adj`` (it states ``relation``): ``h`` [E, heads x out]
    is each edge slot's row by its relation's weight, in
    ``relation_slots``' order (``project_by_relation``), ``att_src`` each
    slot's logit term under its relation, ``[E, heads]`` in the same
    order, and ``att_dst`` each target's under each relation, ``[T, R,
    heads]``; no self edge, a softmax a (target, relation) group."""
    mask = adj.target_mask()
    with profiling.scope(profiling.QT_ATTENTION):
        if adj.relation is not None:
            return _attend_typed(h, att_src, att_dst, adj, slope)
        if adj.fanout is not None:
            with profiling.scope(profiling.QT_ATTENTION_SLOTS):
                return _attend_slots(h, att_src, att_dst, adj.edge_index[0],
                                     mask, adj.fanout, slope)
        return _attend_segments(h, att_src, att_dst, adj.edge_index, mask,
                                slope)


class _Kernel(nn.Module):
    """The projection's weight where ``nn.Dense`` keeps it
    (``{"kernel": [in, out]}``), for a product ``nn.Dense`` does not run."""

    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.glorot_uniform(),
                          self.shape)


class GATConv(nn.Module):
    """``conv(x, adj)``: ``x`` [S, in] are the layer's sources, its
    targets their first ``adj.size[1]`` rows. ``relations`` > 1 asks for
    the relational layer, which needs an ``Adj`` that states each slot's
    relation; an untyped ``Adj`` takes the one-relation layer."""

    out_dim: int
    heads: int = 1
    concat: bool = True
    negative_slope: float = 0.2
    relations: int = 1

    @nn.compact
    def __call__(self, x, adj):
        heads, out = self.heads, self.out_dim
        if adj.relation is not None or self.relations != 1:
            return self._typed(x, adj)
        kernel = _Kernel((x.shape[-1], heads * out), name="lin")()
        with profiling.scope(profiling.QT_PROJECT):
            # the rows past the last source and target are read by nothing
            (h,), rows = live_rows_product(x, (kernel,), live_sources(adj),
                                           _tile_rows(x.shape[0]))
        _sow_rows(self, rows, x.shape[0])
        att_src = self.param("att_src", nn.initializers.glorot_uniform(),
                             (heads, out))
        att_dst = self.param("att_dst", nn.initializers.glorot_uniform(),
                             (heads, out))
        bias = self.param("bias", nn.initializers.zeros,
                          (heads * out if self.concat else out,))
        y = gat_attention(h, att_src, att_dst, adj, self.negative_slope)
        if not self.concat:
            y = y.reshape(-1, heads, out).mean(axis=1)
        return y + bias

    def _typed(self, x, adj):
        """The relational layer (the module docstring's equations)."""
        heads, out, r = self.heads, self.out_dim, self.relations
        if adj.relations != r:
            raise ValueError(
                f"GATConv(relations={r}) over blocks of a graph with "
                + ("no relations: an untyped graph (a typed one is "
                   "ops.TypedIndices)" if adj.relations is None
                   else f"{adj.relations} relations"))
        glorot = nn.initializers.glorot_uniform(batch_axis=(0,))
        w = self.param("kernel", glorot, (r, x.shape[-1], heads * out))
        att_src = self.param("att_src", glorot, (r, heads, out))
        att_dst = self.param("att_dst", glorot, (r, heads, out))
        bias = self.param("bias", nn.initializers.zeros,
                          (r, heads * out if self.concat else out))
        src, rel, ok = relation_slots(adj)
        with profiling.scope(profiling.QT_PROJECT):
            # the logit terms x . (W_r a_r): [in, heads] a relation, no
            # [.., heads x out] row is read for them
            per_head = w.reshape(r, x.shape[-1], heads, out)
            v = jnp.einsum("rfho,rho->rfh", per_head, att_src)
            u = jnp.einsum("rfho,rho->frh", per_head, att_dst)
        # a slot's row and its source's term, each by the slot's relation
        (p, at_src), rows, cap = project_by_relation(x, (w, v), src, rel, ok)
        _sow_rows(self, rows, cap)
        with profiling.scope(profiling.QT_PROJECT):
            # the targets' terms under every relation
            dst = (x[:adj.size[1]] @ u.reshape(x.shape[-1], r * heads)
                   ).reshape(-1, r, heads)
        y = gat_attention(p, at_src, dst, adj, self.negative_slope)
        if not self.concat:
            y = y.reshape(-1, heads, out).mean(axis=1)
        # a relation's bias enters where the block holds an edge of it
        for i in range(r):
            y = y + jnp.where(jnp.any(ok & (rel == i)), bias[i], 0.0)
        return y


class GAT(nn.Module):
    hidden_dim: int
    out_dim: int
    num_layers: int
    heads: int = 4
    dropout: float = 0.5

    @nn.compact
    def __call__(self, x, adjs, *, train: bool = False):
        for i, adj in enumerate(adjs):
            last = i == self.num_layers - 1
            conv = GATConv(self.out_dim if last else self.hidden_dim,
                           heads=1 if last else self.heads,
                           concat=not last, name=f"conv{i}")
            x = conv(x, adj)
            if not last:
                x = nn.elu(x)
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return x
