"""End-to-end training steps: sample -> gather -> forward/backward -> update
as one XLA program, data-parallel over a mesh.

This replaces the reference's DDP story (survey §2.3: vanilla torch DDP
around Quiver components, per-rank python processes + CUDA-IPC handles,
NCCL allreduce). TPU-native: ONE process per host, `shard_map` over the
``data`` mesh axis; every chip samples its own seed shard, gathers
features, and gradients are `pmean`ed over ICI — no IPC, no NCCL
bootstrap, no per-GPU processes.

Graph topology, the feature array, and the optional hot-order permutation
are explicit arguments of the returned step functions (not closures), so
the same compiled program serves any same-shape graph and nothing large is
baked into the executable as a constant.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .. import profiling
from ..ops.sample_multihop import sample_multihop
from ..profiling import hot_path
from ..pyg.sage_sampler import Adj, layer_shapes


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array


def cross_entropy_logits(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()


def layers_to_adjs(layers, batch_size: int, sizes: Sequence[int]):
    """LayerSamples (sampling order) -> Adj list (outermost hop first).

    Precondition: every layer is a ``compact_layer`` output over dense
    seeds (``seeds_dense=True``'s promise: the hop-0 batch is valid-first
    with -1 at the tail only; hops >= 1 always are). A valid seed's local
    id is then its position, so edge slot ``e`` targets ``e // fanout``
    or nothing, which is what each ``Adj.fanout`` set here states. A
    caller that cannot promise it builds its ``Adj``s without a fanout."""
    shapes = layer_shapes(batch_size, sizes)
    adjs = []
    for layer, shape in zip(layers, shapes):
        adjs.append(Adj(edge_index=jnp.stack([layer.col, layer.row]),
                        e_id=layer.e_id,
                        size=(shape.n_id_cap, shape.num_seeds),
                        mask=layer.col >= 0, fanout=shape.fanout))
    return adjs[::-1]


@hot_path
def masked_feature_gather(feat, n_id: jax.Array,
                          feature_order=None,
                          collector=None) -> jax.Array:
    """Feature rows for a -1-padded frontier, through the optional
    hot-order indirection (reference feature.py:296-301); padded rows
    come back zeroed so aggregation stays exact. ``feat`` may be a
    plain array or a quantized store (``ops.quant`` — e.g.
    ``quant.quantize(feat, "int8")``): dequantization fuses into the
    gather, so the step reads narrow rows + sidecars and the model
    consumes float activations unchanged. ``collector`` is accepted for
    gather-protocol uniformity (single-tier: nothing tiered to count)."""
    from ..ops import quant
    with profiling.scope(profiling.QT_GATHER):
        ids = n_id
        if feature_order is not None:
            ids = feature_order[jnp.clip(n_id, 0)]
        safe = jnp.clip(ids, 0, quant.tier_rows(feat) - 1)
        x = quant.gather_rows(feat, safe)
        return x * (n_id >= 0).astype(x.dtype)[:, None]


@hot_path
def dedup_feature_gather(feat, n_id: jax.Array,
                         feature_order=None,
                         budget: int | None = None,
                         collector=None) -> jax.Array:
    """``masked_feature_gather`` reading each distinct valid id ONCE:
    the frontier's -1 padding (the bulk of a static multi-hop cap) and
    any repeated ids collapse into a static-``budget`` unique table,
    the feature read is one [budget, dim] gather, and positions expand
    from it. Falls back to the plain full gather via ``lax.cond`` when
    the unique count overflows — identical output in every case.
    Default budget: ``max(len(n_id)//4, 256)``."""
    from ..ops.dedup import unique_within_budget
    from ..ops.quant import default_cold_budget
    n = n_id.shape[0]
    if budget is None:
        budget = default_cold_budget(n)
    if budget >= n:
        return masked_feature_gather(feat, n_id, feature_order)
    valid = n_id >= 0

    def narrow(_):
        # uniq's int32-max fill clips to the LAST feature row — those
        # slots hold real (unused) data, NOT zeros: inv never points a
        # valid position at them, and invalid positions carry in-range-
        # garbage inv that the re-mask below zeroes
        rows_u = masked_feature_gather(feat, uniq, feature_order)
        x = jnp.take(rows_u, inv, axis=0)
        return x * valid.astype(x.dtype)[:, None]

    # one scope over the unique table, both reads and the expansion
    with profiling.scope(profiling.QT_GATHER):
        uniq, inv, n_uniq = unique_within_budget(n_id, budget, valid=valid,
                                                 collector=collector)
        return jax.lax.cond(n_uniq > budget,
                            lambda _: masked_feature_gather(feat, n_id,
                                                            feature_order),
                            narrow, None)


def _fused_multihop_x(feat, forder, indptr, indices, seeds, sizes, key,
                      row_cap=2048, rng=None, interpret=None,
                      hot_rows=None, collector=None):
    """The fused frontier walk (``ops.pallas.fused.fused_multihop``):
    interior hops run the sampling-only fused kernel (in-kernel indptr
    resolution), the leaf hop samples AND gathers in one kernel, and
    the gather-free compaction chains them — frontier ids live only in
    VMEM/SMEM at every hop, so the step's modeled
    ``gather_index_bytes`` is zero across the whole ladder. The layer
    COOs and the ``[cap, dim]`` frontier block come back bit-identical
    to ``masked_feature_gather(feat, n_id, forder)`` over the same
    picks (valid slots).

    The sampling PRNG is the KERNEL's stream (hop ``i`` seeded from
    ``fold_in(key, i)``), not ``jax.random`` — losses are
    bit-comparable with the split Pallas oracle
    (``ops.pallas.fused.fused_multihop_reference``), not with the
    ``sample_multihop`` path. A 1-hop ``sizes`` reduces exactly to the
    qt-fuse single-hop behavior. ``hot_rows`` zeroes rows whose
    (``forder``-translated) storage row falls outside the hot tier;
    callers with a cold tier overlay exactly those slots afterwards
    (the serve step's tiered fixup)."""
    from ..ops.pallas.fused import fused_multihop, pad_indices
    n_id, layers, x = fused_multihop(
        indptr, pad_indices(indices, row_cap), seeds, feat, list(sizes),
        key, row_cap=row_cap, rng=rng, interpret=interpret,
        feature_order=forder, hot_rows=hot_rows)
    if collector is not None:
        from ..metrics import FRONTIER_CAP, FRONTIER_VALID
        collector.add(FRONTIER_VALID, jnp.sum(n_id >= 0))
        collector.add(FRONTIER_CAP, int(n_id.shape[0]))
    return x, layers


def _fused_knobs(enabled, row_cap, rng, interpret, sizes, method,
                 dedup_gather=None, indices_stride=None, hub_frac=None):
    """Validate + pack the ``fused_hot_hop`` builder knobs (shared by
    the train and serve builders). The fused walk covers any
    exact-method fanout ladder (qt-fuse-deep) and does its own
    in-kernel gather, so the knob composes with nothing that reshapes
    sampling or the gather."""
    if not enabled:
        return None
    if not sizes:
        raise ValueError("fused_hot_hop needs at least one hop in sizes")
    if method != "exact":
        raise ValueError(
            f"fused_hot_hop requires method='exact', got {method!r}")
    if dedup_gather is not None:
        raise ValueError(
            "fused_hot_hop gathers in-kernel (one DMA per frontier "
            "slot); dedup_gather does not compose with it")
    if indices_stride is not None or hub_frac is not None:
        raise ValueError(
            "fused_hot_hop takes neither indices_stride nor hub_frac "
            "(no wide-exact/rotation layout views in the fused kernel)")
    return {"row_cap": int(row_cap), "rng": rng, "interpret": interpret}


@hot_path
def _fused_loss(model, loss_fn, sizes, batch_size, params, feat, forder,
                indptr, indices, seeds, labels, key, method="exact",
                indices_rows=None, indices_stride=None, gather=None,
                hub_frac=None, collector=None, fused=None):
    """``gather(feat, n_id, forder, collector=None)`` defaults to the
    local ``masked_feature_gather``; the multi-host fused step
    substitutes the partitioned all_to_all lookup. Everything else
    (sampling keys, the dropout fold constant, the logits slice) is THE
    shared definition — dist/DP loss parity depends on there being
    exactly one copy. ``collector`` (a ``metrics.Collector``) opts into
    device-counter telemetry: sampling and the gather record counts
    they already compute; the loss itself is untouched (bit-identical
    with collection on or off).

    Batch contract: ``seeds`` must be distinct valid ids with -1 padding
    at the TAIL only. That was always required here — ``labels`` are
    indexed by batch position while interior holes would shift seeds to
    rank-based output rows, silently misaligning the loss — so hop 0
    also takes the cheaper dense-seed compaction path.

    ``fused`` (the packed ``fused_hot_hop`` builder knobs, see
    ``_fused_knobs``) swaps the sample->gather pair for the fused
    Pallas walk (``_fused_multihop_x``) — frontier ids stay on chip at
    EVERY hop; everything from the frontier block on is unchanged."""
    if fused is not None:
        if indices_rows is not None:
            raise TypeError(
                "fused_hot_hop does not take indices_rows (the fused "
                "walk does its own in-kernel CSR reads every hop)")
        x, layers = _fused_multihop_x(feat, forder, indptr, indices,
                                      seeds, sizes, key,
                                      collector=collector, **fused)
    else:
        n_id, layers = sample_multihop(
            indptr, indices, seeds, sizes, key, method=method,
            indices_rows=indices_rows, indices_stride=indices_stride,
            seeds_dense=True, hub_frac=hub_frac, collector=collector)
        x = (gather or masked_feature_gather)(feat, n_id, forder,
                                              collector=collector)
    adjs = layers_to_adjs(layers, batch_size, sizes)
    with profiling.scope(profiling.QT_FORWARD):
        logits = model.apply(params, x, adjs, train=True,
                             rngs={"dropout": jax.random.fold_in(key, 1000)})
    with profiling.scope(profiling.QT_LOSS):
        return loss_fn(logits[:batch_size], labels)


def _check_rows(method: str, indices_rows, kind: str) -> bool:
    """Shared indices_rows contract for the step builders: rotation and
    window REQUIRE the per-epoch shuffled view (as_index_rows /
    as_index_rows_overlapping; refresh via permute_csr). exact
    OPTIONALLY takes a layout view of the UN-shuffled indices — that
    switches the scattered draw to the wide-fetch exact path
    (``sample_layer_exact_wide``; same i.i.d. statistics, fewer
    scattered loads). Returns whether the method is windowed."""
    windowed = method in ("rotation", "window")
    if windowed and indices_rows is None:
        raise TypeError(
            f"{method} {kind} step requires indices_rows (the shuffled "
            "as_index_rows/as_index_rows_overlapping view; refresh per "
            "epoch via permute_csr)")
    return windowed


def _apply_update(state, tx, grads) -> TrainState:
    """The optimizer's step, shared by every builder."""
    with profiling.scope(profiling.QT_OPTIMIZER):
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
    return TrainState(params, opt_state, state.step + 1)


def _pmean_update(state, tx, grads, loss, axis):
    """Cross-shard gradient/loss reduction + optimizer update (shared by
    the shard_map builders)."""
    grads = jax.lax.pmean(grads, axis)
    loss = jax.lax.pmean(loss, axis)
    return _apply_update(state, tx, grads), loss


def _check_donatable(kind, fn, checked, state, *args, **kwargs):
    """Pre-flight guard for donated ``TrainState`` args: XLA quietly
    falls back to a COPY when a donated buffer can't be reused because
    the returned state's shape/dtype/structure drifted (e.g. an optax
    chain that changes a moment's dtype) — the donation "works" but
    every step still reallocates. Trace the step abstractly on the
    FIRST call per jitted fn and fail loudly on any drift. Single-shot
    by design: once the in/out specs match, every later state IS a
    prior output (same specs by induction), so the steady-state cost
    is one O(1) set lookup, not a per-step pytree walk."""
    if id(fn) in checked:
        return
    out_state = jax.eval_shape(fn, state, *args, **kwargs)[0]
    flat_in, tree_in = jax.tree_util.tree_flatten_with_path(state)
    flat_out, tree_out = jax.tree_util.tree_flatten_with_path(out_state)
    if tree_in != tree_out:
        raise ValueError(
            f"{kind}: donated TrainState changes pytree structure "
            f"across the step ({tree_in} -> {tree_out}); donation would "
            "silently copy every buffer. Fix the model/optimizer to "
            "return the same structure, or pass donate=False.")
    bad = [
        (jax.tree_util.keystr(p_in),
         (tuple(jnp.shape(a)), str(jnp.result_type(a))),
         (tuple(b.shape), str(b.dtype)))
        for (p_in, a), (_, b) in zip(flat_in, flat_out)
        if tuple(jnp.shape(a)) != tuple(b.shape)
        or jnp.result_type(a) != b.dtype]
    if bad:
        detail = "; ".join(f"{p}: {i} -> {o}" for p, i, o in bad[:4])
        raise ValueError(
            f"{kind}: donated TrainState leaves change shape/dtype "
            f"across the step ({detail}) — XLA cannot reuse the donated "
            "buffers and would silently copy them every step. Make the "
            "step shape/dtype-stable, or pass donate=False.")
    checked.add(id(fn))


_DONATED_DOC = """

    ``donate=True`` (default) donates the ``state`` argument's buffers
    to the step: the update writes in place instead of reallocating the
    full model+optimizer state every step. The INPUT state is dead
    after the call — use the returned state, and pass ``donate=False``
    when a caller genuinely needs to reuse one state across several
    step calls (A/B parity comparisons). A shape/dtype guard traces the
    step abstractly on first use and raises a clear error if the state
    drifts across the step (which would turn donation into a silent
    per-step copy)."""


def _dedup_gather_fn(dedup_gather):
    """``dedup_gather`` knob -> the gather callable ``_fused_loss``
    takes (None keeps the plain masked gather)."""
    if dedup_gather is None:
        return None
    budget = None if dedup_gather is True else int(dedup_gather)
    return lambda feat, n_id, forder, collector=None: dedup_feature_gather(
        feat, n_id, forder, budget, collector=collector)


def _metered_loss_fn(collect: bool, loss_with_collector):
    """Shared value_and_grad plumbing for the ``collect_metrics`` knob:
    ``loss_with_collector(params, collector_or_None)`` is the loss;
    with collection on, a fresh ``metrics.Collector`` is created INSIDE
    the traced function (a collector outliving a trace would leak stale
    tracers into the next one) and its counter vector rides out as
    ``has_aux`` — differentiation sees the identical loss either way.
    Returns ``(loss_of, unpack)`` with
    ``unpack(loss_of(p)) == (loss, counters_or_None, grads)``."""
    if collect:
        from ..metrics import Collector

        def loss_of(p):
            col = Collector()
            return loss_with_collector(p, col), col.counters()

        vg = jax.value_and_grad(loss_of, has_aux=True)
        return vg, lambda out: (out[0][0], out[0][1], out[1])
    vg = jax.value_and_grad(lambda p: loss_with_collector(p, None))
    return vg, lambda out: (out[0], None, out[1])


_COLLECT_DOC = """

    ``collect_metrics=True`` adds ONE auxiliary output to the step — a
    ``metrics.NUM_COUNTERS`` int32 device counter vector (per-shard
    ``[shards, N]`` from the shard_map builders) carrying the observed
    frontier fill, dedup/dup statistics and exchange branch behavior.
    Counters accumulate with pure jnp ops on values the hot path
    already computes: zero host syncs per step, ``lax.cond``
    predicates untouched, losses bit-identical to the metrics-off step,
    donation intact. Feed the vectors to ``metrics.StepStats`` or a
    ``telemetry.TelemetryHub``. The returned step exposes
    ``.jitted_fns`` (the underlying jitted callables) for
    ``StepStats.watch_compiles``. Shard_map builders additionally take
    ``merge_counters=True``: the per-shard block is folded over the
    mesh axis ON DEVICE (``metrics.pmerge_counters`` — psum add slots,
    pmax max slots) and the step returns one replicated global ``[N]``
    vector — on a real multi-host mesh each process can only address
    its own shard of the per-shard output, so this is how every host
    observes the global picture. Losses stay bit-identical with the
    merge on or off."""


def build_train_step(model, tx, sizes: Sequence[int], batch_size: int,
                     loss_fn: Callable = cross_entropy_logits,
                     method: str = "exact",
                     indices_stride: int | None = None,
                     hub_frac: float | None = None,
                     donate: bool = True,
                     dedup_gather=None,
                     collect_metrics: bool = False,
                     fused_hot_hop: bool = False,
                     fused_row_cap: int = 2048,
                     fused_rng: str | None = None,
                     fused_interpret: bool | None = None):
    """Single-chip fused step:
    fn(state, feat, forder, indptr, indices, seeds, labels, key[,
    indices_rows]). With ``method="rotation"`` pass the shuffled
    ``as_index_rows`` view as ``indices_rows`` (refresh per epoch with
    ``reshuffle_csr`` — exact sort or cheap butterfly) — or, with
    ``indices_stride=128``, the
    ``as_index_rows_overlapping`` view (one row gather per seed, 2x
    index memory). With ``method="exact"`` + an un-shuffled layout view
    as ``indices_rows``, pass ``hub_frac`` (the cached
    ``CSRTopo.exact_bucket_meta().frac``) so the wide-exact hub budget
    is sized from the graph's degree-bucket split. ``dedup_gather``
    (True or an int unique budget) swaps the frontier feature gather
    for ``dedup_feature_gather`` — one read per distinct node instead
    of per frontier slot. ``feat`` may be a quantized store
    (``ops.quant.quantize(feat, "int8"|"bf16")``): dequant fuses into
    the gather and the model consumes float activations unchanged.

    ``fused_hot_hop=True`` (any ``sizes`` ladder, ``method="exact"``
    only) swaps the sample->gather pair for the fused Pallas walk
    (``ops.pallas.fused.fused_multihop``): interior hops run the
    sampling-only fused kernel, the leaf hop fuses reservoir sampling
    with the per-pick feature-row DMA (int8 dequant applied
    in-register), and frontier ids never materialize in HBM at ANY hop
    — the step's modeled ``gather_index_bytes`` is zero across the
    whole ladder. ``fused_row_cap`` bounds the in-VMEM CSR window per
    seed (degrees beyond it are truncated — the sample kernel's
    contract); ``fused_rng``/``fused_interpret`` default to the
    backend-appropriate choices ("tpu" PRNG on TPU, portable "hash" +
    interpret mode elsewhere). The fused step's sampling stream is the
    kernel PRNG (hop ``i`` seeded from ``fold_in(key, i)``), so losses
    are not bit-comparable with the split step — only with the split
    Pallas oracle (``ops.pallas.fused.fused_multihop_reference``)."""
    sizes = list(sizes)
    gather = _dedup_gather_fn(dedup_gather)
    fused = _fused_knobs(fused_hot_hop, fused_row_cap, fused_rng,
                         fused_interpret, sizes, method,
                         dedup_gather=dedup_gather,
                         indices_stride=indices_stride,
                         hub_frac=hub_frac)

    def step(state: TrainState, feat, forder, indptr, indices, seeds,
             labels, key, indices_rows=None):
        loss_of, unpack = _metered_loss_fn(
            collect_metrics,
            lambda p, col: _fused_loss(model, loss_fn, sizes, batch_size,
                                       p, feat, forder, indptr, indices,
                                       seeds, labels, key, method,
                                       indices_rows, indices_stride,
                                       gather=gather, hub_frac=hub_frac,
                                       collector=col, fused=fused))
        loss, counters, grads = unpack(loss_of(state.params))
        new_state = _apply_update(state, tx, grads)
        if collect_metrics:
            return new_state, loss, counters
        return new_state, loss

    jitted = jax.jit(step, donate_argnums=(0,) if donate else ())
    jitted.jitted_fns = (jitted,)
    if not donate:
        return jitted
    checked = set()

    def guarded(state, *args, **kwargs):
        _check_donatable("build_train_step", jitted, checked, state,
                         *args, **kwargs)
        return jitted(state, *args, **kwargs)

    guarded.jitted_fns = (jitted,)
    return guarded


def build_e2e_train_step(model, tx, sizes: Sequence[int],
                         per_device_batch: int, mesh: Mesh,
                         axis: str = "data",
                         loss_fn: Callable = cross_entropy_logits,
                         method: str = "exact",
                         indices_stride: int | None = None,
                         hub_frac: float | None = None,
                         donate: bool = True,
                         dedup_gather=None,
                         collect_metrics: bool = False,
                         merge_counters: bool = False,
                         fused_hot_hop: bool = False,
                         fused_row_cap: int = 2048,
                         fused_rng: str | None = None,
                         fused_interpret: bool | None = None):
    """Data-parallel fused step over ``mesh[axis]``:
    fn(state, feat, forder, indptr, indices, seeds, labels, key[,
    indices_rows]) with seeds/labels [n_dev * per_device_batch] sharded
    over ``axis``; state/feat/topology (and the shuffled rows view when
    ``method="rotation"``) replicated; grads pmean over ``axis``.
    ``indices_stride=128`` switches ``indices_rows`` to the
    ``as_index_rows_overlapping`` layout (one row gather per seed).
    ``hub_frac`` (cached ``CSRTopo.exact_bucket_meta().frac``) sizes the
    wide-exact hub budget when exact mode gets an ``indices_rows``.
    ``dedup_gather`` (True or an int unique budget) swaps each shard's
    frontier feature gather for ``dedup_feature_gather``. ``feat`` may
    be a quantized store (``ops.quant``) — the P() spec broadcasts
    over its leaves as a pytree prefix.

    ``fused_hot_hop=True`` swaps each shard's sample->gather pair for
    the fused Pallas walk (``ops.pallas.fused.fused_multihop``) with
    the same contract as ``build_train_step``: exact method, any
    ``sizes`` ladder, zero modeled ``gather_index_bytes`` per shard;
    the per-shard key fold keeps shards on distinct kernel streams."""
    sizes = list(sizes)
    gather = _dedup_gather_fn(dedup_gather)
    fused = _fused_knobs(fused_hot_hop, fused_row_cap, fused_rng,
                         fused_interpret, sizes, method,
                         dedup_gather=dedup_gather,
                         indices_stride=indices_stride,
                         hub_frac=hub_frac)
    if merge_counters and not collect_metrics:
        raise ValueError("merge_counters=True requires "
                         "collect_metrics=True")

    def per_shard(state: TrainState, feat, forder, indptr, indices, seeds,
                  labels, key, indices_rows=None):
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
        loss_of, unpack = _metered_loss_fn(
            collect_metrics,
            lambda p, col: _fused_loss(model, loss_fn, sizes,
                                       per_device_batch, p, feat, forder,
                                       indptr, indices, seeds, labels, key,
                                       method, indices_rows, indices_stride,
                                       gather=gather, hub_frac=hub_frac,
                                       collector=col, fused=fused))
        loss, counters, grads = unpack(loss_of(state.params))
        new_state, loss = _pmean_update(state, tx, grads, loss, axis)
        if collect_metrics:
            if merge_counters:
                # device-side cross-shard fold (psum/pmax slot
                # semantics): the step emits ONE global [N] vector
                from ..metrics import pmerge_counters
                return new_state, loss, pmerge_counters(counters, axis)
            # per-shard counters, [1, N] here -> [n_dev, N] outside
            return new_state, loss, counters[None]
        return new_state, loss

    specs = [P(), P(), P(), P(), P(), P(axis), P(axis), P()]
    if collect_metrics:
        outs = (P(), P(), P() if merge_counters else P(axis))
    else:
        outs = (P(), P())
    # shard_map arity is fixed at build time, but exact may or may not
    # bring the (optional) wide-path rows view — build both arities; jit
    # compiles lazily so the unused one costs nothing
    with_rows = shard_map(
        per_shard, mesh=mesh,
        in_specs=tuple(specs + [P()]),   # indices_rows, replicated
        out_specs=outs,
        check_vma=False)
    without_rows = shard_map(
        per_shard, mesh=mesh,
        in_specs=tuple(specs),
        out_specs=outs,
        check_vma=False)
    dn = (0,) if donate else ()
    jitted_rows = jax.jit(with_rows, donate_argnums=dn)
    jitted = jax.jit(without_rows, donate_argnums=dn)
    checked = set()

    # validate the optional arg up front so a mismatch is a clear
    # TypeError, not an opaque shard_map/jit arity failure
    def step(state, feat, forder, indptr, indices, seeds, labels, key,
             indices_rows=None):
        _check_rows(method, indices_rows, "e2e")
        if indices_rows is not None:
            args = (feat, forder, indptr, indices, seeds, labels, key,
                    indices_rows)
            fn = jitted_rows
        else:
            args = (feat, forder, indptr, indices, seeds, labels, key)
            fn = jitted
        if donate:
            _check_donatable("build_e2e_train_step", fn, checked, state,
                             *args)
        return fn(state, *args)

    step.jitted_fns = (jitted_rows, jitted)
    return step


def build_split_train_step(model, tx, sizes: Sequence[int], batch_size: int,
                           loss_fn: Callable = cross_entropy_logits,
                           method: str = "exact",
                           indices_stride: int | None = None,
                           hub_frac: float | None = None,
                           donate: bool = True):
    """Two-phase step for tiered feature stores (the reference's own
    architecture: sampling and feature collection run as separate stages
    around the model, examples/pyg/reddit_quiver.py:116-122):

      sample_fn(indptr, indices, seeds, key[, indices_rows]) -> (n_id, adjs)
      step_fn(state, x, adjs, labels, key) -> (state, loss)

    Use when features live partly on host/disk: sample on device, fetch
    ``x = feature[n_id]`` through the tiered store (give the store
    ``dedup_cold=True`` so the host tier is read once per unique cold
    node; pair with ``Feature.prefetch`` / ``quiver_tpu.pipeline`` so
    batch i+1's staging overlaps step i), then run the fused
    forward/backward/update. ``sample_fn``'s inputs (topology, seeds)
    are reused across steps, so nothing there is donatable.
    """
    sizes = list(sizes)

    @jax.jit
    def sample_fn(indptr, indices, seeds, key, indices_rows=None):
        # same batch contract as _fused_loss: distinct valid ids,
        # -1 padding at the tail only (labels are position-indexed)
        n_id, layers = sample_multihop(
            indptr, indices, seeds, sizes, key, method=method,
            indices_rows=indices_rows,
            indices_stride=indices_stride if indices_rows is not None
            else None, seeds_dense=True, hub_frac=hub_frac)
        return n_id, layers_to_adjs(layers, batch_size, sizes)

    def step_fn_raw(state: TrainState, x, adjs, labels, key):
        def loss_of(p):
            with profiling.scope(profiling.QT_FORWARD):
                logits = model.apply(p, x, adjs, train=True,
                                     rngs={"dropout": key})
            with profiling.scope(profiling.QT_LOSS):
                return loss_fn(logits[:batch_size], labels)

        loss, grads = jax.value_and_grad(loss_of)(state.params)
        return _apply_update(state, tx, grads), loss

    jitted = jax.jit(step_fn_raw, donate_argnums=(0,) if donate else ())
    if not donate:
        return sample_fn, jitted
    checked = set()

    def step_fn(state, *args, **kwargs):
        _check_donatable("build_split_train_step", jitted, checked, state,
                         *args, **kwargs)
        return jitted(state, *args, **kwargs)

    return sample_fn, step_fn


def init_state(model, tx, example_x, example_adjs, key) -> TrainState:
    params = model.init(key, example_x, example_adjs)
    return TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))


# the donation contract is identical across the step builders — stamp
# it onto each docstring once instead of drifting three copies
# (guarded: under python -OO docstrings are None)
for _b in (build_train_step, build_e2e_train_step, build_split_train_step):
    if _b.__doc__:
        _b.__doc__ += _DONATED_DOC
# likewise for the collect_metrics contract (split step: no knob — its
# stages are driven from the host, where StepStats times them directly)
for _b in (build_train_step, build_e2e_train_step):
    if _b.__doc__:
        _b.__doc__ += _COLLECT_DOC
del _b
