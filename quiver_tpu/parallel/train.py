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
from ..profiling import hot_path
# the gathers and ``layers_to_adjs`` live with the walk; this module is
# where their callers have always found them
from .frontier import (ALL_KNOBS, SAMPLING_KNOBS, Walk,  # noqa: F401
                       dedup_feature_gather, documented, layers_to_adjs,
                       masked_feature_gather, walk_doc, walk_frontier)


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array


def cross_entropy_logits(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()


@hot_path
def _fused_loss(model, loss_fn, walk: Walk, batch_size, params, feat, forder,
                indptr, indices, seeds, labels, key, indices_rows=None,
                collector=None):
    """The loss of one batch: the walk (``walk_frontier``: sample, then
    the walk's gather, or the fused kernel), the model's forward under
    ``qt_forward``, ``loss_fn`` under ``qt_loss``. The dropout fold
    constant and the logits slice are THE shared definition — dist/DP
    loss parity depends on there being exactly one copy. ``collector``
    (a ``metrics.Collector``) opts into device-counter telemetry:
    sampling and the gather record counts they already compute; the loss
    itself is untouched (bit-identical with collection on or off)."""
    _, x, layers = walk_frontier(walk, feat, forder, indptr, indices, seeds,
                                 key, indices_rows, collector)
    adjs = layers_to_adjs(layers, batch_size, walk.sizes)
    with profiling.scope(profiling.QT_FORWARD):
        logits = model.apply(params, x, adjs, train=True,
                             rngs={"dropout": jax.random.fold_in(key, 1000)})
    with profiling.scope(profiling.QT_LOSS):
        return loss_fn(logits[:batch_size], labels)


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




def _jit_guarded(who: str, step, donate: bool):
    """``step`` jitted, its state donated behind the donation guard
    (``_check_donatable`` on the first call); carries ``.jitted_fns``."""
    jitted = jax.jit(step, donate_argnums=(0,) if donate else ())
    jitted.jitted_fns = (jitted,)
    if not donate:
        return jitted
    checked = set()

    def guarded(state, *args, **kwargs):
        _check_donatable(who, jitted, checked, state, *args, **kwargs)
        return jitted(state, *args, **kwargs)

    guarded.jitted_fns = (jitted,)
    return guarded


def _sharded_step(who: str, walk: Walk, loss, tx, mesh: Mesh, axis: str,
                  in_specs: Sequence, tail_specs: Sequence, donate: bool,
                  collect_metrics: bool, merge_counters: bool):
    """The ``shard_map`` shell of the data-parallel and the row-sharded
    train step. The jitted program's operands are ``(state, *operands,
    key[, indices_rows], *tail)``: ``in_specs`` places ``operands``,
    ``tail_specs`` the optional ``tail``; state, key and the rows view are
    replicated. Each shard folds its index into ``key``, takes
    ``loss(params, collector, key, indices_rows, *operands, *tail)``
    through ``_metered_loss_fn``, and gradients and loss are ``pmean``ed
    over ``axis``. Counters leave per shard (``[1, N]`` here, ``[shards,
    N]`` outside) or, with ``merge_counters``, folded on device into ONE
    global ``[N]`` vector (``metrics.pmerge_counters``: psum/pmax slot
    semantics), which every host can read.

    Returns ``run(state, operands, indices_rows, tail=())``, with
    ``.jitted_fns = (with rows, without)``: a shard_map's arity is fixed
    when it is built and ``"exact"`` may or may not bring a rows view, so
    both are built; jit compiles lazily, the unused one costs nothing."""
    if merge_counters and not collect_metrics:
        raise ValueError("merge_counters=True requires "
                         "collect_metrics=True")
    n = len(in_specs)

    def per_shard_of(has_rows):
        def per_shard(state: TrainState, *ops):
            key = jax.random.fold_in(ops[n], jax.lax.axis_index(axis))
            rows = ops[n + 1] if has_rows else None
            rest = ops[:n] + ops[n + 1 + has_rows:]
            loss_of, unpack = _metered_loss_fn(
                collect_metrics,
                lambda p, col: loss(p, col, key, rows, *rest))
            value, counters, grads = unpack(loss_of(state.params))
            new_state, value = _pmean_update(state, tx, grads, value, axis)
            if not collect_metrics:
                return new_state, value
            if merge_counters:
                from ..metrics import pmerge_counters
                return new_state, value, pmerge_counters(counters, axis)
            return new_state, value, counters[None]
        return per_shard

    if collect_metrics:
        outs = (P(), P(), P() if merge_counters else P(axis))
    else:
        outs = (P(), P())
    jitted = {
        has_rows: jax.jit(shard_map(
            per_shard_of(has_rows), mesh=mesh,
            in_specs=(P(), *in_specs, *[P()] * (1 + has_rows), *tail_specs),
            out_specs=outs, check_vma=False),
            donate_argnums=(0,) if donate else ())
        for has_rows in (True, False)}
    checked = set()

    def run(state, operands, indices_rows, tail=()):
        # asked up front, so that a mismatch is a clear TypeError and
        # not an opaque shard_map/jit arity failure
        walk.check_rows(indices_rows)
        fn = jitted[indices_rows is not None]
        if indices_rows is not None:
            operands += (indices_rows,)
        if donate:
            _check_donatable(who, fn, checked, state, *operands, *tail)
        return fn(state, *operands, *tail)

    run.jitted_fns = tuple(jitted.values())
    return run


@documented(walk_doc(ALL_KNOBS), _DONATED_DOC, _COLLECT_DOC)
def build_train_step(model, tx, sizes: Sequence[int], batch_size: int,
                     loss_fn: Callable = cross_entropy_logits,
                     donate: bool = True, collect_metrics: bool = False,
                     gather: Callable | None = None, **walk):
    """Single-chip fused step:
    fn(state, feat, forder, indptr, indices, seeds, labels, key[,
    indices_rows]) -> (state, loss). ``feat`` may be a quantized store
    (``ops.quant.quantize(feat, "int8"|"bf16")``): dequant fuses into
    the gather and the model consumes float activations unchanged.
    ``gather`` overrides the whole gather callable, exactly as
    ``build_serve_step``'s does (the protocol of ``parallel.frontier``;
    it wins over ``dedup_gather``): ``frontier.feature_splice(store)``
    gives ``(feat, forder, gather)`` of a ``Feature`` store, so a table
    larger than the chip's memory trains through this step with its
    cold rows read from pinned host memory inside the one program;
    ``feat`` is then the ``(device_part, host_tier)`` pair."""
    walk = Walk.of("build_train_step", ALL_KNOBS, sizes, walk,
                   gather=gather)

    def step(state: TrainState, feat, forder, indptr, indices, seeds,
             labels, key, indices_rows=None):
        loss_of, unpack = _metered_loss_fn(
            collect_metrics,
            lambda p, col: _fused_loss(model, loss_fn, walk, batch_size, p,
                                       feat, forder, indptr, indices, seeds,
                                       labels, key, indices_rows, col))
        loss, counters, grads = unpack(loss_of(state.params))
        new_state = _apply_update(state, tx, grads)
        if collect_metrics:
            return new_state, loss, counters
        return new_state, loss

    return _jit_guarded("build_train_step", step, donate)


@documented(walk_doc(ALL_KNOBS), _DONATED_DOC, _COLLECT_DOC)
def build_e2e_train_step(model, tx, sizes: Sequence[int],
                         per_device_batch: int, mesh: Mesh,
                         axis: str = "data",
                         loss_fn: Callable = cross_entropy_logits,
                         donate: bool = True, collect_metrics: bool = False,
                         merge_counters: bool = False, **walk):
    """Data-parallel fused step over ``mesh[axis]``:
    fn(state, feat, forder, indptr, indices, seeds, labels, key[,
    indices_rows]) with seeds/labels [n_dev * per_device_batch] sharded
    over ``axis``; state/feat/topology (and the rows view) replicated;
    grads pmean over ``axis``; the per-shard key fold keeps shards on
    distinct streams. ``feat`` may be a quantized store (``ops.quant``) —
    the P() spec broadcasts over its leaves as a pytree prefix."""
    who = "build_e2e_train_step"
    walk = Walk.of(who, ALL_KNOBS, sizes, walk)

    def loss(p, col, key, rows, feat, forder, indptr, indices, seeds,
             labels):
        return _fused_loss(model, loss_fn, walk, per_device_batch, p, feat,
                           forder, indptr, indices, seeds, labels, key,
                           rows, col)

    run = _sharded_step(who, walk, loss, tx, mesh, axis,
                        (P(), P(), P(), P(), P(axis), P(axis)), (), donate,
                        collect_metrics, merge_counters)

    def step(state, feat, forder, indptr, indices, seeds, labels, key,
             indices_rows=None):
        return run(state, (feat, forder, indptr, indices, seeds, labels,
                           key), indices_rows)

    step.jitted_fns = run.jitted_fns
    return step


@documented(walk_doc(SAMPLING_KNOBS), _DONATED_DOC)
def build_split_train_step(model, tx, sizes: Sequence[int], batch_size: int,
                           loss_fn: Callable = cross_entropy_logits,
                           donate: bool = True, **walk):
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
    are reused across steps, so nothing there is donatable. No
    ``collect_metrics``: its stages are driven from the host, where
    ``StepStats`` times them directly."""
    # the rows are the caller's to fetch: the walk gathers nothing
    walk = Walk.of("build_split_train_step", SAMPLING_KNOBS, sizes, walk,
                   gather=lambda feat, n_id, forder, collector=None: None)

    @jax.jit
    def sample_fn(indptr, indices, seeds, key, indices_rows=None):
        n_id, _, layers = walk_frontier(walk, None, None, indptr, indices,
                                        seeds, key, indices_rows)
        return n_id, layers_to_adjs(layers, batch_size, walk.sizes)

    def step_fn_raw(state: TrainState, x, adjs, labels, key):
        def loss_of(p):
            with profiling.scope(profiling.QT_FORWARD):
                logits = model.apply(p, x, adjs, train=True,
                                     rngs={"dropout": key})
            with profiling.scope(profiling.QT_LOSS):
                return loss_fn(logits[:batch_size], labels)

        loss, grads = jax.value_and_grad(loss_of)(state.params)
        return _apply_update(state, tx, grads), loss

    return sample_fn, _jit_guarded("build_split_train_step", step_fn_raw,
                                   donate)


def init_state(model, tx, example_x, example_adjs, key) -> TrainState:
    params = model.init(key, example_x, example_adjs)
    return TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
