"""Device-side naming hooks.

Replaces the reference's compile-time ``TRACE_SCOPE`` macros
(trace.hpp:1-14, enabled via QUIVER_ENABLE_TRACE + stdtracer
FetchContent) with jax's built-in profiler: a named scope lands in
every instruction's ``op_name``, which is how an XProf trace (and
``chipbench/trace.py``) attributes device time to a stage. Capture a
profile with ``jax.profiler.trace(log_dir)``; time a HOST block with
``quiver_tpu.tracing.stage``.
"""

from __future__ import annotations

import jax

# named scope: annotates ops for the profiler (the TRACE_SCOPE
# equivalent). The step builders reach it as ``profiling.scope`` so a
# test can swap it for a null context and show that nothing but names
# changes.
scope = jax.named_scope

# the scopes of the fused steps, in one place. ``qt_draw``/``qt_compact``
# sit beneath ``qt_sample_hop{i}`` (ops/sample_multihop.py), which
# together with ``qt_serve_forward`` (serving.py) predates them; under
# ``value_and_grad`` an op reads ``jvp(<scope>)``, the backward's
# ``transpose(jvp(<scope>))``. ``qt_aggregate_dense`` sits inside
# ``qt_aggregate`` and is there exactly when the mean ran as a reduce
# over the fanout axis (models/sage.py): a choice made at trace time
# leaves its record in the program's names. ``qt_lookup_hot`` /
# ``qt_lookup_cold`` are the two tiers of a ``Feature`` store's fused
# lookup (feature.py ``lookup_tiered_body``): the HBM cache's gather, and
# the pinned-host tier's (the device's loop of row fetches out of host
# memory); they sit beneath the ``qt_gather`` of a step over a spliced
# tiered store, which covers all of the lookup. ``qt_project``,
# ``qt_attention`` and ``qt_norm`` sit beneath ``qt_forward`` in the
# attention model (models/gat.py, models/mag.py): every product with a
# weight matrix (the layer's shared projection, the skip, the head); the
# logits, the softmax over a target's edges and the weighted sum; the
# batch norm over the valid rows. ``qt_attention_slots`` sits inside
# ``qt_attention`` exactly when the softmax ran over the slot axis of an
# ``Adj`` that states its ``fanout`` (as ``qt_aggregate_dense`` does for
# the mean).
(QT_DRAW, QT_COMPACT, QT_GATHER, QT_FORWARD, QT_LOSS, QT_OPTIMIZER,
 QT_AGGREGATE, QT_AGGREGATE_DENSE, QT_EXCHANGE, QT_LOOKUP_HOT,
 QT_LOOKUP_COLD, QT_PROJECT, QT_ATTENTION, QT_ATTENTION_SLOTS,
 QT_NORM) = DEVICE_SCOPES = (
    "qt_draw", "qt_compact", "qt_gather", "qt_forward", "qt_loss",
    "qt_optimizer", "qt_aggregate", "qt_aggregate_dense", "qt_exchange",
    "qt_lookup_hot", "qt_lookup_cold", "qt_project", "qt_attention",
    "qt_attention_slots", "qt_norm")

# the row-sharded store's lookup (comm.dist_lookup_local) stands where a
# one-chip step has ``qt_gather``: ALL of it sits under ``qt_exchange``,
# its stages beneath that, in the order they run. ``_dedup`` and
# ``_expand`` are the compact layout's ends (the sort that finds the
# distinct ids; each batch slot reading its row out of the response
# block, and the -1 mask), ``_requests`` and ``_responses`` hold the two
# ``all_to_all``s.
(QT_EXCHANGE_ROUTE, QT_EXCHANGE_DEDUP, QT_EXCHANGE_BUCKET,
 QT_EXCHANGE_REQUESTS, QT_EXCHANGE_GATHER, QT_EXCHANGE_RESPONSES,
 QT_EXCHANGE_EXPAND) = EXCHANGE_STAGES = tuple(
    QT_EXCHANGE + "_" + stage for stage in (
        "route", "dedup", "bucket", "requests", "gather", "responses",
        "expand"))


def hot_path(fn):
    """Marker for sync-free hot-path functions — the contract
    ``analysis.host_lint`` verifies statically: a function carrying
    this decorator must never block on the device
    (``jax.device_get`` / ``.block_until_ready()`` / ``np.asarray`` on
    a jax array). The marker adds NO wrapper (jit/donation semantics
    untouched); it only stamps ``__qt_hot_path__`` so tools can find
    the marked set."""
    fn.__qt_hot_path__ = True
    return fn
