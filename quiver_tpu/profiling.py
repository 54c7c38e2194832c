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
# the mean). ``qt_relation_route`` sits beneath ``qt_forward`` in a layer
# over a typed graph (models/gat.py): what brings each edge slot to its
# relation's weight and back (the sort of the slots by relation, the
# group sizes, the sources' rows read in that order, the product's rows
# put back in slot order); the product itself stays ``qt_project``.
(QT_DRAW, QT_COMPACT, QT_GATHER, QT_FORWARD, QT_LOSS, QT_OPTIMIZER,
 QT_AGGREGATE, QT_AGGREGATE_DENSE, QT_EXCHANGE, QT_LOOKUP_HOT,
 QT_LOOKUP_COLD, QT_PROJECT, QT_ATTENTION, QT_ATTENTION_SLOTS,
 QT_NORM, QT_RELATION_ROUTE) = DEVICE_SCOPES = (
    "qt_draw", "qt_compact", "qt_gather", "qt_forward", "qt_loss",
    "qt_optimizer", "qt_aggregate", "qt_aggregate_dense", "qt_exchange",
    "qt_lookup_hot", "qt_lookup_cold", "qt_project", "qt_attention",
    "qt_attention_slots", "qt_norm", "qt_relation_route")

# the row-sharded store's lookup (comm.dist_lookup_local) stands where a
# one-chip step has ``qt_gather``: ALL of it sits under ``qt_exchange``,
# its stages beneath that, in the order they run. ``_route`` builds the
# book (node -> owner * rows_per_host + local row, one word a node) and,
# a second time after ``_bucket``, holds each batch slot's read of its
# owner and place from its distinct id (the dense path: the slots' read
# of the book). ``_dedup`` and ``_expand`` are the compact layout's ends
# (the sort that finds the distinct ids; each batch slot reading its row
# out of the response block, and the -1 mask); ``_bucket`` the distinct
# ids' one read of the book, their places, the count of rounds and the
# request block; ``_requests`` and ``_responses`` hold the two
# ``all_to_all``s.
(QT_EXCHANGE_ROUTE, QT_EXCHANGE_DEDUP, QT_EXCHANGE_BUCKET,
 QT_EXCHANGE_REQUESTS, QT_EXCHANGE_GATHER, QT_EXCHANGE_RESPONSES,
 QT_EXCHANGE_EXPAND) = EXCHANGE_STAGES = tuple(
    QT_EXCHANGE + "_" + stage for stage in (
        "route", "dedup", "bucket", "requests", "gather", "responses",
        "expand"))

# the parts of a hop's draw, beneath ``qt_sample_hop{i}/qt_draw`` in the
# order they run (ops/sample.py ``sample_layer``, the arm every builder's
# default walk runs): ``_rows`` the seeds' two reads of ``indptr``, their
# degrees and counts (``_segment_heads``, the same two reads for the
# rotation and window arms, carries it too); ``_picks`` the partial
# Fisher-Yates over the degrees (``_fisher_yates_rows``: the ``scan``,
# no scope inside its body); ``_neighbors`` the one read of ``indices``
# at ``start + picks``, its clip, the mask and the slots. The hop's key
# arithmetic stays under ``qt_draw`` alone, and so do the other arms'
# bodies (rotation, window, wide-exact, weighted, the Pallas kernels)
# until a cell runs them.
(QT_DRAW_ROWS, QT_DRAW_PICKS, QT_DRAW_NEIGHBORS) = DRAW_STAGES = tuple(
    QT_DRAW + "_" + stage for stage in ("rows", "picks", "neighbors"))

# the per-relation walk (ops/sample_multihop.py ``typed_hop``, where
# every relation of a typed graph keeps its own neighbour lists and
# draws its own fanout): ``qt_draw_relation`` around one relation's draw,
# beneath the hop's ``qt_draw`` (the draw's own stages above beneath
# it); ``qt_type_union`` around the first-occurrence union of each node
# type's frontier with that hop's picks of it, the cap and the count of
# what a cap could not take, beneath the hop's ``qt_compact``. The
# relations' blocks in local ids stay under ``qt_compact`` alone.
(QT_DRAW_RELATION, QT_TYPE_UNION) = TYPED_STAGES = (
    QT_DRAW + "_relation", "qt_type_union")

# the bookkeeping of a tiered store's lookup, beneath its ``qt_gather``
# as siblings of ``qt_lookup_hot`` / ``qt_lookup_cold`` and never inside
# them (feature.py ``lookup_tiered_rows``): ``_translate`` the read of
# the order map (node id -> storage row), which tier a slot's row lies
# in, its row within the cold tier; ``_compact`` the cold slots' ranks,
# the sort that brings their positions to the front of a ``cold_budget``
# block, and the block's cold rows' ids; ``_merge`` the cold block
# written into the hot gather's rows (the scatter; in the full read of
# an overflow or of a budget past the batch, the select between the
# tiers' rows). The ``-1`` mask of ``finish`` stays under ``qt_gather``
# alone, and so does the ``lax.cond`` between the narrow
# block and the overflow's full read (a name around it would hold the
# branch's ``qt_lookup_cold`` too): they are the remainder. The dedup
# branch, which no cell runs, takes none of its own: its translation is
# the shared one, its fallback is the compaction above with its names.
(QT_LOOKUP_TRANSLATE, QT_LOOKUP_COMPACT, QT_LOOKUP_MERGE) = LOOKUP_STAGES = \
    tuple("qt_lookup_" + stage for stage in ("translate", "compact", "merge"))


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
