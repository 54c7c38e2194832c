"""Static-shape neighbor sampling + layer compaction (jnp reference impl).

This is the TPU-native redesign of the reference's CUDA sampling stack:

- ``sample_layer``   <- warp-per-row reservoir kernel ``CSRRowWiseSampleKernel``
  (cuda_random.cu.hpp:7-69) + the orchestration in ``TorchQuiver::sample_kernel``
  (quiver_sample.cu:134-200). Same contract — per seed, draw
  ``min(degree, k)`` distinct neighbors uniformly without replacement — but
  expressed as a vectorized partial Fisher–Yates over a fixed ``(bs, k)``
  output with a validity count, because XLA requires static shapes (the
  reference allocates a dynamic ``tot``-sized buffer instead). The draw
  keeps a ``[k, bs]`` log of its own swaps and reads it back by compares
  and selects over the k columns, never by a gather: on this chip a
  gather costs by the index, so a hop's only gathers are the data's own
  (two reads of ``indptr``, one of ``indices``).

- ``compact_layer``  <- the device ordered hashtable + prefix-sum compaction
  (``reindex_single``/``FillWithDuplicates``, quiver_sample.cu:202-357,
  reindex.cu.hpp:20-183). TPUs have no atomics-friendly hashtable — and
  XLA's TPU gather/scatter runs as a serial ~25ns-per-index loop — so
  uniqueness is computed purely with ``lax.sort`` + dense prefix scans.
  Ordering contract (slightly relaxed vs the reference's first-occurrence
  order, same downstream semantics): valid seeds keep slots [0, v), the
  remaining unique neighbors follow in ascending id order.

- ``sample_prob``    <- ``cal_next`` probability propagation
  (cuda_random.cu.hpp:71-104, sage_sampler.py:149-157) as pure segment ops.

All functions are jit-compatible: static ``k``/capacities, explicit PRNG
keys, masked invalid slots (id == -1).

This module doubles as the correctness oracle for the Pallas kernels in
``quiver_tpu.ops.pallas``.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import profiling


def _scatter_friendly() -> bool:
    """True when the backend executes gather/scatter as vectorized memory
    ops (CPU). On TPU, XLA lowers per-index gather/scatter to a ~25ns
    serial loop, so the sort-only formulations below stay the fast form
    there; on the CPU backend (CI, the smoke bench, the host fallback
    tier) the same sorts are the SLOW form — XLA-CPU's multi-operand
    sort runs ~8x slower than its scatters at 1M elements. Evaluated at
    trace time, so each backend compiles its own fast path."""
    return jax.default_backend() == "cpu"


class LayerSample(NamedTuple):
    """One sampled hop, fixed shapes.

    n_id:     [cap] unique node ids (valid seeds first, keeping their
              slots; then new neighbors in ascending id order; -1 fill
              past ``n_count``)
    n_count:  [] number of valid entries in ``n_id``
    row:      [num_seeds*k] local (compacted) index of the seed of each
              sampled edge; -1 fill
    col:      [num_seeds*k] local index of the sampled neighbor; -1 fill
    edge_count: [] number of valid sampled edges
    e_id:     [num_seeds*k] global edge id of each sampled edge (-1
              fill), present only when edge-id tracking was requested
              (``sample_multihop(..., eid=...)``); None otherwise
    seed_count: [] number of valid seeds; they hold the local ids
              ``[0, seed_count)`` (see ``compact_layer``), so a target
              slot ``t`` of this hop is a real node iff ``t < seed_count``
    """

    n_id: jax.Array
    n_count: jax.Array
    row: jax.Array
    col: jax.Array
    edge_count: jax.Array
    e_id: jax.Array | None = None
    seed_count: jax.Array | None = None


def _fisher_yates_rows(key: jax.Array, deg: jax.Array, k: int) -> jax.Array:
    """Per row, draw ``min(deg, k)`` distinct positions in ``[0, deg)``.

    Vectorized partial Fisher–Yates: a virtual array ``a = [0..deg)`` per
    row; step i swaps ``a[i]`` with ``a[j]``, ``j ~ U[i, deg)``, and emits
    ``a[j]``. Only the <=k written entries are materialized (a tiny write
    log), so the work is independent of degree — the same trick the
    reference's warp reservoir achieves with atomics, minus the atomics.

    The log is held ``[k, bs]``: a column (one step's writes) is a dense
    ``[bs]`` vector with the rows along the lanes. The virtual read
    ``a[x]`` walks the k columns with a compare and a select each, later
    columns overwriting earlier ones (last write wins); an unwritten
    column holds position -1 and never matches an ``x >= 0``. No gather:
    XLA's TPU gather costs by the index, not by the byte (~7.5 ns an
    index even out of this small log), so fetching ONE of a row's k
    logged values that way cost half of what fetching a neighbour out of
    ``indices`` does, 2k times a hop: 16 ms of an 89 ms papers100M
    train step. The 4k compares and selects of a step fuse with its
    ``randint`` into one elementwise pass over ``[bs]`` vectors (0.06 ms
    at ``bs`` = 180,224). k is static and small (the fanout); unrolling
    the k steps as well buys nothing at run time and costs the compiler
    up to 40 s at k = 25, so they stay a ``scan``.

    Returns positions [bs, k]; entries at slot i >= min(deg, k) are
    meaningless and must be masked by the caller.
    """
    bs = deg.shape[0]

    def lookup(pos_log, val_log, x):
        # virtual read a[x]: last write wins; unwritten -> x itself
        res = x
        for c in range(k):
            res = jnp.where(pos_log[c] == x, val_log[c], res)
        return res

    def body(carry, xs):
        pos_log, val_log = carry
        i, subkey = xs
        span = jnp.maximum(deg - i, 1)
        j = i + jax.random.randint(subkey, (bs,), 0, span).astype(deg.dtype)
        a_j = lookup(pos_log, val_log, j)
        a_i = lookup(pos_log, val_log, jnp.full((bs,), i, dtype=deg.dtype))
        pos_log = jax.lax.dynamic_update_slice_in_dim(
            pos_log, j[None, :], i, axis=0)
        val_log = jax.lax.dynamic_update_slice_in_dim(
            val_log, a_i[None, :], i, axis=0)
        return (pos_log, val_log), a_j

    pos_log = jnp.full((k, bs), -1, dtype=deg.dtype)
    val_log = jnp.zeros((k, bs), dtype=deg.dtype)
    steps = jnp.arange(k, dtype=jnp.int32)
    keys = jax.random.split(key, k)
    (_, _), picks = jax.lax.scan(
        body, (pos_log, val_log), (steps, keys))
    return jnp.transpose(picks)                              # [bs, k]


def sample_layer(indptr: jax.Array, indices: jax.Array, seeds: jax.Array,
                 k: int, key: jax.Array, with_slots: bool = False):
    """Sample up to ``k`` distinct neighbors for each seed.

    seeds may contain -1 fill (masked rows). Returns
    (neighbors [bs, k] with -1 fill, counts [bs]); with ``with_slots``
    additionally the CSR slot of each pick ([bs, k], -1 fill) — the
    input to edge-id (``eid``) lookups.
    """
    n = indptr.shape[0] - 1
    e = indices.shape[0]
    # the draw's three parts carry their own names beneath the hop's
    # ``qt_draw`` (profiling.DRAW_STAGES)
    with profiling.scope(profiling.QT_DRAW_ROWS):
        valid = seeds >= 0
        safe = jnp.clip(seeds, 0, max(n - 1, 0)).astype(indptr.dtype)
        start = indptr[safe]
        deg = jnp.where(valid, indptr[safe + 1] - start, 0).astype(jnp.int32)
        counts = jnp.minimum(deg, k)
    with profiling.scope(profiling.QT_DRAW_PICKS):
        picks = _fisher_yates_rows(key, deg, k)
    with profiling.scope(profiling.QT_DRAW_NEIGHBORS):
        gather = jnp.clip(start[:, None] + picks.astype(indptr.dtype),
                          0, e - 1)
        nbrs = indices[gather].astype(jnp.int32)
        mask = jnp.arange(k, dtype=jnp.int32)[None, :] < counts[:, None]
        nbrs = jnp.where(mask, nbrs, -1)
        if with_slots:
            return nbrs, counts, jnp.where(mask, gather, -1)
    return nbrs, counts


def edge_row_ids(indptr: jax.Array, edge_count: int) -> jax.Array:
    """Row id of every CSR slot, built scatter-once + cumsum (cheap at
    graph-build time; cached by CSRTopo)."""
    z = jnp.zeros((edge_count,), jnp.int32)
    inner = indptr[1:-1]
    z = z.at[jnp.clip(inner, 0, max(edge_count - 1, 0))].add(
        jnp.where(inner < edge_count, 1, 0).astype(jnp.int32))
    return jnp.cumsum(z).astype(jnp.int32)


def permute_csr(indices: jax.Array, row_ids: jax.Array,
                key: jax.Array, with_slot_map: bool = False,
                extra=None):
    """Uniformly shuffle every CSR row's neighbor list, on device, in one
    2-key sort over the edge array. O(E log E), ~4ms per 1M edges on
    v5e — refresh once per epoch so rotation sampling (below) draws fresh
    subsets each epoch.

    With ``with_slot_map`` also returns ``slot_map`` where
    ``slot_map[p]`` = the ORIGINAL CSR slot now stored at permuted
    position ``p`` (feeds edge-id tracking under rotation sampling).

    ``extra``: optional tuple of CSR-slot-aligned arrays (e.g. edge
    weights) co-permuted as additional sort payloads — far cheaper than
    an E-sized ``arr[slot_map]`` gather after the fact. Returns
    ``(permuted, extras_tuple[, slot_map])`` when given."""
    rand = jax.random.bits(key, (indices.shape[0],)).astype(jnp.int32)
    ops = [row_ids, rand, indices.astype(jnp.int32)]
    ops += [jnp.asarray(x) for x in (extra or ())]
    if with_slot_map:
        ops.append(jnp.arange(indices.shape[0], dtype=jnp.int32))
    out = jax.lax.sort(tuple(ops), num_keys=2)
    permuted = out[2]
    n_extra = len(extra) if extra is not None else 0
    extras = tuple(out[3:3 + n_extra])
    if with_slot_map and extra is not None:
        return permuted, extras, out[-1]
    if with_slot_map:
        return permuted, out[-1]
    if extra is not None:
        return permuted, extras
    return permuted


def butterfly_shuffle(indices: jax.Array, row_ids: jax.Array,
                      key: jax.Array, with_slot_map: bool = False,
                      max_stride: int = 128, extra=None):
    """Cheap per-epoch within-row re-mix: a masked butterfly network.

    ``permute_csr`` (exact uniform per-row shuffle) costs a 2-key sort
    over the whole edge array — ~650 ms/epoch on a products-scale graph,
    ~23% of a sampling epoch. Rotation/window sampling only need the row
    order to be *fresh* each epoch (the draw's own random offset supplies
    marginal randomness); this provides freshness at ~2% of the sort's
    cost with zero gathers:

    for stride s in 1,2,4,...,``max_stride``: view the (phase-rolled)
    edge array as [E/2s, 2, s] and swap the two halves of each block
    elementwise where (a) both positions belong to the same CSR row and
    (b) a fresh coin says so. Pairing is position XOR s, expressed as a
    reshape — no gather/scatter. A per-epoch random phase roll re-aligns
    the pairing blocks so hub rows (deg > 2*``max_stride``) also mix
    across block boundaries over epochs. Elements provably never leave
    their row (a swap requires both sides in the row), so the CSR
    structure is preserved exactly.

    One pass is not a uniform shuffle; composed over epochs (fresh coins
    + fresh phase each call — pass the PREVIOUS epoch's output back in)
    the order keeps mixing. Accuracy parity with exact sampling is
    recorded in docs/introduction.md alongside the sort-based shuffle.

    Returns the re-ordered edge array; with ``with_slot_map`` also the
    slot map — but note the map is INPUT-relative (``out[p] ==
    indices[slot_map[p]]`` for the array passed in), unlike
    ``permute_csr`` whose input is always the original CSR order. Under
    the feed-output-back-in composition, edge-id tracking must compose
    maps across epochs: ``running = running[slot_map_this_epoch]``.

    ``extra``: optional tuple of slot-aligned arrays (e.g. edge weights)
    carried through the same swaps; returned as
    ``(out, extras_tuple[, slot_map])`` — compose them across epochs by
    feeding the outputs back in, like ``indices`` itself.
    """
    e = indices.shape[0]
    out = indices.astype(jnp.int32)
    payload = (jnp.arange(e, dtype=jnp.int32) if with_slot_map else None)
    extras = [jnp.asarray(x) for x in (extra or ())]
    kphi, kcoin = jax.random.split(key)
    # phase-roll so pairing-block alignment differs per epoch
    phi = jax.random.randint(kphi, (), 0, e, dtype=jnp.int32)
    out = jnp.roll(out, phi)
    rows = jnp.roll(row_ids, phi)
    if payload is not None:
        payload = jnp.roll(payload, phi)
    extras = [jnp.roll(x, phi) for x in extras]

    s = 1
    pass_i = 0
    while s <= max_stride:
        pad = (-e) % (2 * s)
        def blocks(x, fill):
            if pad:
                x = jnp.concatenate(
                    [x, jnp.full((pad,), fill, x.dtype)])
            return x.reshape(-1, 2, s)
        rb = blocks(rows, -2)
        same = rb[:, 0, :] == rb[:, 1, :]
        coin = jax.random.bernoulli(
            jax.random.fold_in(kcoin, pass_i), 0.5, same.shape)
        do = same & coin

        def swap(x, fill):
            xb = blocks(x, fill)
            lo = jnp.where(do, xb[:, 1, :], xb[:, 0, :])
            hi = jnp.where(do, xb[:, 0, :], xb[:, 1, :])
            return jnp.stack([lo, hi], axis=1).reshape(-1)[:e]

        out = swap(out, -1)
        if payload is not None:
            payload = swap(payload, -1)
        extras = [swap(x, 0) for x in extras]
        s *= 2
        pass_i += 1

    out = jnp.roll(out, -phi)
    ext_out = tuple(jnp.roll(x, -phi) for x in extras)
    if payload is not None and extra is not None:
        return out, ext_out, jnp.roll(payload, -phi)
    if payload is not None:
        return out, jnp.roll(payload, -phi)
    if extra is not None:
        return out, ext_out
    return out


def reshuffle_csr(indices: jax.Array, row_ids: jax.Array, key: jax.Array,
                  method: str = "sort", with_slot_map: bool = False,
                  extra=None):
    """Per-epoch row-order refresh for rotation/window sampling:
    ``method="sort"`` = ``permute_csr`` (exact uniform per-row shuffle,
    O(E log E) sort), ``"butterfly"`` = ``butterfly_shuffle`` (~40x
    cheaper masked swap network; composes toward uniform over epochs —
    feed each epoch's output into the next call). ``extra`` co-permutes
    slot-aligned arrays (e.g. edge weights) alongside."""
    if method == "sort":
        return permute_csr(indices, row_ids, key,
                           with_slot_map=with_slot_map, extra=extra)
    if method == "butterfly":
        return butterfly_shuffle(indices, row_ids, key,
                                 with_slot_map=with_slot_map, extra=extra)
    raise ValueError(f"unknown reshuffle method {method!r}")


def compose_slot_map(prev_map, smap: jax.Array, base, bfly: bool):
    """Maintain a co-permuted slot -> edge-id map across reshuffles
    (the correctness-critical composition both the homogeneous and the
    hetero samplers rely on for ``with_eid`` under rotation/window —
    keep it in ONE place).

    - sort shuffles start from the ORIGINAL row order every epoch, so
      the new map is ``smap`` (or ``base[smap]`` when the topology
      carries an eid map) and ``prev_map`` is ignored;
    - butterfly's ``smap`` is INPUT-relative (the shuffle composes on
      the previous epoch's output), so the running map composes:
      ``prev_map[smap]``, seeded from ``base``/identity on first use.
    """
    if not bfly:
        return smap if base is None else jnp.asarray(base)[smap]
    if prev_map is not None:
        return prev_map[smap]
    if base is not None:
        return jnp.asarray(base)[smap]
    return smap


def as_index_rows(indices: jax.Array, width: int = 128) -> jax.Array:
    """Pad + reshape the CSR ``indices`` array into 128-wide rows. TPU
    random access costs ~25ns per gather *index* regardless of row width
    (up to a lane), so the sampler fetches 128-wide rows, not elements."""
    e = indices.shape[0]
    rows = (e + 2 * width - 1) // width + 1
    pad = rows * width - e
    return jnp.concatenate(
        [indices, jnp.zeros((pad,), indices.dtype)]).reshape(rows, width)


def as_index_rows_overlapping(indices: jax.Array,
                              width: int = 128) -> jax.Array:
    """Overlapping 2*width-wide view of the CSR ``indices`` array:
    row i covers flat positions [i*width, i*width + 2*width). Any
    k <= width consecutive-position window [p, p+k) then fits entirely
    inside row p // width, so ``sample_layer_rotation`` needs ONE row
    gather per seed instead of the two the non-overlapping layout
    requires to cover boundary-crossing windows. Costs 2x the memory of
    ``as_index_rows`` — the trade the hot sampling path wants when the
    edge array fits HBM twice."""
    e = indices.shape[0]
    rows = (e + 2 * width - 1) // width + 1
    pad = rows * width - e
    flat = jnp.concatenate([indices, jnp.zeros((pad,), indices.dtype)])
    base = flat.reshape(rows, width)
    nxt = jnp.concatenate([base[1:], jnp.zeros_like(base[:1])])
    return jnp.concatenate([base, nxt], axis=1)        # [rows, 2*width]


def _window_layout(indices_rows: jax.Array, stride: int | None, k: int):
    """Validate a windowed-layout (pair or overlapping) request and
    return (step, win): flat positions per row step and the assembled
    window length."""
    width = indices_rows.shape[1]
    overlap = stride is not None
    if overlap and width != 2 * stride:
        # a mismatched layout would silently gather the wrong CSR rows
        raise ValueError(
            f"stride={stride} requires an as_index_rows_overlapping "
            f"layout of width 2*stride={2 * stride}, got width {width}")
    step = stride if overlap else width
    win = 2 * step
    k_cap = (step + 1) if overlap else width
    if k > k_cap:
        raise ValueError(
            f"windowed sampling supports k <= {k_cap} for this layout "
            f"(got {k}): the row window only covers that many picks")
    return step, win


def _segment_heads(indptr: jax.Array, seeds: jax.Array):
    """Per-seed (start, deg) shared by the windowed samplers; invalid
    (-1) seeds get deg 0, which masks them downstream."""
    n = indptr.shape[0] - 1
    with profiling.scope(profiling.QT_DRAW_ROWS):
        valid = seeds >= 0
        safe = jnp.clip(seeds, 0, max(n - 1, 0)).astype(indptr.dtype)
        start = indptr[safe]
        deg = jnp.where(valid, indptr[safe + 1] - start, 0).astype(jnp.int32)
    return start, deg


def _gather_window(indices_rows: jax.Array, p0: jax.Array, step: int,
                   stride: int | None):
    """Assemble each seed's 2*step-wide window anchored at flat
    position p0: one gather on the overlapping layout, two on pair."""
    r0 = (p0 // step).astype(jnp.int32)
    off = (p0 % step).astype(jnp.int32)
    if stride is not None:
        w = indices_rows[r0]                                # [bs, 2*step]
    else:
        w = jnp.concatenate(
            [indices_rows[r0], indices_rows[r0 + 1]], axis=1)
    return w, r0, off


def _extract_window_cols(w: jax.Array, pos: jax.Array, k: int):
    """nbrs[b, j] = w[b, pos[b, j]]; out-of-window positions yield 0.

    TPU: k onehot passes — per-index gathers are serial there, dense
    compare+select is the fast form. CPU backend: a real row-local
    gather — measured 33x faster than the compare+select at the bench's
    last-hop shape (180k x 256), where this extraction dominates the
    wide-fetch samplers' cost."""
    if _scatter_friendly():
        width = w.shape[1]
        safe = jnp.clip(pos, 0, width - 1)
        out = jnp.take_along_axis(w, safe, axis=1)
        return jnp.where((pos >= 0) & (pos < width), out, 0) \
            .astype(jnp.int32)
    wiota = jax.lax.broadcasted_iota(jnp.int32, (1, w.shape[1]), 1)
    cols = []
    for j in range(k):
        onehot = wiota == pos[:, j][:, None]
        cols.append(jnp.sum(jnp.where(onehot, w, 0), axis=1))
    return jnp.stack(cols, axis=1).astype(jnp.int32)


def sample_layer_rotation(indptr: jax.Array, indices_rows: jax.Array,
                          seeds: jax.Array, k: int, key: jax.Array,
                          with_slots: bool = False,
                          stride: int | None = None):
    """Rotation sampling: draw ``min(deg, k)`` *consecutive* entries of the
    (pre-shuffled) neighbor row at a uniform random offset.

    With rows re-shuffled every epoch (``permute_csr``), each draw is
    marginally uniform over the true neighbors and slots are distinct —
    the same guarantees the reference's reservoir kernel provides
    (cuda_random.cu.hpp:7-69) — while the per-seed memory traffic is one
    or two wide row fetches instead of k scattered loads. Subsets within
    one epoch are limited to runs of that epoch's shuffle (documented
    trade-off; use ``sample_layer`` for i.i.d. exact subsets).

    Returns (neighbors [bs, k] -1 fill, counts [bs]).

    Layouts:
    - ``as_index_rows`` (default, ``stride`` omitted): rows are disjoint
      ``width``-wide blocks; TWO row gathers build a 2*width window that
      covers any boundary-crossing pick run. ``k`` <= width.
    - ``as_index_rows_overlapping`` + ``stride=width``: rows overlap
      (each covers [i*stride, i*stride + 2*stride)), so any pick run
      [p, p+k) with ``k`` <= stride+1 sits inside row p // stride: ONE
      gather per seed — half the gather traffic of the default layout,
      for 2x index memory.
    """
    step, _ = _window_layout(indices_rows, stride, k)
    start, deg = _segment_heads(indptr, seeds)
    counts = jnp.minimum(deg, k)

    bs = seeds.shape[0]
    span = jnp.maximum(deg - k, 0) + 1
    o = jax.random.randint(key, (bs,), 0, span, dtype=jnp.int32)
    p0 = start + o.astype(start.dtype)      # window anchored at the pick
    w, _, off = _gather_window(indices_rows, p0, step, stride)
    pos = off[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    nbrs = _extract_window_cols(w, pos, k)
    mask = jnp.arange(k, dtype=jnp.int32)[None, :] < counts[:, None]
    if with_slots:
        # pick j sits at flat position p0 + j of the (permuted) edge
        # array; map through permute_csr's slot_map for original slots
        slots = p0[:, None] + jnp.arange(k, dtype=p0.dtype)[None, :]
        return jnp.where(mask, nbrs, -1), counts, jnp.where(mask, slots, -1)
    return jnp.where(mask, nbrs, -1), counts


def sample_layer_window(indptr: jax.Array, indices_rows: jax.Array,
                        seeds: jax.Array, k: int, key: jax.Array,
                        with_slots: bool = False,
                        stride: int | None = None):
    """Window sampling: an i.i.d. ``min(deg, k)``-subset drawn uniformly
    without replacement from a >=129-entry window of the (pre-shuffled)
    neighbor row.

    Statistics: for ``deg <= window`` the window IS the whole segment,
    so this is exactly the reference reservoir kernel's draw (i.i.d.
    uniform subsets) under ANY row order — no shuffle needed at all for
    such rows. Hub rows (deg > step+1) anchor their window at a
    rotation-style uniform random offset, so every draw walks the whole
    segment (the neighbor marginal is uniform in expectation over the
    per-epoch reshuffle, exactly rotation's guarantee) while the subset
    WITHIN the window is still an independent uniform draw — strictly
    more within-epoch mixing than rotation's consecutive runs at the
    same fetch cost. Any mixing reshuffle (sort or butterfly) serves.

    Cost: the same one (overlap layout, ``stride=width``) or two (pair
    layout) row gathers per seed as rotation, plus the gather-free
    Fisher-Yates position draw (``_fisher_yates_rows``: 4k selects a
    step over ``[bs]`` vectors) — the price of subset independence.
    (A [bs, window] uniform-priorities + top_k draw gives the same
    distribution but costs a 256-wide sort per seed; measured 3x
    slower end-to-end on v5e, so the write-log form is the one used.)

    Returns (neighbors [bs, k] -1 fill, counts [bs]); with
    ``with_slots``, also the (permuted-array) flat slot of each pick.
    """
    step, win = _window_layout(indices_rows, stride, k)
    start, deg = _segment_heads(indptr, seeds)
    counts = jnp.minimum(deg, k)

    # hub rows anchor the window at a random in-segment offset o with
    # >= step+1 entries guaranteed after it; rows whose WHOLE segment
    # fits the start-anchored window keep o=0 — their draw is then an
    # exact uniform k-subset of every neighbor under any fixed order
    # (that can reach up to ~2*step depending on the start alignment,
    # not just step+1)
    kanchor, kdraw = jax.random.split(key)
    bs = seeds.shape[0]
    span = jnp.maximum(deg - (step + 1), 0) + 1
    o = jax.random.randint(kanchor, (bs,), 0, span, dtype=jnp.int32)
    start_off = (start % step).astype(jnp.int32)
    o = jnp.where(deg <= win - start_off, 0, o)
    p0 = start + o.astype(start.dtype)
    w, r0, off = _gather_window(indices_rows, p0, step, stride)
    # the window covers neighbor positions [o, o + cap) of the segment,
    # cap = min(deg - o, win - off) >= min(deg, step + 1); Fisher-Yates
    # draws min(cap, k) distinct positions uniformly — an i.i.d.
    # k-subset of the window
    cap = jnp.minimum(deg - o, win - off)                   # [bs]
    picks = off[:, None] + _fisher_yates_rows(kdraw, cap, k)  # [bs, k]
    nbrs = _extract_window_cols(w, picks, k)
    mask = jnp.arange(k, dtype=jnp.int32)[None, :] < counts[:, None]
    if with_slots:
        base = (r0.astype(start.dtype) * step)[:, None]
        slots = base + picks.astype(start.dtype)
        return jnp.where(mask, nbrs, -1), counts, jnp.where(mask, slots, -1)
    return jnp.where(mask, nbrs, -1), counts


class ExactBucketMeta(NamedTuple):
    """Static degree-bucket split for the wide-fetch exact sampler,
    computed ONCE per (graph, layout) and cached on ``CSRTopo``.

    A row is a "hub" when its segment cannot fit its start-anchored
    window (``deg > window - start % step``) — the same classification
    ``sample_layer_exact_wide`` applies per seed at sample time. The
    metadata summarizes how much of the graph falls in that bucket:

    node_frac: fraction of NODES that are hubs — the hub rate of a
               uniform seed batch (hop 0).
    edge_frac: fraction of EDGES owned by hub rows — the hub rate of a
               degree-biased hop frontier (hops >= 1 arrive roughly
               proportional to in-degree; C-SAW's routing argument,
               arxiv 2009.06693).
    frac:      max of the two — the per-hop hub-rate bound
               ``suggest_hub_cap`` sizes the static scattered-load
               budget from.

    All three are host floats: the split parameterizes the XLA program
    statically (the budget becomes a compile-time shape), so the whole
    multi-hop expansion stays one program.
    """

    node_frac: float
    edge_frac: float
    frac: float


def exact_bucket_meta(indptr, step: int = 128) -> ExactBucketMeta:
    """Classify every row against the wide-fetch window (``win =
    2*step``) and reduce to the static bucket-split fractions. Works on
    device (jnp) and host (numpy int64 topologies) indptr alike; the
    result is tiny host data — cache it (``CSRTopo.exact_bucket_meta``
    does) rather than recomputing per epoch."""
    win = 2 * step
    start = indptr[:-1]
    deg = indptr[1:] - start
    hub = deg > (win - (start % step))
    n = max(int(deg.shape[0]), 1)
    e = max(int(deg.sum()), 1)
    node_frac = float(hub.sum()) / n
    edge_frac = float((deg * hub).sum()) / e
    return ExactBucketMeta(node_frac=node_frac, edge_frac=edge_frac,
                           frac=max(node_frac, edge_frac))


def suggest_hub_cap(num_seeds: int, hub_frac: float | None) -> int | None:
    """Static scattered-load budget for a ``num_seeds``-wide batch given
    the graph's hub fraction (``ExactBucketMeta.frac``). 3x the expected
    hub count plus a 64-row floor keeps budget overflow (the exact-but-
    slower ``lax.cond`` full-scatter fallback) a many-sigma event while
    cutting the blind ``bs // 2`` default's scattered traffic several-
    fold on power-law graphs. ``None`` (no metadata) keeps the default.
    """
    if hub_frac is None:
        return None
    return int(min(num_seeds,
                   math.ceil(num_seeds * min(1.0, 3.0 * hub_frac)) + 64))


def sample_layer_exact_wide(indptr: jax.Array, indices: jax.Array,
                            indices_rows: jax.Array, seeds: jax.Array,
                            k: int, key: jax.Array,
                            stride: int | None = None,
                            hub_cap: int | None = None,
                            with_slots: bool = False):
    """Exact i.i.d. sampling at windowed-fetch cost.

    Same draw as ``sample_layer`` — ``min(deg, k)`` distinct neighbors,
    uniform without replacement, the reference reservoir kernel's
    contract (cuda_random.cu.hpp:7-69) — but the per-seed memory traffic
    is one (overlap layout) or two (pair) wide row gathers for every
    seed whose whole segment fits its start-anchored window (deg <=
    window - start%step; the vast majority on power-law graphs),
    instead of k scattered loads. Only "hub" rows pay scattered loads,
    and only up to a static budget ``hub_cap`` of them; if a batch
    exceeds the budget, a ``lax.cond`` falls back to the full scattered
    gather for that batch — exactness holds in every case, only the
    speedup degrades. The default budget is a blind bs//2; pass
    ``suggest_hub_cap(bs, ExactBucketMeta.frac)`` (the degree-bucket
    split cached on ``CSRTopo.exact_bucket_meta``) to size it from the
    graph's actual hub mass — several-fold less scattered traffic on
    power-law graphs, same exactness guarantee.

    How often does the fallback fire? Distributional analysis (numpy,
    2M-node samples; not a hardware measurement): on the products-scale
    lognormal degree model (mu=ln 25, sigma=1) a uniform 1024-seed
    batch averages ~24 hub rows and a degree-biased hop frontier (seeds
    arrive proportional to in-degree) ~163 — vs the 512 default budget,
    overflow is a 30-100 sigma event, and the big later hops
    (s=180k, budget 90k vs ~29k expected hubs) sit further out still.
    The cond exists for pathological dense graphs where most rows
    exceed the window; there the wide fetch has no advantage and the
    full scatter is the right behavior anyway.

    Unlike rotation/window, NO reshuffle is needed: the Fisher-Yates
    positions are uniform under any fixed row order, so
    ``indices_rows`` is just a layout view (``as_index_rows`` /
    ``as_index_rows_overlapping``) of the SAME flat ``indices`` array
    passed alongside (hub fallbacks read the flat array; both must be
    in the same order).

    Returns (neighbors [bs, k] -1 fill, counts [bs]); with
    ``with_slots`` also each pick's flat CSR slot (-1 fill) — original-
    order slots, directly usable for edge-id lookups.
    """
    step, win = _window_layout(indices_rows, stride, 1)  # k-cap-free
    start, deg = _segment_heads(indptr, seeds)
    counts = jnp.minimum(deg, k)
    bs = seeds.shape[0]
    e = indices.shape[0]
    picks = _fisher_yates_rows(key, deg, k)              # exact, all rows

    # wide path: every row whose segment fits the start-anchored window
    off0 = (start % step).astype(jnp.int32)
    low = deg <= (win - off0)
    w, _, off = _gather_window(indices_rows, start, step, stride)
    pos = off[:, None] + picks
    nbrs = _extract_window_cols(
        w, jnp.where(low[:, None], pos, 0), k)           # hubs: garbage

    # hub path: scattered loads for at most hub_cap rows
    if hub_cap is None:
        hub_cap = max(1, bs // 2)
    hub_cap = min(hub_cap, bs)
    iota = jnp.arange(bs, dtype=jnp.int32)
    hub = (~low) & (deg > 0)
    n_hub = jnp.sum(hub).astype(jnp.int32)
    hrank = jnp.cumsum(hub).astype(jnp.int32) - 1
    if _scatter_friendly():
        # stream-compact the hub rows by scatter (fast on CPU)
        tgt = jnp.where(hub & (hrank < hub_cap), hrank, hub_cap)
        hpos = jnp.zeros((hub_cap,), jnp.int32).at[tgt].set(
            iota, mode="drop")         # hub row positions (garbage past n_hub)
    else:
        okey = jnp.where(hub & (hrank < hub_cap), hrank, _I32_MAX)
        # (okey, iota) pairs are unique, so the unstable sort is exact
        _, hpos = jax.lax.sort((okey, iota), num_keys=1, is_stable=False)
        hpos = hpos[:hub_cap]          # hub row positions (garbage past n_hub)
    h_valid = (jnp.arange(hub_cap, dtype=jnp.int32)
               < jnp.minimum(n_hub, hub_cap))
    h_start = start[hpos]
    h_picks = picks[hpos]
    g = jnp.clip(h_start[:, None] + h_picks.astype(h_start.dtype), 0, e - 1)
    h_nbrs = indices[g].astype(jnp.int32)
    tgt = jnp.where(h_valid, hpos, bs)                   # bs = drop slot
    nbrs = nbrs.at[tgt].set(h_nbrs, mode="drop")

    def _full_scatter(_):
        ga = jnp.clip(start[:, None] + picks.astype(start.dtype), 0, e - 1)
        return indices[ga].astype(jnp.int32)

    nbrs = jax.lax.cond(n_hub > hub_cap, _full_scatter,
                        lambda _: nbrs, None)
    mask = jnp.arange(k, dtype=jnp.int32)[None, :] < counts[:, None]
    nbrs = jnp.where(mask, nbrs, -1)
    if with_slots:
        slots = start[:, None] + picks.astype(start.dtype)
        return nbrs, counts, jnp.where(mask, slots, -1)
    return nbrs, counts


_I32_MAX = jnp.iinfo(jnp.int32).max


def _fill_from_run_start(values: jax.Array, at: jax.Array) -> jax.Array:
    """Forward-fill ``values`` (defined where ``at`` is True) to every
    later position until the next ``at``. Dense O(n log n) associative
    scan — no gathers (TPU gathers cost ~25ns *per index*, serial)."""
    def combine(a, b):
        av, asn = a
        bv, bsn = b
        return jnp.where(bsn, bv, av), asn | bsn

    filled, _ = jax.lax.associative_scan(
        combine, (jnp.where(at, values, 0), at))
    return filled


def _compact_core(ids: jax.Array, s: int, seeds_dense: bool = False):
    """Shared sort-only compaction. ``ids[:s]`` is the prefix ("seeds"):
    its valid entries MUST be distinct (duplicate seeds leave holes in the
    slot assignment and corrupt ``n_id`` — same alignment break as the
    reference when fed duplicate seeds); they occupy slots [0, v) ordered
    by position (slot = rank among valid seeds, so -1 holes anywhere in
    the prefix are safe); the remaining unique values follow in ascending
    id order.

    ``seeds_dense=True`` promises the valid seeds are exactly the prefix
    positions [0, v) (valid-first, -1 tail fill — the invariant this
    function's own ``n_id`` output satisfies, so hop>=1 of a multi-hop
    expansion can always pass it). Rank-among-valid then equals position,
    which drops the third operand from the big 2-key sort — the hot
    hops' main cost. A violating input (interior -1 holes) silently
    corrupts slot assignment, so only enable it where the invariant is
    guaranteed by construction.

    Returns (n_id [cap] -1-filled, n_count, local [cap]) with ``local[i]``
    = position of ``ids[i]`` in ``n_id`` (garbage where ``ids[i] < 0``).

    Built exclusively from ``lax.sort`` + dense prefix scans because XLA's
    TPU gather/scatter is a ~25ns-per-index serial loop — on a 1M-element
    layer the reference-style hashtable compaction (reindex.cu.hpp:20-183)
    re-expressed with argsort+gathers costs ~40ms, this form ~8ms.
    Requires ids < 2^31-1 and cap < 2^30.
    """
    cap = ids.shape[0]
    ids = ids.astype(jnp.int32)
    iota = jnp.arange(cap, dtype=jnp.int32)
    valid = ids >= 0
    is_seed = (iota < s) & valid

    B30 = jnp.int32(1 << 30)
    idk = jnp.where(valid, ids, _I32_MAX)
    # tag bit30 orders a run's seed entry before its duplicates; low bits
    # carry the original position through the sort. A third operand
    # carries each seed's rank among *valid* seeds: seed slots are rank-
    # based so -1 holes in the prefix can't collide with extra slots.
    # With ``seeds_dense`` rank == position, so the position already in
    # the tag's low bits serves and the third operand is dropped.
    # (idk, tag) pairs are unique (tag embeds the position), so every
    # sort here runs unstable — the output is fully determined either
    # way and XLA's unstable comparator is measurably cheaper.
    tag = jnp.where(is_seed, 0, B30) | iota
    if seeds_dense:
        sid, stag = jax.lax.sort((idk, tag), num_keys=2, is_stable=False)
        spos = stag & (B30 - 1)
        srk = spos
    else:
        seed_rank = (jnp.cumsum(is_seed).astype(jnp.int32) - 1)
        sid, stag, srk = jax.lax.sort(
            (idk, tag, jnp.where(is_seed, seed_rank, 0)), num_keys=2,
            is_stable=False)
        spos = stag & (B30 - 1)
    sseed = stag < B30

    flag = jnp.concatenate(
        [jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    fvalid = sid != _I32_MAX
    vseeds = jnp.sum(is_seed).astype(jnp.int32)
    sflag = flag & sseed                      # seed-run starts
    nsflag = flag & fvalid & ~sseed           # valid non-seed run starts

    # per-element fills (all monotone -> cummax, or assoc-scan fallback)
    rs = jax.lax.cummax(jnp.where(flag, iota, -1), axis=0)      # my run's start
    lss = jax.lax.cummax(jnp.where(sflag, iota, -1), axis=0)    # last seed-run start
    in_seedrun = (lss == rs) & (lss >= 0)

    # seed slot of my run's seed (= its rank among valid seeds, carried
    # through the sort as srk). srank (rank among seed runs) is monotone,
    # so (srank << 9 | srk-half) stays sortable under cummax; two packed
    # fills carry the 18-bit srk in 9-bit halves within int32.
    if s < (1 << 18) and cap < (1 << 30):
        srank = jnp.cumsum(sflag) - 1                   # const within run
        hi = jax.lax.cummax(
            jnp.where(sflag, (srank << 9) | (srk >> 9), -1), axis=0)
        lo = jax.lax.cummax(
            jnp.where(sflag, (srank << 9) | (srk & 511), -1), axis=0)
        seed_local = ((hi & 511) << 9) | (lo & 511)
    else:
        seed_local = _fill_from_run_start(srk, sflag)

    nsrank = jnp.cumsum(nsflag).astype(jnp.int32) - 1   # const within run
    local_sorted = jnp.where(in_seedrun, seed_local, vseeds + nsrank)

    n_count = (vseeds + jnp.sum(nsflag)).astype(jnp.int32)

    if _scatter_friendly():
        # CPU backend: the two permutation steps below are plain
        # scatters there — ~8x cheaper than the equivalent sorts at the
        # bench's 1M-wide last hop (where compaction dominates the whole
        # exact epoch). Run-start locals are distinct and spos is a
        # permutation, so both scatters are collision-free.
        n_id = jnp.full((cap,), -1, jnp.int32).at[
            jnp.where(flag & fvalid, local_sorted, cap)].set(
                sid, mode="drop")
        local = jnp.zeros((cap,), jnp.int32).at[spos].set(local_sorted)
        return n_id, n_count, local

    # n_id[local] = id at run starts; scatter expressed as key+payload
    # sort (unstable: key ties are all _I32_MAX drop slots, masked below)
    okey = jnp.where(flag & fvalid, local_sorted, _I32_MAX)
    _, n_id_payload = jax.lax.sort((okey, sid), num_keys=1,
                                   is_stable=False)
    n_id = jnp.where(iota < n_count, n_id_payload, -1)

    # route local ids back to original positions (spos is a permutation,
    # so the unstable sort is exact)
    _, local = jax.lax.sort((spos, local_sorted), num_keys=1,
                            is_stable=False)
    return n_id, n_count, local


def compact_ids(ids: jax.Array):
    """Deduplicate a -1-padded id vector. Returns (n_id [cap] -1-filled,
    n_count, local_ids [cap]) where ``local_ids[i]`` is the position of
    ``ids[i]`` in ``n_id`` (garbage where ``ids[i] < 0``). ``n_id`` lists
    the unique values in ascending order. Sort-only replacement for the
    reference's device ordered hashtable (reindex.cu.hpp:20-183)."""
    # s=0: no seed prefix, so the dense promise holds vacuously and the
    # rank operand is never read — take the 2-operand sort
    return _compact_core(ids, 0, seeds_dense=True)


def compact_union(prefix_ids: jax.Array, extra_ids: jax.Array):
    """Union ``prefix_ids ++ extra_ids`` (both -1-padded, any lengths).
    Valid prefix entries (assumed distinct) keep their slots in ``n_id``;
    remaining unique extras follow in ascending id order.
    Returns (n_id, n_count, local_ids_of_extra)."""
    p = prefix_ids.shape[0]
    n_id, n_count, local = _compact_core(
        jnp.concatenate([prefix_ids.astype(jnp.int32),
                         extra_ids.astype(jnp.int32)]), p)
    extra_local = jnp.where(extra_ids >= 0, local[p:], -1)
    return n_id, n_count, extra_local


def compact_layer(seeds: jax.Array, nbrs: jax.Array,
                  seeds_dense: bool = False) -> LayerSample:
    """Deduplicate ``concat(seeds, nbrs)`` and emit the layer's bipartite
    COO in local (compacted) ids.

    seeds: [s] int32, -1 fill allowed; valid entries must be distinct
    (true for frontiers and training batches). nbrs: [s, k] int32, -1
    fill. Output capacity is the static ``s + s*k``. Valid seeds keep
    slots [0, n_valid_seeds) of ``n_id`` (the invariant training relies
    on: layer outputs for the batch are rows [0, bs)); new neighbors
    follow in ascending id order. ``seeds_dense`` promises valid seeds
    are a prefix (see ``_compact_core``) — true whenever ``seeds`` is a
    previous hop's ``n_id``; drops one operand from the big sort.
    """
    s, k = nbrs.shape
    n_id, n_count, local_ids = _compact_core(
        jnp.concatenate([seeds, nbrs.reshape(-1)]), s,
        seeds_dense=seeds_dense)
    nbr_valid = nbrs.reshape(-1) >= 0
    col = jnp.where(nbr_valid, local_ids[s:], -1)
    seed_local = jax.lax.broadcast_in_dim(
        local_ids[:s], (s, k), (0,)).reshape(-1)
    row = jnp.where(nbr_valid, seed_local, -1)
    edge_count = jnp.sum(nbr_valid).astype(jnp.int32)
    return LayerSample(n_id=n_id, n_count=n_count, row=row, col=col,
                       edge_count=edge_count,
                       seed_count=jnp.sum(seeds >= 0).astype(jnp.int32))


def sample_prob_step(indptr: jax.Array, indices: jax.Array,
                     last_prob: jax.Array, k: int,
                     row_ids: jax.Array | None = None) -> jax.Array:
    """One hop of sampled-probability propagation (== ``cal_next``,
    cuda_random.cu.hpp:71-104): for each node v with neighbors u,

        cur[v] = 1 - (1 - last[v]) * prod_u (1 - last[u] * min(1, k/deg(u)))

    and cur[v] = 0 when deg(v) == 0 (reference quirk kept for parity).
    """
    n = indptr.shape[0] - 1
    deg = (indptr[1:] - indptr[:-1]).astype(jnp.float32)
    frac = jnp.where(deg > 0, jnp.minimum(1.0, k / jnp.maximum(deg, 1.0)), 0.0)
    skip = 1.0 - last_prob * frac                            # per node
    if row_ids is None:
        row_ids = edge_rows(indptr, indices.shape[0])
    acc = jax.ops.segment_prod(skip[indices], row_ids, num_segments=n)
    cur = 1.0 - (1.0 - last_prob) * acc
    return jnp.where(deg > 0, cur, 0.0)


def sample_prob(indptr: jax.Array, indices: jax.Array, train_idx: jax.Array,
                sizes, total_node_count: int) -> jax.Array:
    """k-hop access probability from train seeds (== ``sample_prob``,
    sage_sampler.py:149-157). Feeds cache ordering and partitioning."""
    prob = jnp.zeros((total_node_count,), jnp.float32).at[train_idx].set(1.0)
    rows = edge_rows(indptr, indices.shape[0])
    for k in sizes:
        prob = sample_prob_step(indptr, indices, prob, k, row_ids=rows)
    return prob


def edge_rows(indptr: jax.Array, edge_count: int) -> jax.Array:
    """Row id of every CSR slot: searchsorted-based expansion of indptr."""
    return (jnp.searchsorted(
        indptr, jnp.arange(edge_count, dtype=indptr.dtype), side="right") - 1
    ).astype(jnp.int32)
