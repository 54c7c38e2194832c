"""Distributed communication backend — XLA collectives over ICI/DCN.

Replaces the reference's three-mechanism stack (survey §5: CUDA-IPC,
P2P peer loads, raw NCCL wrapper + hand-rolled exchange schedule,
quiver_comm.cu:9-100 + comm.py:5-186) with the single TPU-native
mechanism: a global ``jax.sharding.Mesh`` and collectives inside
``shard_map``. There is no id bootstrap (``getNcclId``/TCPStore) —
``jax.distributed.initialize`` wires up DCN; the function is kept as an
API-compat no-op token.

``HostRankTable`` and ``schedule`` reproduce the reference's rank
bookkeeping and contention-free pairwise scheduling (comm.py:5-75) for
host-driven exchange planning; the on-device path doesn't need them (the
XLA collective scheduler owns link contention).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from .ops import quant
from .ops.dedup import I32_MAX, unique_within_budget
from . import profiling
from .profiling import hot_path


def get_comm_id() -> bytes:
    """API-compat shim for ``quiver.getNcclId`` (comm.py:185-186). TPU
    bootstrap happens in ``jax.distributed.initialize``; nothing to mint."""
    return b"quiver-tpu-comm"


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None):
    """Multi-host bootstrap (replaces NcclId + TCPStore rendezvous)."""
    jax.distributed.initialize(coordinator_address, num_processes, process_id)


class HostRankTable:
    """(host, lane) <-> global rank mapping (reference comm.py:5-39)."""

    def __init__(self, hosts: int, rank_per_host: int):
        self.hosts = hosts
        self.rank_per_host = rank_per_host
        self.world_size = hosts * rank_per_host

    def rank(self, host: int, lane: int) -> int:
        return host * self.rank_per_host + lane

    def host_lane(self, rank: int):
        return divmod(rank, self.rank_per_host)

    def ranks_of_host(self, host: int) -> List[int]:
        base = host * self.rank_per_host
        return list(range(base, base + self.rank_per_host))


def schedule(size_matrix: np.ndarray) -> List[List[tuple]]:
    """Greedy contention-free step packing of pairwise transfers
    (capability parity with reference comm.py:42-75): given an ws x ws
    byte matrix, emit steps where no rank appears twice, biggest first."""
    sizes = np.array(size_matrix, dtype=np.int64, copy=True)
    ws = sizes.shape[0]
    np.fill_diagonal(sizes, 0)
    steps: List[List[tuple]] = []
    while sizes.any():
        busy = set()
        step = []
        order = np.argsort(sizes, axis=None)[::-1]
        for flat in order:
            src, dst = divmod(int(flat), ws)
            if sizes[src, dst] == 0 or src in busy or dst in busy:
                continue
            step.append((src, dst))
            busy.add(src)
            busy.add(dst)
            sizes[src, dst] = 0
        steps.append(step)
    return steps


def build_exchange_fn(mesh: Mesh, axis: str, rows_per_host: int, cap: int,
                      dtype=None):
    """One jitted SPMD program implementing the full DistFeature exchange
    (reference comm.py:127-182's two send/recv loops + local gather):

      req_ids [H, H, cap]  req_ids[s, d] = local row ids host s wants of d
      feat    [H*rows_per_host, dim] row-sharded over ``axis`` — a plain
              array or a quantized-tier pytree (``ops.quant``)
      -> resp [H, H, cap, dim]  resp[s, d] = rows host s got from host d

    One ``all_to_all`` ships requests, a local gather reads rows, a second
    ``all_to_all`` ships responses — the reference's allreduced size matrix
    and scheduled pair steps collapse into the collective itself. A
    quantized store ships the NARROW payload + per-row sidecars through
    the response collective and dequantizes after it, so DCN bytes per
    row shrink with the storage width. ``dtype`` is the caller's payload
    dtype (None = the store's own dequantized dtype — never a silent
    fp32 default).
    """

    def body(req, feat):
        # local views: req [1, H, cap], feat [rows_per_host, dim]
        incoming = jax.lax.all_to_all(req, axis, split_axis=1, concat_axis=0)
        ids = jnp.clip(incoming[:, 0, :], 0, rows_per_host - 1)   # [H, cap]
        ship = lambda leaf: jax.lax.all_to_all(
            leaf[ids], axis, split_axis=0, concat_axis=0)
        # quantized payloads cross the collective narrow; dequant AFTER
        resp = quant.dequantize(quant.tree_map_tier(ship, feat), dtype)
        return resp[None]                                         # [1,H,cap,dim]

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False)
    return jax.jit(mapped)


def cap_for_expected_load(per_owner: float, slack: float = 1.25) -> int:
    """THE cap-sizing formula, shared by ``default_exchange_cap`` and
    ``PartitionInfo.plan_exchange_cap`` so the headroom term can't
    drift between them: ``slack`` proportional headroom plus ~3-sigma
    binomial headroom on the expected per-owner unique-request load.
    The sqrt term is what small batches need (a 128-unique batch over
    8 owners overflows a bare mean-sized bucket ~half the time, since
    per-owner skew is relative to sqrt(count)); at production counts
    it vanishes into the slack term."""
    return max(1, int(np.ceil(slack * per_owner
                              + 3.0 * np.sqrt(max(per_owner, 0.0)))))


def default_exchange_cap(batch: int, hosts: int, slack: float = 1.25) -> int:
    """Per-owner request-slot budget for the compact exchange when the
    caller has no partition statistics: assume a multi-hop-frontier
    duplicate factor of >= 8 (bench fanouts run 10-50x) and balanced
    ownership, with ``slack`` headroom for per-owner skew. Callers with
    a real partition should prefer
    ``PartitionInfo.plan_exchange_cap`` (degree-mass-aware sizing)."""
    uniq = max(batch // 8, hosts)
    return min(batch, cap_for_expected_load(uniq / hosts, slack))


@hot_path
def dist_lookup_local(ids: jax.Array, g2h: jax.Array, loc: jax.Array,
                      feat, axis: str, h_count: int,
                      rows_per_host: int, dtype=None, rep=None,
                      exchange_cap: Optional[int] = None,
                      collector=None):
    """The per-shard body of the fused DistFeature lookup — callable from
    INSIDE any ``shard_map`` over ``axis`` (e.g. the multi-host fused
    train step composes it with sampling and the model step):

      ids  [B] this shard's global node ids, -1 fill
      g2h/loc [N] replicated owner / local-row maps
      feat [rows_per_host, dim] this shard's rows — a plain array or a
           quantized-tier pytree (``ops.quant.QuantizedTensor``)
      -> [B, dim] feature rows (zeros at -1 fill)

    An id is resolved ONCE, to one word: the two maps fold into a book,
    ``g2h * rows_per_host + loc`` (the node's row in the row-sharded
    ``[H * rows_per_host, dim]`` store), and a read of the book gives
    owner and local row by one division. Bucket ids by owner (one-hot
    + cumsum), scatter into a static request block, one ``all_to_all``
    ships requests, a local gather reads rows, a second ``all_to_all``
    ships responses, and a final gather unbuckets them into batch
    order. A quantized ``feat`` ships
    the narrow rows + per-row sidecars through the response collective
    and dequantizes only the unbucketed result — the exchange moves
    storage-width bytes, not fp32. ``rep`` optionally carries
    (is_rep [N], rep_rank [N], bases [H]) for replicated-node
    resolution against the calling host's replica tail. ``dtype`` is
    the output dtype; None (the default) uses the store's own
    dequantized dtype — a bf16 store must never silently upcast
    through a hardcoded fp32 here.

    ``exchange_cap`` (None = dense) switches the collectives to the
    COMPACT deduplicated layout: the frontier's valid ids dedup once
    (``ops.dedup.unique_within_budget``: two sorts and a scatter over
    the batch's slots, no search; 8 ms at 1.08 M slots on a v5e),
    only the *unique* ids read the book (the one gather out of an [N]
    table a lookup), each batch slot reads its owner and its place in
    the owner's bucket from its distinct id (``place * H + owner``, one
    gather out of a [B] table; ``B * H`` must stay below 2**31), and
    the unique ids bucket by owner into a [H, cap] request block —
    the same shape ``build_exchange_fn`` uses — and the wire carries
    [H, cap] requests + [H, cap, width] responses instead of [H, B] /
    [H, B, width]; each batch slot then reads its row out of the
    response block. A multi-hop frontier is mostly -1 padding plus
    repeated hub ids, so ``B/cap``-ish fewer bytes cross DCN while
    each distinct remote row moves exactly once. The exchange's
    transient memory is BOUNDED BY ``cap``: no branch of the program
    holds an [H, B, width] block. When an owner's bucket overflows
    ``cap``, the ids past it are served by further rounds of the same
    [H, cap] exchange (a ``fori_loop`` whose trip count is the
    ``pmax`` over ``axis`` of ``ceil(fullest bucket / cap)``: the loop
    carries collectives, so it must be UNIFORM across shards or they
    would deadlock) — never by the dense blocks and never by dropping
    rows. Output is bit-identical to the dense path in every case
    (rows are copied; dequant is elementwise and runs on the rows in
    batch order, as the dense path's does). A cap sized for the
    traffic runs ONE round; each further round costs one more pass
    over the [B, width] output.

    ``collector`` (optional ``metrics.Collector``) records the
    telemetry the cap planner flies blind on: whether the lookup took
    rounds beyond the first (``exchange_fallback``: a cap too small
    for this batch), the peak per-owner bucket load vs ``cap``, and
    the dedup dup statistics — all from values this function already
    computes OUTSIDE the loop (the shard-uniform pmax'd count
    included), so collection adds no host sync and cannot perturb the
    rounds or the output.

    Every op sits under the scope ``qt_exchange``
    (``profiling.QT_EXCHANGE``), its stages beneath it: ``_route``,
    ``_dedup``, ``_bucket``, ``_requests``, ``_gather``,
    ``_responses``, ``_expand``. ``_route`` runs in two places: first
    the book, then (compact) the slots' read of their owner and place
    after ``_bucket``, or (dense) the slots' read of the book;
    ``_bucket`` holds the distinct ids' read of the book, their places
    (one-hot + cumsum), the count of rounds and the request block.
    """
    batch = ids.shape[0]
    valid = ids >= 0
    n_nodes = g2h.shape[0]

    def read(book, ids_, valid_):
        """Global id -> (owning host, local row) by ONE read of the
        book; -1 owner at invalid slots (so they match no bucket).
        Clips from above too: the compact path's unique table carries
        int32-max fill."""
        row = book[jnp.clip(ids_, 0, n_nodes - 1)]
        owner = row // rows_per_host
        return jnp.where(valid_, owner, -1), row - owner * rows_per_host

    def bucket_pos(owner):
        """Each id's place in its owner's bucket, and counts[h] = valid
        ids owned by h (the compact path's count of rounds)."""
        onehot = owner[None, :] == jnp.arange(
            h_count, dtype=owner.dtype)[:, None]            # [H, n]
        bucket_pos = jnp.cumsum(onehot, axis=1) - 1         # [H, n]
        my_pos = jnp.sum(jnp.where(onehot, bucket_pos, 0), axis=0)
        return my_pos, jnp.sum(onehot, axis=1)

    def fill(owner, local, keep, slot, cap_):
        """Scatter local rows into a [H, cap_] per-owner request block.
        Entries outside ``keep`` (-1 fill, another round's ids) must
        route to a POSITIVELY out-of-bounds row:
        `.at[...].set(mode="drop")` resolves negative indices
        NumPy-style BEFORE the bounds check, so owner=-1 would silently
        overwrite host H-1's bucket slot 0."""
        owner_idx = jnp.where(keep, owner, h_count)
        return jnp.zeros((h_count, cap_), jnp.int32).at[
            owner_idx, slot].set(local, mode="drop")

    def ship(req):
        """The collective pair: requests out, local gather, responses
        back. Returns the [H, cap_, ...] response block, leaf by leaf:
        the narrow payload + sidecars cross the collective, dequant
        happens on the unbucketed rows, after the exchange."""
        with profiling.scope(profiling.QT_EXCHANGE_REQUESTS):
            incoming = jax.lax.all_to_all(
                req, axis, split_axis=0, concat_axis=0)
            read = jnp.clip(incoming, 0, rows_per_host - 1)

        def leaf_round(leaf):
            with profiling.scope(profiling.QT_EXCHANGE_GATHER):
                rows = leaf[read]
            with profiling.scope(profiling.QT_EXCHANGE_RESPONSES):
                return jax.lax.all_to_all(
                    rows, axis, split_axis=0, concat_axis=0)

        return quant.tree_map_tier(leaf_round, feat)

    def unbucket(resp, owner, slot):
        """Response block -> the caller's slot order ([n, dim])."""
        return quant.dequantize(quant.tree_map_tier(
            lambda leaf: leaf[jnp.clip(owner, 0), slot], resp))

    def dense():
        with profiling.scope(profiling.QT_EXCHANGE_ROUTE):
            owner, local = read(book, ids, valid)
        with profiling.scope(profiling.QT_EXCHANGE_BUCKET):
            my_pos, counts = bucket_pos(owner)
            req = fill(owner, local, valid, my_pos, batch)
        if collector is not None:
            from .metrics import EXCH_BUCKET_MAX
            collector.peak(EXCH_BUCKET_MAX, jnp.max(counts))
        return unbucket(ship(req), owner, my_pos)

    def compact(cap):
        """Rounds of the SAME [H, cap] exchange over the frontier's
        distinct ids: round ``k`` carries, for every owner, the ids at
        places ``[k * cap, (k + 1) * cap)`` of its bucket, and writes
        their rows to the batch slots that asked for them. One round
        when every bucket fits ``cap``; the count is ``pmax``-reduced
        over ``axis`` first, since the loop carries collectives and
        every shard must run it as often. Only the distinct ids read
        the book; a batch slot reads its owner and its place from its
        distinct id, packed as ``place * H + owner`` (one gather out of
        a [B] table, not the [N] maps)."""
        if batch * h_count >= 2 ** 31:
            raise ValueError(
                f"the compact exchange packs a place and an owner into "
                f"one int32: batch {batch} x hosts {h_count} must stay "
                f"below 2**31")
        with profiling.scope(profiling.QT_EXCHANGE_DEDUP):
            uniq, inv, _ = unique_within_budget(
                ids, batch, valid=valid, collector=collector)
        u_valid = uniq != I32_MAX
        with profiling.scope(profiling.QT_EXCHANGE_BUCKET):
            owner_u, local_u = read(book, uniq, u_valid)
            pos_u, counts = bucket_pos(owner_u)
            fullest = jnp.max(counts)
            rounds = jax.lax.pmax(-(-fullest // cap), axis)
        if collector is not None:
            # recorded outside the loop, the flag on the pmax'd count
            from .metrics import EXCH_BUCKET_MAX, EXCH_CAP, EXCH_FALLBACK
            collector.add(EXCH_FALLBACK, rounds > 1)
            collector.peak(EXCH_BUCKET_MAX, fullest)
            collector.peak(EXCH_CAP, cap)
        with profiling.scope(profiling.QT_EXCHANGE_ROUTE):
            # invalid slots read garbage here: they are masked below
            # and at the end
            slot = (pos_u * h_count + owner_u)[inv]
            owner = jnp.where(valid, slot % h_count, -1)
            pos = slot // h_count        # the place each batch slot reads

        def one_round(k, out):
            base = k * cap
            with profiling.scope(profiling.QT_EXCHANGE_BUCKET):
                req = fill(owner_u, local_u, u_valid & (pos_u // cap == k),
                           jnp.clip(pos_u - base, 0, cap - 1), cap)
            resp = ship(req)
            with profiling.scope(profiling.QT_EXCHANGE_EXPAND):
                rows = unbucket(resp, owner,
                                jnp.clip(pos - base, 0, cap - 1))
                return jnp.where((valid & (pos // cap == k))[:, None],
                                 rows, out)

        return jax.lax.fori_loop(
            0, rounds, one_round,
            jnp.zeros((batch, quant.tier_dim(feat)),
                      quant.tier_dtype(feat)))

    with profiling.scope(profiling.QT_EXCHANGE):
        with profiling.scope(profiling.QT_EXCHANGE_ROUTE):
            # THE resolution of an id, one int32 a node: its row in the
            # row-sharded store. One elementwise pass over the maps,
            # which XLA materializes, so a read of it loads one word an
            # id, not one from each map. Replicated nodes resolve to this
            # host's replica tail.
            book = g2h * rows_per_host + loc
            if rep:
                is_rep, rep_rank, bases = rep
                me = jax.lax.axis_index(axis).astype(book.dtype)
                book = jnp.where(
                    is_rep, me * rows_per_host + bases[me] + rep_rank, book)
        if collector is not None:
            from .metrics import EXCH_CALLS
            collector.add(EXCH_CALLS, 1)
        if exchange_cap is None or int(exchange_cap) >= batch:
            out = dense()
        else:
            out = compact(int(exchange_cap))
        if dtype is None:
            dtype = out.dtype
        with profiling.scope(profiling.QT_EXCHANGE_EXPAND):
            return jnp.where(valid[:, None], out, 0).astype(dtype)


def build_dist_lookup_fn(mesh: Mesh, axis: str, rows_per_host: int,
                         batch_per_host: int, dtype=None,
                         with_replicate: bool = False,
                         exchange_cap: Optional[int] = None,
                         collect_metrics: bool = False,
                         merge_counters: bool = False):
    """The WHOLE DistFeature lookup as one jitted SPMD program
    (reference feature.py:555-567 dispatch + comm.py:127-182 exchange +
    scatter, fused):

      ids  [H*B] global node ids, -1 fill, sharded over ``axis``
      g2h  [N]   node -> owning host            (replicated)
      loc  [N]   node -> local row on its owner (replicated)
      feat [H*rows_per_host, dim] row-sharded over ``axis`` — a plain
           array or a quantized-tier pytree (the P(axis) spec applies
           leaf-wise as a pytree prefix, so int8 rows and their
           sidecars shard together and the exchange ships narrow)
      -> out [H*B, dim] sharded over ``axis`` (zeros at -1 fill);
         dtype = the store's dequantized dtype unless ``dtype`` is
         given explicitly (no silent fp32 default)

    Per shard: bucket ids by owner (one-hot + cumsum — jittable, no host
    round trip), scatter into a [H, B] request block, one ``all_to_all``
    ships requests, a local gather reads rows, a second ``all_to_all``
    ships responses, and a final gather unbuckets them into batch order.

    With ``with_replicate`` the program takes three extra replicated
    operands (is_rep [N] bool, rep_rank [N], bases [H]) and resolves
    replicated nodes against the calling host's replica tail
    (reference feature.py:510-526's replicate override).

    ``exchange_cap`` (None = dense) switches the exchange to the
    compact deduplicated [H, cap] layout — see ``dist_lookup_local``.

    ``collect_metrics=True`` adds a second output: the per-shard
    ``[H, metrics.NUM_COUNTERS]`` int32 device counter block (fallback
    flag, peak bucket load vs cap, dedup statistics) — pure jnp
    accumulation, no host sync, rows bit-identical either way.

    ``merge_counters=True`` (requires ``collect_metrics``) folds that
    block over ``axis`` ON DEVICE before it leaves the program
    (``metrics.pmerge_counters`` — psum add slots, pmax max slots) and
    returns ONE replicated ``[metrics.NUM_COUNTERS]`` vector instead of
    the per-shard block: on a real multi-host mesh, where each process
    can only address its own shard of a ``P(axis)`` output, every
    host then observes the GLOBAL hit/fallback/dup picture. Two extra
    int32-vector collectives per lookup; rows bit-identical either way.
    """
    h_count = mesh.shape[axis]
    if merge_counters and not collect_metrics:
        raise ValueError("merge_counters=True requires "
                         "collect_metrics=True")

    def body(ids, g2h, loc, feat, *rep):
        col = None
        if collect_metrics:
            from .metrics import Collector
            col = Collector()
        out = dist_lookup_local(ids.reshape(-1), g2h, loc, feat, axis,
                                h_count, rows_per_host, dtype,
                                rep=rep or None,
                                exchange_cap=exchange_cap,
                                collector=col)
        if collect_metrics:
            if merge_counters:
                from .metrics import pmerge_counters
                return out, pmerge_counters(col.counters(), axis)
            return out, col.counters()[None]
        return out

    specs = (P(axis), P(), P(), P(axis))
    if with_replicate:
        specs += (P(), P(), P())
    if collect_metrics:
        # merged counters are replicated (every shard holds the global
        # vector after the psum/pmax), so they leave unsharded
        outs = (P(axis), P()) if merge_counters else (P(axis), P(axis))
    else:
        outs = P(axis)
    mapped = shard_map(
        body, mesh=mesh,
        in_specs=specs,
        out_specs=outs,
        check_vma=False)
    return jax.jit(mapped)


class TpuComm:
    """Cross-host exchange driver with the reference ``NcclComm`` surface
    (rank/world_size, allreduce, exchange; quiver_comm.cu:17-86 +
    comm.py:78-182).

    Modes:
    - SPMD (mesh given): requests/responses ride ``all_to_all`` over the
      mesh's host axis — works identically on a virtual CPU mesh, a TPU
      slice (ICI), or multi-slice (DCN).
    - simulation (``peers`` registry): in-process stand-ins for the other
      hosts' Features, for single-process tests of the dispatch protocol.
    """

    def __init__(self, rank: int, world_size: int,
                 comm_id=None, hosts: Optional[int] = None,
                 rank_per_host: int = 1,
                 mesh: Optional[Mesh] = None, axis: str = "host",
                 peers: Optional[dict] = None):
        self.rank = rank
        self.world_size = world_size
        self.table = HostRankTable(hosts or world_size, rank_per_host)
        self.mesh = mesh
        self.axis = axis
        self.peers = peers or {}
        self._exchange_fns = {}

    # -- reference-parity small ops -----------------------------------------
    def allreduce(self, x):
        if self.world_size == 1:
            return x
        from jax.experimental import multihost_utils
        return multihost_utils.process_allgather(jnp.asarray(x)).sum(axis=0)

    def send(self, tensor, dst: int):
        raise NotImplementedError(
            "point-to-point sends do not exist on TPU; use exchange() — "
            "the all_to_all collective is the native equivalent")

    recv = send

    # -- the real path -------------------------------------------------------
    def exchange(self, host_ids: Sequence[np.ndarray], feature):
        """Fetch rows from every remote host. host_ids[h] = local row ids
        this rank needs from host h. Returns per-host row blocks
        (None for self / empty)."""
        results: List[Optional[jax.Array]] = [None] * self.table.hosts
        for h in range(self.table.hosts):
            if h == self.rank or host_ids[h].size == 0:
                continue
            if h in self.peers:
                results[h] = self.peers[h][jnp.asarray(host_ids[h])]
            else:
                raise ValueError(
                    f"no peer registered for host {h} and no mesh-driven "
                    "path engaged: under a mesh, use DistFeature (its "
                    "lookup runs the fused SPMD exchange) or "
                    "exchange_spmd()/build_dist_lookup_fn directly")
        return results

    def exchange_spmd(self, req_ids: jax.Array, feat: jax.Array,
                      cap: Optional[int] = None) -> jax.Array:
        """Single-controller SPMD exchange over the mesh host axis.
        req_ids [H, H, cap] (-1 fill), feat [H*rows, dim] sharded.
        ``cap`` is the per-owner request-slot budget — the knob the
        compact fused exchange shares (``exchange_cap``); None derives
        it from ``req_ids``'s own trailing dimension, so callers that
        already built a capped block don't repeat themselves."""
        if self.mesh is None:
            raise ValueError("exchange_spmd needs a mesh")
        if cap is None:
            cap = int(req_ids.shape[-1])
        h = self.mesh.shape[self.axis]
        rows = quant.tier_rows(feat) // h
        # the store's ACTUAL payload dtype keys (and parameterizes) the
        # program — a bf16 or quantized store never upcasts to fp32
        key = (rows, cap, quant.tier_key(feat))
        fn = self._exchange_fns.get(key)
        if fn is None:
            fn = build_exchange_fn(self.mesh, self.axis, rows, cap,
                                   quant.tier_dtype(feat))
            self._exchange_fns[key] = fn
        return fn(req_ids, feat)
