"""The registered jitted hot paths ``qt_verify`` checks.

Every entry point the serving/training system can reach at runtime is
declared here as an :class:`~quiver_tpu.analysis.jaxpr_lint.EntrySpec`
builder: a small-CPU-shape instantiation of the REAL builder (same code
path production takes — ``build_train_step``, ``build_e2e_train_step``,
``build_dist_train_step``, ``build_dist_lookup_fn`` /
``dist_lookup_local``, ``build_serve_step`` via ``ServeEngine``,
``Feature.lookup_tiered``; the step builders take the walk's knobs as the
``**walk`` that ``parallel.frontier.Walk.of`` validates, and every one of
them draws and gathers in ``parallel.frontier.walk_frontier``) plus the
invariants it promises: sync-free,
donation-honored, shard-uniform branching, traffic budgets, and the
executable-census lattice. Shapes are tiny (tracing only — nothing
compiles), so the full registry runs in seconds on CPU.

Mesh entries trace over ALL visible devices — run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
tests/conftest.py convention; ``scripts/qt_verify.py`` sets it before
importing jax).

Registering a new entry point: write a builder returning an
``EntrySpec`` and call :func:`register_entry` (see docs/analysis.md).
Tests use the same hook to register seeded-violation entries.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

from .jaxpr_lint import CensusSpec, EntrySpec, run_rules

# name -> (builder, quick): quick entries form the mini matrix
# ``qt_verify --quick`` (and scripts/lint.sh) runs
_REGISTRY: Dict[str, tuple] = {}


def register_entry(name: str, builder: Callable[[], EntrySpec],
                   quick: bool = False) -> None:
    _REGISTRY[name] = (builder, quick)


def entry_names(quick: bool = False) -> List[str]:
    return [n for n, (_, q) in _REGISTRY.items() if q or not quick]


def build_entry_specs(name: str) -> List[EntrySpec]:
    """ALL specs of one entry — a builder may return several so every
    point of its census lattice (each fanout variant, each jit arity)
    is actually traced by the rules, not just a representative."""
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown entry point {name!r}; registered: "
            f"{', '.join(sorted(_REGISTRY))}")
    built = _REGISTRY[name][0]()
    return list(built) if isinstance(built, (list, tuple)) else [built]


def build_entry(name: str) -> EntrySpec:
    """The entry's primary spec (the one carrying its census)."""
    return build_entry_specs(name)[0]


def run_registry(names: Optional[List[str]] = None,
                 quick: bool = False):
    """Build + verify entries; returns ``(findings, entries_run)``."""
    findings, ran = [], []
    for name in (names or entry_names(quick=quick)):
        for spec in build_entry_specs(name):
            findings += run_rules(spec)
        ran.append(name)
    return findings, ran


# ---------------------------------------------------------------------------
# shared small-shape fixture (built once per process)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _fixture() -> SimpleNamespace:
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    from ..models import GraphSAGE
    from ..ops.sample_multihop import sample_multihop
    from ..parallel.train import (init_state, layers_to_adjs,
                                  masked_feature_gather)

    rng = np.random.default_rng(0)
    n, dim, bs, sizes = 256, 16, 8, [3, 2]
    deg = rng.integers(1, 6, n).astype(np.int64)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, n, int(indptr[-1]), dtype=np.int32)
    indptr_j = jnp.asarray(indptr.astype(np.int32))
    indices_j = jnp.asarray(indices)
    feat = jnp.asarray(rng.standard_normal((n, dim)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 4, n).astype(np.int32))
    model = GraphSAGE(hidden_dim=8, out_dim=4, num_layers=2, dropout=0.0)
    tx = optax.adam(1e-3)
    seeds = jnp.arange(bs, dtype=jnp.int32)
    n_id, layers = sample_multihop(indptr_j, indices_j, seeds, sizes,
                                   jax.random.key(0))
    state = init_state(model, tx, masked_feature_gather(feat, n_id),
                       layers_to_adjs(layers, bs, sizes),
                       jax.random.key(1))
    return SimpleNamespace(n=n, dim=dim, bs=bs, sizes=sizes,
                           indptr_np=indptr, indices_np=indices,
                           indptr=indptr_j, indices=indices_j,
                           feat=feat, labels=labels, model=model,
                           tx=tx, seeds=seeds, state=state)


def _mesh(axis: str):
    import numpy as np
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()), (axis,))


def _frontier_cap(batch: int, sizes) -> int:
    from ..pyg.sage_sampler import layer_shapes
    return layer_shapes(batch, sizes)[-1].n_id_cap


# ---------------------------------------------------------------------------
# the entries
# ---------------------------------------------------------------------------


def _train_step() -> EntrySpec:
    import jax
    from ..parallel import build_train_step
    fx = _fixture()
    step = build_train_step(fx.model, fx.tx, fx.sizes, fx.bs,
                            dedup_gather=True, collect_metrics=True)
    args = (fx.state, fx.feat, None, fx.indptr, fx.indices, fx.seeds,
            fx.labels[fx.seeds], jax.random.key(2))
    return EntrySpec(
        name="train_step", fn=step.jitted_fns[0], args=args,
        donate_argnums=(0,),
        census=CensusSpec({"program": ("fused",)}, max_programs=1))


def _lookup_tiered() -> EntrySpec:
    import numpy as np
    import jax.numpy as jnp
    from ..feature import Feature
    from ..utils import CSRTopo
    fx = _fixture()
    budget = 64
    topo = CSRTopo(indptr=fx.indptr_np, indices=fx.indices_np)
    store = Feature(device_cache_size=(fx.n // 4) * fx.dim * 4,
                    csr_topo=topo, dedup_cold=True, cold_budget=budget)
    store.from_cpu_tensor(np.asarray(fx.feat))
    host = jnp.asarray(store.host_part)
    ids = jnp.asarray(
        np.random.default_rng(1).integers(0, fx.n, 128, dtype=np.int32))
    raw = store._lookup_tiered_raw

    def fn(dev_part, host_part, ids_, order):
        # the driven lattice: unmasked, metered — phase 5/9's path
        return raw(dev_part, host_part, ids_, order, False, True)

    return EntrySpec(
        name="lookup_tiered", fn=fn,
        args=(store.device_part, host, ids, store.feature_order),
        tier_budgets=((host, budget, 0),),
        census=CensusSpec({"masked": (False,), "collect": (True,)},
                          max_programs=1),
        detail={"cold_budget": budget})


def _dist_lookup() -> EntrySpec:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from ..comm import build_dist_lookup_fn
    fx = _fixture()
    h = len(jax.devices())
    rows, batch, cap = 32, 64, 8
    mesh = _mesh("host")
    fn = build_dist_lookup_fn(mesh, "host", rows, batch,
                              exchange_cap=cap, collect_metrics=True,
                              merge_counters=True)
    total = h * rows
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(0, total, h * batch, dtype=np.int32))
    g2h = jnp.asarray((np.arange(total) // rows).astype(np.int32))
    loc = jnp.asarray((np.arange(total) % rows).astype(np.int32))
    feat = jnp.asarray(
        rng.standard_normal((total, fx.dim)).astype(np.float32))
    dense_bytes = h * batch * 4 + h * batch * fx.dim * 4
    return EntrySpec(
        name="dist_lookup", fn=fn, args=(ids, g2h, loc, feat),
        exchange={"prims": ("all_to_all",),
                  "dense_bytes": dense_bytes, "max_frac": 0.25,
                  "dense_shapes": ((h, batch), (h, batch, fx.dim))},
        census=CensusSpec({"program": ("fused",)}, max_programs=1),
        detail={"exchange_cap": cap, "batch_per_host": batch})


def _serve_step() -> List[EntrySpec]:
    import jax
    from ..serving import ServeEngine
    fx = _fixture()
    engine = ServeEngine(fx.model, fx.state.params,
                         (fx.indptr, fx.indices), fx.feat,
                         sizes_variants=[[3, 2], [2, 1], [1, 1]],
                         batch_cap=16, dedup_gather=True,
                         collect_metrics=True)
    seeds = engine.pad_seeds(list(range(8)))
    args = (engine.params, engine._key, engine._feat, engine._forder,
            engine._indptr, engine._indices,
            jax.numpy.asarray(seeds))
    census = CensusSpec({"fanout_variant": tuple(
        tuple(v) for v in engine.variants)}, max_programs=4)
    # EVERY ladder variant is traced (a host sync introduced only in
    # the shed variant must not slip past the verifier); the census
    # rides the primary spec once
    return [EntrySpec(
        name="serve_step" if v == 0 else f"serve_step[variant{v}]",
        fn=step, args=args,
        donate_argnums=(1,),        # the threaded PRNG key chain
        census=census if v == 0 else None,
        detail={"batch_cap": engine.batch_cap,
                "fanout": engine.variants[v]})
        for v, step in enumerate(engine._steps)]


def _sharded_serve_step() -> List[EntrySpec]:
    import numpy as np
    import jax
    from ..feature import DistFeature, PartitionInfo
    from ..comm import TpuComm
    from ..serving import ShardedServeEngine
    fx = _fixture()
    h = len(jax.devices())
    cap = 16
    mesh = _mesh("host")
    # identity partition: global id g lives at (host g//rows, row g%rows)
    rows = fx.n // h
    g2h = (np.arange(fx.n) // rows).astype(np.int32)
    info = PartitionInfo(host=0, hosts=h, global2host=g2h)
    comm = TpuComm(rank=0, world_size=h, mesh=mesh, axis="host")
    dist = DistFeature.from_partition(np.asarray(fx.feat), info, comm,
                                      exchange_cap=cap)
    engine = ShardedServeEngine(fx.model, fx.state.params,
                                (fx.indptr, fx.indices), dist,
                                sizes_variants=[[3, 2], [2, 1], [1, 1]],
                                batch_cap=16, home=0,
                                collect_metrics=True)
    seeds = jax.numpy.asarray(engine.pad_seeds(list(range(8))))
    args = (engine.params, engine._key, dist._spmd_feat, engine._g2h,
            engine._g2l, engine._indptr, engine._indices, seeds)
    census = CensusSpec({"fanout_variant": tuple(
        tuple(v) for v in engine.variants)}, max_programs=4)

    def budget(sizes):
        frontier = _frontier_cap(engine.batch_cap, sizes)
        dense = h * frontier * 4 + h * frontier * fx.dim * 4
        return {"prims": ("all_to_all",), "dense_bytes": dense,
                "max_frac": 0.25,
                "dense_shapes": ((h, frontier), (h, frontier, fx.dim))}

    # EVERY ladder variant is traced (each is its own shard_map program
    # over the partitioned store); the census rides the primary once
    return [EntrySpec(
        name="sharded_serve_step" if v == 0
        else f"sharded_serve_step[variant{v}]",
        fn=step, args=args,
        donate_argnums=(1,),        # the threaded PRNG key chain
        exchange=budget(engine.variants[v]),
        census=census if v == 0 else None,
        detail={"batch_cap": engine.batch_cap, "exchange_cap": cap,
                "home": engine.home, "fanout": engine.variants[v]})
        for v, step in enumerate(engine._steps)]


def _rows_view():
    """The exact-mode wide-path layout view of the fixture's indices
    (what callers pass as ``indices_rows``) — lets the rows arity of
    the shard_map builders be traced, not just declared in the
    census."""
    from ..ops import as_index_rows
    return as_index_rows(_fixture().indices)


def _e2e_train_step() -> List[EntrySpec]:
    import jax
    from ..parallel import build_e2e_train_step
    fx = _fixture()
    h = len(jax.devices())
    mesh = _mesh("data")
    per_dev = 4
    step = build_e2e_train_step(fx.model, fx.tx, fx.sizes, per_dev,
                                mesh, dedup_gather=True,
                                collect_metrics=True,
                                merge_counters=True)
    seeds = jax.numpy.arange(h * per_dev, dtype=jax.numpy.int32)
    args = (fx.state, fx.feat, None, fx.indptr, fx.indices, seeds,
            fx.labels[seeds], jax.random.key(3))
    census = CensusSpec({"rows_arity": (False, True)}, max_programs=2)
    return [
        EntrySpec(name="e2e_train_step", fn=step.jitted_fns[1],
                  args=args, donate_argnums=(0,), census=census),
        # the with-rows arity (wide-exact path) is its own program —
        # trace it too so both census points are actually verified
        EntrySpec(name="e2e_train_step[rows]", fn=step.jitted_fns[0],
                  args=args + (_rows_view(),), donate_argnums=(0,))]


def _dist_train_step() -> EntrySpec:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from ..parallel import build_dist_train_step
    fx = _fixture()
    h = len(jax.devices())
    mesh = _mesh("host")
    rows = fx.n // h
    per_host, cap = 4, 8
    step = build_dist_train_step(fx.model, fx.tx, fx.sizes, per_host,
                                 mesh, rows_per_host=rows,
                                 exchange_cap=cap,
                                 collect_metrics=True,
                                 merge_counters=True)
    # identity partition: global id g lives at (host g//rows, row g%rows)
    g2h = jnp.asarray((np.arange(fx.n) // rows).astype(np.int32))
    g2l = jnp.asarray((np.arange(fx.n) % rows).astype(np.int32))
    seeds = jnp.arange(h * per_host, dtype=jnp.int32)
    args = (fx.state, fx.feat, g2h, g2l, fx.indptr, fx.indices, seeds,
            fx.labels[seeds], jax.random.key(4))
    frontier = _frontier_cap(per_host, fx.sizes)
    dense_bytes = h * frontier * 4 + h * frontier * fx.dim * 4
    exchange = {"prims": ("all_to_all",),
                "dense_bytes": dense_bytes, "max_frac": 0.25,
                "dense_shapes": ((h, frontier), (h, frontier, fx.dim))}
    detail = {"exchange_cap": cap, "frontier_cap": frontier}
    return [
        EntrySpec(name="dist_train_step",
                  fn=step.jitted_fns[1],    # the no-indices_rows arity
                  args=args, donate_argnums=(0,), exchange=exchange,
                  census=CensusSpec({"rows_arity": (False, True)},
                                    max_programs=2),
                  detail=detail),
        EntrySpec(name="dist_train_step[rows]", fn=step.jitted_fns[0],
                  args=args + (_rows_view(),), donate_argnums=(0,),
                  exchange=exchange, detail=detail)]


def _fused_hot_hop() -> List[EntrySpec]:
    import numpy as np
    import jax.numpy as jnp
    from ..ops import quant
    from ..ops.pallas.fused import (default_interpret, fused_hot_hop,
                                    pad_indices)
    fx = _fixture()
    k, row_cap = 4, 64
    rng = np.random.default_rng(3)
    # dedicated lane-aligned table: per-row feature DMAs need the row
    # width to be a multiple of 128 (the fixture's dim-16 table would
    # trip the full-table pad cliff on every call)
    wide = jnp.asarray(
        rng.standard_normal((fx.n, 128)).astype(np.float32))
    feat_q = quant.quantize(wide, "int8")
    idx = pad_indices(fx.indices, row_cap)
    interpret = default_interpret()

    def make(feat):
        def fn(indptr, indices_padded, seeds, seed):
            # the portable "hash" rng: the entry is executable (the
            # profiler runs registry entries) and bit-compatible with
            # the split oracle on every backend
            return fused_hot_hop(indptr, indices_padded, seeds, feat,
                                 k, seed, row_cap=row_cap, rng="hash",
                                 interpret=interpret)
        return fn

    args = (fx.indptr, idx, fx.seeds, jnp.int32(7))
    # rows the kernel DMAs from the tier per call: one padded seed
    # block plus its picks (no gather eqn exists to meter — the budget
    # bounds the structural _pallas_tier_rows count via costmodel)
    budget = 128 * (1 + k)
    return [
        EntrySpec(
            name="fused_hot_hop", fn=make(feat_q), args=args,
            tier_budgets=((feat_q, budget, 0),),
            census=CensusSpec({"variant": ("quantized", "plain")},
                              max_programs=2),
            detail={"k": k, "row_cap": row_cap, "rng": "hash"}),
        # the plain-f32 tier variant is its own program — trace it too
        # so both census points are actually verified
        EntrySpec(
            name="fused_hot_hop[plain]", fn=make(wide), args=args,
            tier_budgets=((wide, budget, 0),))]


def _fused_multihop() -> List[EntrySpec]:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from ..ops import quant
    from ..ops.pallas.fused import (default_interpret, fused_multihop,
                                    pad_indices)
    fx = _fixture()
    sizes, row_cap = [3, 2], 64
    rng = np.random.default_rng(3)
    # same lane-aligned dim-128 table as the single-hop entry (per-row
    # feature DMAs need a multiple-of-128 row width)
    wide = jnp.asarray(
        rng.standard_normal((fx.n, 128)).astype(np.float32))
    feat_q = quant.quantize(wide, "int8")
    idx = pad_indices(fx.indices, row_cap)
    interpret = default_interpret()

    def make(feat):
        def fn(indptr, indices_padded, seeds, key):
            # the whole fused walk — interior sampling-only hops,
            # leaf sample+gather hop, gather-free compaction and the
            # frontier-block reassembly: the multi-hop train/serve
            # front-end whose modeled gather_index_bytes must be 0
            return fused_multihop(indptr, indices_padded, seeds, feat,
                                  sizes, key, row_cap=row_cap,
                                  rng="hash", interpret=interpret)
        return fn

    args = (fx.indptr, idx, fx.seeds, jax.random.key(11))
    # tier rows the LEAF kernel DMAs per call: its seed block is the
    # hop-0 frontier cap (8 * (1+3) = 32) padded to one 128-seed grid
    # block, each block reading (1 + k_leaf) rows per seed; interior
    # hops never touch the tier
    budget = 128 * (1 + sizes[-1])
    return [
        EntrySpec(
            name="fused_multihop", fn=make(feat_q), args=args,
            tier_budgets=((feat_q, budget, 0),),
            census=CensusSpec({"variant": ("quantized", "plain")},
                              max_programs=2),
            detail={"sizes": tuple(sizes), "row_cap": row_cap,
                    "rng": "hash"}),
        EntrySpec(
            name="fused_multihop[plain]", fn=make(wide), args=args,
            tier_budgets=((wide, budget, 0),))]


register_entry("train_step", _train_step, quick=True)
register_entry("lookup_tiered", _lookup_tiered, quick=True)
register_entry("dist_lookup", _dist_lookup, quick=True)
register_entry("serve_step", _serve_step, quick=True)
register_entry("sharded_serve_step", _sharded_serve_step, quick=True)
register_entry("fused_hot_hop", _fused_hot_hop, quick=True)
register_entry("fused_multihop", _fused_multihop, quick=True)
register_entry("e2e_train_step", _e2e_train_step)
register_entry("dist_train_step", _dist_train_step)
