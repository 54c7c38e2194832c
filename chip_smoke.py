"""Smoke test on the chip: products-SAGE train and serve, once, for real.

    python chip_smoke.py              # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4    # the two cross-chip paths, four chips

Drives the system's main path through the entry points a user calls
(``qv.CSRTopo``, ``qv.Feature``, ``build_train_step``, ``ServeEngine`` /
``MicroBatchServer``) at the published width of products-SAGE
(BASELINE.json configs[1]): a seeded graph of 2,449,029 nodes and about
123.7 M directed edges with lognormal degrees, 100 float32 features
fully in the HBM tier, 47 classes, three SAGE layers of hidden 256,
fanout [15, 10, 5], batch 1024. Weights and data are random, from
``--seed``.

One process, no child. It needs a TPU: on any other backend it exits 1
and prints no result line. Any phase that fails raises, so the script
exits non-zero. Every phase prints one line (wall seconds, seconds spent
in the backend compiler, number of programs compiled); the last line of
stdout is the result,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<checkout>/.jax_cache`` (``quiver_tpu.utils.compile_cache``).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import numpy as np

NODES = 2_449_029
MEAN_DEGREE = 50.517        # 123,718,280 directed edges / NODES
DIM = 100
CLASSES = 47
HIDDEN = 256
SIZES = (15, 10, 5)
BATCH = 1024
SERVE_CAP = 64
SERVE_REQUESTS = 32
ROW_CAP = 2048
LR = 3e-3


class Phases:
    """Times each phase and counts what the backend compiler did in it
    (``jax.monitoring``: one duration event per compiled program)."""

    def __init__(self):
        import jax
        self._compiles = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self._compiles.append(secs)

    @contextlib.contextmanager
    def __call__(self, name, **facts):
        first = len(self._compiles)
        t0 = time.perf_counter()
        yield facts
        wall = time.perf_counter() - t0
        compiled = self._compiles[first:]
        print(json.dumps({"phase": name, "wall_s": round(wall, 3),
                          "compile_s": round(sum(compiled), 3),
                          "programs_compiled": len(compiled), **facts}),
              flush=True)


def make_world(nodes, mean_degree, dim, classes, seed):
    """A seeded planted-label graph: lognormal degrees (sigma 1, capped
    at 10,000, as examples/train_products_synthetic.py draws them),
    uniform neighbours, features = class centre + 0.5 * noise."""
    rng = np.random.default_rng(seed)
    # int() floors: half an edge a node, which the mean makes up for
    mu = np.log(mean_degree + 0.5) - 0.5
    deg = np.minimum(rng.lognormal(mu, 1.0, nodes).astype(np.int64), 10_000)
    indptr = np.zeros(nodes + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, nodes, int(indptr[-1]), dtype=np.int32)
    labels = rng.integers(0, classes, nodes).astype(np.int32)
    centers = rng.standard_normal((classes, dim), dtype=np.float32)
    feat = centers[labels]
    feat += 0.5 * rng.standard_normal((nodes, dim), dtype=np.float32)
    return {"indptr": indptr.astype(np.int32), "indices": indices,
            "feat": feat, "labels": labels, "rng": rng}


def make_state(model, tx, indptr, indices, feat, batch, sizes, key):
    """``init_state`` on an all-zero batch of the step's static shapes."""
    import jax
    import jax.numpy as jnp
    from quiver_tpu.ops import sample_multihop
    from quiver_tpu.parallel.train import (init_state, layers_to_adjs,
                                           masked_feature_gather)

    def example(indptr, indices, feat, seeds):
        n_id, layers = sample_multihop(indptr, indices, seeds, list(sizes),
                                       jax.random.key(0))
        return (masked_feature_gather(feat, n_id, None),
                layers_to_adjs(layers, batch, list(sizes)))

    shapes = jax.eval_shape(example, indptr, indices, feat,
                            jnp.zeros((batch,), jnp.int32))
    x, adjs = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    return jax.jit(lambda x, adjs, key: init_state(model, tx, x, adjs, key))(
        x, adjs, key)


def train_batches(world, batch, steps, seed):
    """``steps`` batches of distinct training seeds with their labels."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    perm = rng.permutation(world["feat"].shape[0])[:batch * steps]
    perm = perm.astype(np.int32).reshape(steps, batch)
    return [(jnp.asarray(p), jnp.asarray(world["labels"][p])) for p in perm]


def run_train(phases, name, step, state, dev, batches, **facts):
    """A warm-up step, then the rest ending in ``block_until_ready``;
    the loss must be finite and lower at the last step than the first.
    Returns ``(state, losses)``."""
    import jax
    with phases(name, **facts) as out:
        losses = []
        t0 = time.perf_counter()
        for i, (seeds, labels) in enumerate(batches):
            state, loss = step(state, dev["feat"], None, dev["indptr"],
                               dev["indices"], seeds, labels,
                               jax.random.key(1000 + i))
            if i == 0:
                jax.block_until_ready(loss)
                out["warmup_step_s"] = round(time.perf_counter() - t0, 3)
                t0 = time.perf_counter()
            losses.append(loss)
        jax.block_until_ready((state, losses))
        out["steady_steps"] = len(batches) - 1
        out["steady_s"] = round(time.perf_counter() - t0, 3)
        losses = [float(l) for l in losses]
        out["losses"] = [round(l, 4) for l in losses]
        if not np.all(np.isfinite(losses)):
            raise AssertionError(f"{name}: non-finite loss {losses}")
        if not losses[-1] < losses[0]:
            raise AssertionError(f"{name}: loss did not fall: {losses}")
    return state, losses


def count_compiled_kernels(step, *args):
    """How many ``tpu_custom_call`` the step's lowered program holds:
    one per hop when the fused walk is the compiled Mosaic kernels, none
    when it is the interpreter."""
    n = step.jitted_fns[0].lower(*args).as_text().count("tpu_custom_call")
    if n != len(SIZES):
        raise AssertionError(
            f"the fused step holds {n} tpu_custom_call, expected "
            f"{len(SIZES)}: it is not the compiled kernels")
    return n


def check_fused_kernel(phases, world, dev, seeds):
    """The fused sample+gather kernel against what it must return: with
    the portable "hash" generator, bit-equal to the split two-program
    oracle compiled for the same chip (the comparison tests/test_fused.py
    makes interpreted); with the chip's own generator, every pick a
    neighbour of its seed, counts = min(degree, k), rows = feature rows."""
    import jax
    import jax.numpy as jnp
    from quiver_tpu.ops.pallas import _dma
    from quiver_tpu.ops.pallas.fused import (fused_hot_hop,
                                             fused_hot_hop_reference,
                                             pad_indices)
    k = SIZES[0]
    with phases("fused_kernel_check", k=k) as out:
        if _dma.default_interpret() or _dma.default_rng() != "tpu":
            raise AssertionError(
                "on a TPU the kernels must default to compiled + on-core "
                f"PRNG, got interpret={_dma.default_interpret()} "
                f"rng={_dma.default_rng()}")
        idx = jax.jit(pad_indices, static_argnums=1)(dev["indices"], ROW_CAP)
        args = (dev["indptr"], idx, seeds, dev["feat"], k, jnp.int32(7))
        got = fused_hot_hop(*args, row_cap=ROW_CAP, rng="hash")
        want = fused_hot_hop_reference(*args, row_cap=ROW_CAP, rng="hash",
                                       interpret=False)
        for name, a, b in zip(("nbrs", "counts", "seed_rows", "pick_rows"),
                              got, want):
            if np.asarray(a).tobytes() != np.asarray(b).tobytes():
                raise AssertionError(
                    f"fused_hot_hop(rng='hash').{name} differs from the "
                    "split oracle on the chip")
        nbrs, counts, seed_rows, pick_rows = (
            np.asarray(a) for a in fused_hot_hop(*args, row_cap=ROW_CAP))
        sd = np.asarray(seeds)
        indptr, indices, feat = (world["indptr"], world["indices"],
                                 world["feat"])
        deg = indptr[sd + 1] - indptr[sd]
        np.testing.assert_array_equal(counts, np.minimum(deg, k))
        for i, v in enumerate(sd):
            row = indices[indptr[v]:indptr[v] + min(deg[i], ROW_CAP)]
            if not np.isin(nbrs[i, :counts[i]], row).all():
                raise AssertionError(f"seed {v}: pick outside its row")
            if not (nbrs[i, counts[i]:] == -1).all():
                raise AssertionError(f"seed {v}: picks past its count")
        np.testing.assert_array_equal(seed_rows, feat[sd])
        flat = nbrs.reshape(-1)
        want_rows = feat[np.maximum(flat, 0)] * (flat >= 0)[:, None]
        np.testing.assert_array_equal(pick_rows, want_rows)
        out["picks"] = int(counts.sum())


def run_serve(phases, model, params, dev, ids, seed):
    """32 point requests through a ``MicroBatchServer``; each must get
    the ``[classes]`` row a second engine, same seed, returns for the
    same seed block called directly (tests/test_serving.py's tolerance).
    Sampling draws from the engine's key chain, so the two engines see
    the same block at the same link of it: one dispatch after warm-up."""
    import jax
    import quiver_tpu as qv
    with phases("serve", requests=len(ids), batch_cap=SERVE_CAP,
                nodes=NODES, dim=DIM, hidden=HIDDEN,
                fanout=list(SIZES)) as out:
        topo = (dev["indptr"], dev["indices"])

        def engine():
            return qv.ServeEngine(model, params, topo, dev["feat"],
                                  sizes_variants=[list(SIZES)],
                                  batch_cap=SERVE_CAP, seed=seed).warmup()

        direct = np.asarray(jax.block_until_ready(
            engine().run(np.asarray(ids, np.int32))))
        srv = qv.MicroBatchServer(
            engine(), qv.ServeConfig(max_wait_ms=50.0, queue_depth=256,
                                     shed_queue_frac=1.0), start=False)
        try:
            futs = [srv.submit(int(i)) for i in ids]
            t0 = time.perf_counter()
            srv.start()
            rows = [f.result(timeout=600) for f in futs]
            out["answer_s"] = round(time.perf_counter() - t0, 3)
            snap = srv.snapshot()["serving"]
        finally:
            srv.close()
        if snap["batches"] != 1:
            raise AssertionError(
                f"expected one coalesced batch, got {snap['batches']}")
        for j, row in enumerate(rows):
            if row.shape != (direct.shape[1],) or not np.isfinite(row).all():
                raise AssertionError(f"request {j}: bad logits {row!r}")
            np.testing.assert_allclose(row, direct[j], rtol=1e-5, atol=1e-6)
        out["out_dim"] = int(direct.shape[1])


def run_tiered(phases, world, seed, cache_frac=0.2, frontier=65_536):
    """The reference's headline layout: a degree-ordered 20 % of rows in
    HBM, the rest pinned in host memory (no numpy fallback allowed); one
    frontier of ``feature[n_id]`` must equal the numpy table's rows."""
    import jax
    import quiver_tpu as qv
    feat = world["feat"]
    n, dim = feat.shape
    with phases("tiered_store", cache_frac=cache_frac, nodes=n,
                dim=dim) as out:
        topo = qv.CSRTopo(indptr=world["indptr"], indices=world["indices"])
        store = qv.Feature(
            device_cache_size=int(n * cache_frac) * dim * feat.itemsize,
            csr_topo=topo, host_placement="offload", allow_fallback=False)
        store.from_cpu_tensor(feat)
        if store._host_offload is None:
            raise AssertionError("the cold tier is not in pinned host memory")
        n_id = np.random.default_rng(seed).integers(
            0, n, min(frontier, n)).astype(np.int32)
        rows = np.asarray(jax.block_until_ready(store[n_id]))
        np.testing.assert_array_equal(rows, feat[n_id])
        hot = np.asarray(store.feature_order)[n_id] < store.cache_rows
        out.update(cache_rows=store.cache_rows, rows=int(n_id.shape[0]),
                   hot_rows=int(hot.sum()))
        if not 0 < hot.sum() < n_id.shape[0]:
            raise AssertionError("the frontier did not touch both tiers")
        store.close()


def one_chip(phases, seed):
    import jax
    import jax.numpy as jnp
    import optax
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.parallel import build_train_step

    with phases("make_graph", nodes=NODES) as out:
        world = make_world(NODES, MEAN_DEGREE, DIM, CLASSES, seed)
        out["edges"] = int(world["indices"].shape[0])
    with phases("to_device"):
        import quiver_tpu as qv
        topo = qv.CSRTopo(indptr=world["indptr"], indices=world["indices"])
        store = qv.Feature(device_cache_size=world["feat"].nbytes)
        store.from_cpu_tensor(world["feat"])
        if store.host_part is not None or store.feature_order is not None:
            raise AssertionError("the feature table is not fully in HBM")
        dev = {"indptr": topo.indptr, "indices": topo.indices,
               "feat": store.device_part}
        jax.block_until_ready(dev)

    model = GraphSAGE(hidden_dim=HIDDEN, out_dim=CLASSES,
                      num_layers=len(SIZES))
    tx = optax.adam(LR)
    with phases("init_state"):
        state0 = make_state(model, tx, dev["indptr"], dev["indices"],
                            dev["feat"], BATCH, SIZES, jax.random.key(seed))
        jax.block_until_ready(state0)
    batches = train_batches(world, BATCH, 6, seed)
    config = dict(nodes=NODES, dim=DIM, hidden=HIDDEN, fanout=list(SIZES),
                  batch=BATCH)

    # both paths start from the same weights; a step donates its state
    split = build_train_step(model, tx, SIZES, BATCH, method="exact")
    state, split_losses = run_train(
        phases, "train_split", split, jax.tree.map(jnp.copy, state0), dev,
        batches, **config)

    fused = build_train_step(model, tx, SIZES, BATCH, method="exact",
                             fused_hot_hop=True, fused_row_cap=ROW_CAP)
    seeds, labels = batches[0]
    n_kernels = count_compiled_kernels(
        fused, state0, dev["feat"], None, dev["indptr"], dev["indices"],
        seeds, labels, jax.random.key(1000))
    _, fused_losses = run_train(phases, "train_fused", fused, state0, dev,
                                batches, tpu_custom_call=n_kernels, **config)
    # same weights and seeds, another sampling stream: the first losses
    # differ by sampling noise only
    if abs(fused_losses[0] - split_losses[0]) > 0.1 * split_losses[0]:
        raise AssertionError(
            f"first-step loss: fused {fused_losses[0]} vs split "
            f"{split_losses[0]}, outside the 10 % band")
    check_fused_kernel(phases, world, dev, seeds)

    ids = world["rng"].choice(NODES, SERVE_REQUESTS, replace=False)
    run_serve(phases, model, state.params, dev, ids, seed)
    del dev, store, topo, state, state0
    run_tiered(phases, world, seed)


def four_chips(phases, seed):
    """What exists only across chips, each with what it is compared
    with: data-parallel training over a ("data",) mesh against the
    one-device step on device 0, and the row-sharded feature store
    against the numpy table."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P
    import quiver_tpu as qv
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.parallel import (build_e2e_train_step, build_train_step,
                                     make_mesh)

    n_dev = len(jax.devices())
    with phases("make_graph", nodes=NODES) as out:
        world = make_world(NODES, MEAN_DEGREE, DIM, CLASSES, seed)
        out["edges"] = int(world["indices"].shape[0])
    mesh = make_mesh(("data",))
    rep = NamedSharding(mesh, P())
    with phases("to_device", devices=n_dev):
        dev = {k: jax.device_put(world[k], rep)
               for k in ("indptr", "indices", "feat")}
        jax.block_until_ready(dev)
    model = GraphSAGE(hidden_dim=HIDDEN, out_dim=CLASSES,
                      num_layers=len(SIZES))
    tx = optax.adam(LR)
    d0 = jax.devices()[0]
    on0 = lambda t: jax.tree.map(lambda a: jax.device_put(a, d0), t)
    with phases("init_state"):
        dev0 = on0(dev)
        state0 = make_state(model, tx, dev0["indptr"], dev0["indices"],
                            dev0["feat"], BATCH, SIZES, jax.random.key(seed))
        jax.block_until_ready(state0)

    steps = 3
    batches = train_batches(world, n_dev * BATCH, steps, seed)
    sharded = NamedSharding(mesh, P("data"))
    dp = build_e2e_train_step(model, tx, SIZES, BATCH, mesh)
    with phases("train_data_parallel", devices=n_dev,
                global_batch=n_dev * BATCH, nodes=NODES, hidden=HIDDEN,
                fanout=list(SIZES)) as out:
        # a copy: the step donates its state, and device 0's replica may
        # be state0's own buffer
        state = jax.device_put(jax.tree.map(jnp.copy, state0), rep)
        losses = []
        for i, (seeds, labels) in enumerate(batches):
            state, loss = dp(state, dev["feat"], None, dev["indptr"],
                             dev["indices"], jax.device_put(seeds, sharded),
                             jax.device_put(labels, sharded),
                             jax.random.key(1000 + i))
            losses.append(float(loss))
        jax.block_until_ready(state)
        out["losses"] = [round(l, 4) for l in losses]
        if not np.all(np.isfinite(losses)):
            raise AssertionError(f"non-finite loss {losses}")
        # gradients were pmean-ed: every device holds the same weights
        for leaf in jax.tree.leaves(state.params):
            shards = [np.asarray(s.data) for s in leaf.addressable_shards]
            if len({s.device for s in leaf.addressable_shards}) != n_dev:
                raise AssertionError("a parameter is not on every device")
            for s in shards[1:]:
                if s.tobytes() != shards[0].tobytes():
                    raise AssertionError("parameters differ across devices")
        out["params_identical_on"] = n_dev

    # shard i of the data-parallel step IS the one-device step on shard
    # i's seeds with key fold_in(key, i); its loss is their mean
    # (tests/test_dist_train.py's tolerance)
    one = build_train_step(model, tx, SIZES, BATCH, method="exact",
                           donate=False)
    with phases("train_one_device_reference", device=str(d0)) as out:
        seeds, labels = batches[0]
        ref = []
        for i in range(n_dev):
            part = slice(i * BATCH, (i + 1) * BATCH)
            _, loss = one(state0, dev0["feat"], None, dev0["indptr"],
                          dev0["indices"], on0(seeds[part]),
                          on0(labels[part]),
                          jax.random.fold_in(jax.random.key(1000), i))
            ref.append(float(loss))
        out.update(shard_losses=[round(l, 4) for l in ref],
                   data_parallel_loss=round(losses[0], 4))
        np.testing.assert_allclose(losses[0], np.mean(ref), rtol=1e-5)
    del dev, dev0, state, state0

    with phases("sharded_store", devices=n_dev) as out:
        feat = world["feat"]
        store = qv.Feature(device_cache_size=feat.nbytes // n_dev,
                           cache_policy="p2p_clique_replicate",
                           mesh=make_mesh(("cache",)))
        store.from_cpu_tensor(feat)
        shards = store.device_part.addressable_shards
        owners = {s.device for s in shards}
        if len(owners) != n_dev:
            raise AssertionError(f"hot rows sit on {len(owners)} devices")
        rows_on = sorted(int(s.data.shape[0]) for s in shards)
        if rows_on[0] != rows_on[-1] or \
                abs(rows_on[0] * n_dev - feat.shape[0]) >= n_dev:
            raise AssertionError(f"uneven row shards: {rows_on}")
        n_id = world["rng"].integers(0, NODES, 65_536).astype(np.int32)
        rows = np.asarray(jax.block_until_ready(store[n_id]))
        np.testing.assert_array_equal(rows, feat[n_id])
        out.update(shard_rows=rows_on, shard_devices=sorted(
            str(d) for d in owners), rows=int(n_id.shape[0]))
        store.close()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the cross-chip paths (data-parallel "
                         "training, the row-sharded store)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from quiver_tpu.utils.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    print(json.dumps({"compile_cache": cache_dir, "seed": args.seed,
                      "jax": jax.__version__}), flush=True)
    phases = Phases()
    (one_chip if args.chips == 1 else four_chips)(phases, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
