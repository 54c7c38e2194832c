"""Multi-host fused train step: sample + distributed feature exchange +
train as one shard_map program, on the virtual 8-host mesh.

The key equivalence: with the same state/seeds/keys, the dist step must
produce EXACTLY the loss of the plain data-parallel step — the only
difference is that features arrive via the partitioned all_to_all
exchange instead of a replicated-array gather."""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import quiver_tpu as qv
from quiver_tpu.models import GraphSAGE
from quiver_tpu.ops import sample_multihop
from quiver_tpu.parallel import (build_dist_train_step,
                                 build_e2e_train_step)
from quiver_tpu.parallel.train import (init_state, layers_to_adjs,
                                       masked_feature_gather)


@pytest.fixture
def setup(rng):
    n, dim, classes, hosts = 240, 12, 4, 8
    deg = rng.integers(1, 9, n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, n, int(indptr[-1]), dtype=np.int32)
    feat = rng.standard_normal((n, dim)).astype(np.float32)
    labels = rng.integers(0, classes, n).astype(np.int32)
    g2h = rng.integers(0, hosts, n).astype(np.int32)
    g2h[:hosts] = np.arange(hosts)        # every host owns something

    mesh = Mesh(np.array(jax.devices()), axis_names=("host",))
    info = qv.PartitionInfo(host=0, hosts=hosts, global2host=g2h)
    comm = qv.TpuComm(rank=0, world_size=hosts, mesh=mesh, axis="host")
    dist = qv.DistFeature.from_partition(feat, info, comm)

    sizes, per_host = [3, 2], 8
    model = GraphSAGE(hidden_dim=16, out_dim=classes, num_layers=2,
                      dropout=0.0)
    tx = optax.adam(1e-2)
    indptr_j = jnp.asarray(indptr.astype(np.int32))
    indices_j = jnp.asarray(indices)
    n_id, layers = sample_multihop(indptr_j, indices_j,
                                   jnp.arange(per_host, dtype=jnp.int32),
                                   sizes, jax.random.key(0))
    state = init_state(model, tx,
                       masked_feature_gather(jnp.asarray(feat), n_id),
                       layers_to_adjs(layers, per_host, sizes),
                       jax.random.key(1))
    return (mesh, info, dist, model, tx, sizes, per_host, indptr_j,
            indices_j, jnp.asarray(feat), jnp.asarray(labels), state,
            hosts)


class TestDistTrainStep:
    def test_matches_data_parallel_step(self, setup, rng):
        (mesh, info, dist, model, tx, sizes, per_host, indptr, indices,
         feat, labels, state, hosts) = setup
        g = hosts * per_host
        seeds = jnp.asarray(
            rng.choice(240, g, replace=False).astype(np.int32))
        y = labels[seeds]
        key = jax.random.key(11)
        sharding = NamedSharding(mesh, P("host"))
        seeds_s = jax.device_put(seeds, sharding)
        y_s = jax.device_put(y, sharding)

        # donate=False: the dist arm replays the SAME state right after
        dp_step = build_e2e_train_step(model, tx, sizes, per_host, mesh,
                                       axis="host", donate=False)
        dp_state, dp_loss = dp_step(state, feat, None, indptr, indices,
                                    seeds_s, y_s, key)

        dist_step = build_dist_train_step(
            model, tx, sizes, per_host, mesh,
            rows_per_host=dist._rows_per_host)
        d_state, d_loss = dist_step(
            state, dist._spmd_feat, info.global2host.astype(jnp.int32),
            info.global2local, indptr, indices, seeds_s, y_s, key)

        np.testing.assert_allclose(float(d_loss), float(dp_loss),
                                   rtol=1e-5)
        a = np.asarray(
            dp_state.params["params"]["conv0"]["lin_nbr"]["kernel"])
        b = np.asarray(
            d_state.params["params"]["conv0"]["lin_nbr"]["kernel"])
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-6)

    def test_rotation_mode_matches_dp(self, setup, rng):
        (mesh, info, dist, model, tx, sizes, per_host, indptr, indices,
         feat, labels, state, hosts) = setup
        from quiver_tpu.ops import (as_index_rows, edge_row_ids,
                                    permute_csr)
        g = hosts * per_host
        rids = edge_row_ids(indptr, int(indices.shape[0]))
        rows = as_index_rows(permute_csr(indices, rids,
                                         jax.random.key(3)))
        seeds = jnp.asarray(
            rng.choice(240, g, replace=False).astype(np.int32))
        y = labels[seeds]
        key = jax.random.key(21)
        sharding = NamedSharding(mesh, P("host"))
        seeds_s = jax.device_put(seeds, sharding)
        y_s = jax.device_put(y, sharding)

        dp_step = build_e2e_train_step(model, tx, sizes, per_host, mesh,
                                       axis="host", method="rotation",
                                       donate=False)
        _, dp_loss = dp_step(state, feat, None, indptr, indices, seeds_s,
                             y_s, key, rows)
        dist_step = build_dist_train_step(
            model, tx, sizes, per_host, mesh,
            rows_per_host=dist._rows_per_host, method="rotation")
        _, d_loss = dist_step(
            state, dist._spmd_feat, info.global2host.astype(jnp.int32),
            info.global2local, indptr, indices, seeds_s, y_s, key,
            indices_rows=rows)
        np.testing.assert_allclose(float(d_loss), float(dp_loss),
                                   rtol=1e-5)

    def test_replicated_nodes_resolve_correctly(self, rng):
        # hot nodes replicated on every host must come back with the
        # right features through the fused step's gather (regression:
        # without the rep plumbing they were mis-routed to their owner
        # with a replica-tail-local index)
        n, dim, classes, hosts = 160, 8, 4, 8
        deg = rng.integers(1, 7, n)
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(deg, out=indptr[1:])
        indices = rng.integers(0, n, int(indptr[-1]), dtype=np.int32)
        feat = rng.standard_normal((n, dim)).astype(np.float32)
        labels = rng.integers(0, classes, n).astype(np.int32)
        g2h = rng.integers(0, hosts, n).astype(np.int32)
        g2h[:hosts] = np.arange(hosts)
        rep = np.array([3, 77, 140], np.int32)

        mesh = Mesh(np.array(jax.devices()), axis_names=("host",))
        info = qv.PartitionInfo(host=0, hosts=hosts, global2host=g2h,
                                replicate=rep)
        comm = qv.TpuComm(rank=0, world_size=hosts, mesh=mesh,
                          axis="host")
        dist = qv.DistFeature.from_partition(feat, info, comm)

        sizes, per_host = [3, 2], 6
        model = GraphSAGE(hidden_dim=16, out_dim=classes, num_layers=2,
                          dropout=0.0)
        tx = optax.adam(1e-2)
        indptr_j = jnp.asarray(indptr.astype(np.int32))
        indices_j = jnp.asarray(indices)
        n_id, layers = sample_multihop(
            indptr_j, indices_j, jnp.arange(per_host, dtype=jnp.int32),
            sizes, jax.random.key(0))
        state = init_state(model, tx,
                           masked_feature_gather(jnp.asarray(feat), n_id),
                           layers_to_adjs(layers, per_host, sizes),
                           jax.random.key(1))

        g = hosts * per_host
        # seed batches heavy on the replicated ids
        seeds = np.tile(rep, g // 3 + 1)[:g].astype(np.int32)
        seeds[1::2] = rng.choice(n, g // 2, replace=False)
        sharding = NamedSharding(mesh, P("host"))
        seeds_s = jax.device_put(jnp.asarray(seeds), sharding)
        y_s = jax.device_put(jnp.asarray(labels[seeds]), sharding)
        key = jax.random.key(33)

        dp_step = build_e2e_train_step(model, tx, sizes, per_host, mesh,
                                       axis="host", donate=False)
        _, dp_loss = dp_step(state, jnp.asarray(feat), None, indptr_j,
                             indices_j, seeds_s, y_s, key)
        dist_step = build_dist_train_step(
            model, tx, sizes, per_host, mesh,
            rows_per_host=dist._rows_per_host, with_replicate=True)
        _, d_loss = dist_step(
            state, dist._spmd_feat, info.global2host.astype(jnp.int32),
            info.global2local, indptr_j, indices_j, seeds_s, y_s, key,
            rep_args=dist._rep_args)
        np.testing.assert_allclose(float(d_loss), float(dp_loss),
                                   rtol=1e-5)

    def test_compact_exchange_loss_parity_exact(self, setup, rng):
        """The tentpole contract: the compact deduplicated exchange is
        BIT-IDENTICAL to the dense [H, B] path — on the narrow branch
        (roomy cap) and through the lax.cond fallback (cap too small
        for the frontier's unique count)."""
        (mesh, info, dist, model, tx, sizes, per_host, indptr, indices,
         feat, labels, state, hosts) = setup
        g = hosts * per_host
        seeds = jnp.asarray(
            rng.choice(240, g, replace=False).astype(np.int32))
        y = labels[seeds]
        key = jax.random.key(7)
        sharding = NamedSharding(mesh, P("host"))
        seeds_s = jax.device_put(seeds, sharding)
        y_s = jax.device_put(y, sharding)

        def run(exchange_cap):
            step = build_dist_train_step(
                model, tx, sizes, per_host, mesh,
                rows_per_host=dist._rows_per_host, donate=False,
                exchange_cap=exchange_cap)
            st, loss = step(
                state, dist._spmd_feat,
                info.global2host.astype(jnp.int32), info.global2local,
                indptr, indices, seeds_s, y_s, key)
            return np.asarray(loss), st

        dense_loss, dense_state = run(None)
        # roomy cap (narrow branch), starvation cap (dense fallback),
        # and the self-sizing True knob — all bit-identical
        for cap in (16, 1, True):
            c_loss, c_state = run(cap)
            np.testing.assert_array_equal(c_loss, dense_loss)
            a = np.asarray(dense_state.params["params"]["conv0"]
                           ["lin_nbr"]["kernel"])
            b = np.asarray(c_state.params["params"]["conv0"]
                           ["lin_nbr"]["kernel"])
            np.testing.assert_array_equal(b, a)

    def test_compact_exchange_quantized_store_parity(self, setup, rng):
        """exchange_cap composes with dtype_policy: the narrow int8
        payload + sidecars ride the COMPACT collectives and the loss
        still matches the dense path bit-for-bit (dequant is
        elementwise, so expand-after-dequant == dequant-after-expand)."""
        (mesh, info, _, model, tx, sizes, per_host, indptr, indices,
         feat, labels, state, hosts) = setup
        comm = qv.TpuComm(rank=0, world_size=hosts, mesh=mesh,
                          axis="host")
        dist8 = qv.DistFeature.from_partition(
            np.asarray(feat), info, comm, dtype_policy="int8")
        g = hosts * per_host
        seeds = jnp.asarray(
            rng.choice(240, g, replace=False).astype(np.int32))
        y = labels[seeds]
        key = jax.random.key(9)
        sharding = NamedSharding(mesh, P("host"))
        seeds_s = jax.device_put(seeds, sharding)
        y_s = jax.device_put(y, sharding)

        def run(exchange_cap):
            step = build_dist_train_step(
                model, tx, sizes, per_host, mesh,
                rows_per_host=dist8._rows_per_host, donate=False,
                exchange_cap=exchange_cap)
            _, loss = step(
                state, dist8._spmd_feat,
                info.global2host.astype(jnp.int32), info.global2local,
                indptr, indices, seeds_s, y_s, key)
            return np.asarray(loss)

        np.testing.assert_array_equal(run(16), run(None))

    def test_compact_exchange_with_replicate_parity(self, rng):
        """exchange_cap composes with replicated-node resolution: the
        rep override rewrites owners per shard BEFORE the unique-table
        bucketing, so replicated hubs still resolve locally."""
        n, dim, classes, hosts = 160, 8, 4, 8
        deg = rng.integers(1, 7, n)
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(deg, out=indptr[1:])
        indices = rng.integers(0, n, int(indptr[-1]), dtype=np.int32)
        feat = rng.standard_normal((n, dim)).astype(np.float32)
        labels = rng.integers(0, classes, n).astype(np.int32)
        g2h = rng.integers(0, hosts, n).astype(np.int32)
        g2h[:hosts] = np.arange(hosts)
        rep = np.array([3, 77, 140], np.int32)

        mesh = Mesh(np.array(jax.devices()), axis_names=("host",))
        info = qv.PartitionInfo(host=0, hosts=hosts, global2host=g2h,
                                replicate=rep)
        comm = qv.TpuComm(rank=0, world_size=hosts, mesh=mesh,
                          axis="host")
        dist = qv.DistFeature.from_partition(feat, info, comm)

        sizes, per_host = [3, 2], 6
        model = GraphSAGE(hidden_dim=16, out_dim=classes, num_layers=2,
                          dropout=0.0)
        tx = optax.adam(1e-2)
        indptr_j = jnp.asarray(indptr.astype(np.int32))
        indices_j = jnp.asarray(indices)
        n_id, layers = sample_multihop(
            indptr_j, indices_j, jnp.arange(per_host, dtype=jnp.int32),
            sizes, jax.random.key(0))
        state = init_state(model, tx,
                           masked_feature_gather(jnp.asarray(feat), n_id),
                           layers_to_adjs(layers, per_host, sizes),
                           jax.random.key(1))

        g = hosts * per_host
        seeds = np.tile(rep, g // 3 + 1)[:g].astype(np.int32)
        seeds[1::2] = rng.choice(n, g // 2, replace=False)
        sharding = NamedSharding(mesh, P("host"))
        seeds_s = jax.device_put(jnp.asarray(seeds), sharding)
        y_s = jax.device_put(jnp.asarray(labels[seeds]), sharding)
        key = jax.random.key(33)

        def run(exchange_cap):
            step = build_dist_train_step(
                model, tx, sizes, per_host, mesh,
                rows_per_host=dist._rows_per_host, with_replicate=True,
                donate=False, exchange_cap=exchange_cap)
            _, loss = step(
                state, dist._spmd_feat,
                info.global2host.astype(jnp.int32), info.global2local,
                indptr_j, indices_j, seeds_s, y_s, key,
                rep_args=dist._rep_args)
            return np.asarray(loss)

        np.testing.assert_array_equal(run(12), run(None))

    def test_trains(self, setup, rng):
        (mesh, info, dist, model, tx, sizes, per_host, indptr, indices,
         feat, labels, state, hosts) = setup
        g = hosts * per_host
        step = build_dist_train_step(
            model, tx, sizes, per_host, mesh,
            rows_per_host=dist._rows_per_host)
        sharding = NamedSharding(mesh, P("host"))
        losses = []
        for it in range(15):
            seeds = jax.device_put(jnp.asarray(
                rng.integers(0, 240, g, dtype=np.int32)), sharding)
            y = jax.device_put(labels[seeds], sharding)
            state, loss = step(
                state, dist._spmd_feat,
                info.global2host.astype(jnp.int32), info.global2local,
                indptr, indices, seeds, y,
                jax.random.fold_in(jax.random.key(5), it))
            losses.append(float(loss))
        assert np.mean(losses[-5:]) < np.mean(losses[:5])


class TestCompactExchangeTrafficPin:
    """Static wire-byte pins for the FUSED dist step's exchange, on the
    traced program (no compile/run — bench fanouts trace in well under
    a second): the compact [H, cap] collectives must carry <= 1/4 the
    payload bytes of the dense [H, B] path at bench shapes, and the
    dense shapes must never appear in the compact program at all (an
    overflow takes further [H, cap] rounds, never the dense blocks)."""

    def _trace_args(self, rng, per_host, hosts=8, n=1200, dim=16):
        deg = rng.integers(1, 9, n)
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(deg, out=indptr[1:])
        indices = rng.integers(0, n, int(indptr[-1]), dtype=np.int32)
        feat = rng.standard_normal((n, dim)).astype(np.float32)
        labels = rng.integers(0, 4, n).astype(np.int32)
        g2h = rng.integers(0, hosts, n).astype(np.int32)
        g2h[:hosts] = np.arange(hosts)
        mesh = Mesh(np.array(jax.devices()), axis_names=("host",))
        info = qv.PartitionInfo(host=0, hosts=hosts, global2host=g2h)
        comm = qv.TpuComm(rank=0, world_size=hosts, mesh=mesh,
                          axis="host")
        dist = qv.DistFeature.from_partition(feat, info, comm)
        g = hosts * per_host
        seeds = jnp.asarray(rng.choice(n, g, replace=False)
                            .astype(np.int32))
        y = jnp.asarray(labels)[seeds]
        return (mesh, info, dist,
                (dist._spmd_feat, info.global2host.astype(jnp.int32),
                 info.global2local, jnp.asarray(indptr.astype(np.int32)),
                 jnp.asarray(indices), seeds, y, jax.random.key(0)))

    def test_bench_fanout_payload_bytes_quarter_of_dense(self, rng):
        from _traffic import collective_payloads
        from quiver_tpu.pyg.sage_sampler import layer_shapes
        import optax as _optax
        from quiver_tpu.models import GraphSAGE as _Sage

        hosts, per_host, sizes = 8, 8, [15, 10, 5]   # bench fanouts
        frontier = layer_shapes(per_host, sizes)[-1].n_id_cap
        mesh, info, dist, args = self._trace_args(rng, per_host)
        model = _Sage(hidden_dim=8, out_dim=4, num_layers=3,
                      dropout=0.0)
        tx = _optax.adam(1e-2)
        n_id, layers = sample_multihop(
            args[3], args[4], jnp.arange(per_host, dtype=jnp.int32),
            sizes, jax.random.key(0))
        state = init_state(
            model, tx,
            masked_feature_gather(jnp.asarray(np.zeros((1200, 16),
                                                       np.float32)),
                                  n_id),
            layers_to_adjs(layers, per_host, sizes), jax.random.key(1))
        cap = qv.comm.default_exchange_cap(frontier, hosts)
        assert cap * 4 <= frontier            # the sizing itself

        def build(exchange_cap):
            return build_dist_train_step(
                model, tx, sizes, per_host, mesh,
                rows_per_host=dist._rows_per_host, donate=False,
                exchange_cap=exchange_cap)

        dense = collective_payloads(build(None), (state,) + args,
                                    with_depth=True)
        compact = collective_payloads(build(cap), (state,) + args,
                                      with_depth=True)
        # dense program: the [H, B] pair on the unconditional path
        dense_bytes = sum(b for s, _, b, d in dense)
        assert dense_bytes
        assert {s[1] for s, _, b, d in dense} == {frontier}
        assert all(d == 0 for *_x, d in dense)
        # compact program: narrow [H, cap] collectives and NOTHING
        # dense-shaped on any path: an overflowing bucket is served by
        # further rounds of the same [H, cap] exchange, so the
        # exchange's memory is bounded by the cap
        narrow_bytes = sum(b for s, _, b, d in compact if s[1] == cap)
        assert narrow_bytes
        assert {s[1] for s, _, b, d in compact} == {cap}
        assert not [s for s, _, b, d in compact if frontier in s]
        # the acceptance pin: <= 1/4 of the dense wire bytes (actual
        # ratio at these shapes is ~frontier/cap ~ 40x)
        assert narrow_bytes * 4 <= dense_bytes, (narrow_bytes,
                                                 dense_bytes)

    def test_compact_branch_conditions_analytic_mirror(self):
        """ops.dedup.compact_exchange_slots is the ONE analytic copy of
        the rounds logic the benches report from — pin it: a batch
        whose buckets fit ships cap*hosts slots, one whose fullest
        bucket overflows ships that again for every further round."""
        from quiver_tpu.ops.dedup import compact_exchange_slots
        hosts, cap = 8, 4
        dup_heavy = np.tile(np.arange(16, dtype=np.int32), 64)  # 16 uniq
        assert compact_exchange_slots(dup_heavy, cap, hosts) == cap * hosts
        # 64 distinct ids, 8 an owner -> two rounds
        wide = np.arange(64, dtype=np.int32).repeat(16)
        assert compact_exchange_slots(wide, cap, hosts) == 2 * cap * hosts
        # 9 uniq ids all owned by host 0 -> three rounds
        skew = np.tile(np.arange(9, dtype=np.int32) * hosts, 128)
        assert compact_exchange_slots(skew, cap, hosts) == 3 * cap * hosts
        # -1 padding doesn't count against the buckets
        padded = np.full(1024, -1, np.int32)
        padded[:16] = np.arange(16)
        assert compact_exchange_slots(padded, cap, hosts) == cap * hosts
        # cap >= batch: compact can't beat the dense block
        assert compact_exchange_slots(dup_heavy[:8], 8, hosts) == 8

    def test_plan_exchange_cap_degree_mass(self, rng):
        """The sizing helper: a host owning the degree mass gets the
        bigger bucket; the plan respects the frontier ceiling."""
        n, hosts = 400, 8
        g2h = (np.arange(n) % hosts).astype(np.int32)
        deg = np.ones(n)
        deg[g2h == 3] = 50.0          # host 3 owns the mass
        info = qv.PartitionInfo(host=0, hosts=hosts, global2host=g2h)
        plan = info.plan_exchange_cap(4096, degree=deg)
        balanced = info.plan_exchange_cap(4096)
        assert plan.cap > balanced.cap
        assert plan.owner_frac > 0.8
        assert plan.unique_budget == plan.cap * hosts
        assert info.plan_exchange_cap(16).cap <= 16
        # and the partition-blind default stays within its pin
        assert qv.comm.default_exchange_cap(4096, hosts) * 4 <= 4096

    def test_distfeature_getitem_compact_parity(self, rng):
        """DistFeature.__getitem__ with exchange_cap: bit-identical to
        the dense store, -1 fill included, and composing with
        dedup_cold."""
        n, dim, hosts = 96, 8, 8
        feat = rng.standard_normal((n, dim)).astype(np.float32)
        g2h = rng.integers(0, hosts, n).astype(np.int32)
        g2h[:hosts] = np.arange(hosts)
        mesh = Mesh(np.array(jax.devices()), axis_names=("host",))
        info = qv.PartitionInfo(host=0, hosts=hosts, global2host=g2h)
        comm = qv.TpuComm(rank=0, world_size=hosts, mesh=mesh,
                          axis="host")
        dense = qv.DistFeature.from_partition(feat, info, comm)
        compact = qv.DistFeature.from_partition(feat, info, comm,
                                                exchange_cap=8)
        both = qv.DistFeature.from_partition(feat, info, comm,
                                             dedup_cold=True,
                                             exchange_cap=8)
        pool = rng.integers(0, n, 12)
        ids = pool[rng.integers(0, 12, hosts * 32)].astype(np.int32)
        ids[::7] = -1
        want = np.asarray(dense[jnp.asarray(ids)])
        np.testing.assert_array_equal(
            np.asarray(compact[jnp.asarray(ids)]), want)
        np.testing.assert_array_equal(
            np.asarray(both[jnp.asarray(ids)]), want)


class TestBoundedExchange:
    """The compact exchange's memory is bounded by its cap: a bucket
    that overflows is served by further rounds of the same [H, cap]
    exchange, and the rows are the dense path's bit for bit whether the
    buckets overflow never, once or twice, with a replicated set and
    with a quantised store, at each shard's last rows and where a shard
    asks for one row."""

    N, DIM, HOSTS = 320, 8, 8

    def _stores(self, store, cap):
        n, hosts = self.N, self.HOSTS
        rng = np.random.default_rng(3)      # the same table and book a call
        feat = rng.standard_normal((n, self.DIM)).astype(np.float32)
        g2h = rng.integers(0, hosts, n).astype(np.int32)
        g2h[:hosts] = np.arange(hosts)
        rep = np.array([5, 90, 211], np.int32) if store == "replicate" \
            else None
        mesh = Mesh(np.array(jax.devices()), axis_names=("host",))
        info = qv.PartitionInfo(host=0, hosts=hosts, global2host=g2h,
                                replicate=rep)
        comm = qv.TpuComm(rank=0, world_size=hosts, mesh=mesh, axis="host")
        policy = "int8" if store == "int8" else None
        make = lambda **kw: qv.DistFeature.from_partition(
            feat, info, comm, dtype_policy=policy, **kw)
        return make(), make(exchange_cap=cap, collect_metrics=True)

    def _frontier(self, rng, kind, store):
        """``mixed``: -1 fill and a hub among random ids. ``last_rows``:
        ids at every host's last local row and node ``N - 1``, in a
        store whose ``rows_per_host`` is no power of two, so the book's
        ``owner * rows_per_host + row`` comes apart at the top of each
        shard (with ``replicate``, the last replicated node resolves to
        the fullest shard's very last row). ``lone``: shard 0's frontier
        is all -1 but for one id, and it runs the rounds the other
        shards need."""
        hosts, per_host = self.HOSTS, 64
        ids = rng.integers(0, self.N, hosts * per_host).astype(np.int32)
        if kind == "mixed":
            ids[::5] = -1
            ids[1::9] = 5                   # a hub, replicated or not
        elif kind == "last_rows":
            dense, _ = self._stores(store, None)
            rows = dense._rows_per_host
            assert rows & (rows - 1)
            g2h = np.asarray(dense.info.global2host)
            g2l = np.asarray(dense.info.global2local)
            last = [self.N - 1] + [
                int(i) for h, size in enumerate(dense.info.local_sizes)
                for i in np.flatnonzero((g2h == h) & (g2l == size - 1))]
            assert len(last) > hosts // 2
            ids[::3] = np.resize(last, ids[::3].size)
            ids[::7] = -1
        else:
            ids[::5] = -1
            ids[:per_host] = -1
            ids[per_host // 2] = self.N - 1
        return ids

    @pytest.mark.parametrize("frontier", ["mixed", "last_rows", "lone"])
    @pytest.mark.parametrize("store", ["plain", "replicate", "int8"])
    @pytest.mark.parametrize("rounds", [1, 2, 3])
    def test_rounds_equal_the_dense_path_bit_for_bit(self, rng, store,
                                                     rounds, frontier):
        from quiver_tpu import metrics as qm
        hosts, per_host = self.HOSTS, 64
        ids = self._frontier(rng, frontier, store)
        # the fullest per-owner bucket of distinct ids any shard fills
        _, probe = self._stores(store, per_host - 1)
        probe[jnp.asarray(ids)]
        fullest = int(qm.reduce_counters(
            probe.last_counters)[qm.EXCH_BUCKET_MAX])
        assert fullest >= 6
        cap = -(-fullest // rounds)
        assert -(-fullest // cap) == rounds
        dense, compact = self._stores(store, cap)
        want = np.asarray(dense[jnp.asarray(ids)])
        got = np.asarray(compact[jnp.asarray(ids)])
        np.testing.assert_array_equal(got, want)
        assert not want[ids < 0].any() and want[ids >= 0].any()
        c = qm.reduce_counters(compact.last_counters)
        assert c[qm.EXCH_CAP] == cap and c[qm.EXCH_BUCKET_MAX] == fullest
        # every shard says whether the lookup took a round beyond the
        # first: the pmax'd count, the same on all of them
        assert c[qm.EXCH_FALLBACK] == (hosts if rounds > 1 else 0)

    def test_one_read_of_the_book_and_none_of_the_maps(self):
        """The compact lookup resolves an id ONCE: one gather in the
        program reads an [N] operand, the book that ``_route`` computes
        out of ``g2h`` and ``g2l``, and the distinct ids index it (it
        sits under ``_bucket``); no gather reads ``g2h`` or ``g2l``
        themselves, and the slots' read under ``_route`` reads no [N]
        operand (the parent read the maps three times)."""
        from quiver_tpu import profiling
        from _traffic import _sub_jaxprs, gather_reads
        hosts, per_host, cap = self.HOSTS, 64, 4
        _, compact = self._stores("plain", cap)
        assert self.N not in (hosts, per_host, hosts * per_host, cap,
                              self.DIM, compact._rows_per_host)
        fn = qv.comm.build_dist_lookup_fn(
            compact.comm.mesh, "host", compact._rows_per_host, per_host,
            exchange_cap=cap)
        jaxpr = jax.make_jaxpr(fn)(
            jnp.zeros((hosts * per_host,), jnp.int32),
            compact.info.global2host, compact.info.global2local,
            compact._spmd_feat)
        assert gather_reads(jaxpr, (self.N,)) == [(per_host, 0)]

        def gathers(j):
            for eqn in j.eqns:
                if eqn.primitive.name == "gather":
                    yield j, eqn
                for sub in _sub_jaxprs(eqn):
                    yield from gathers(sub)

        route = []
        for j, eqn in gathers(jaxpr.jaxpr):
            stack = str(eqn.source_info.name_stack)
            if eqn.invars[0].aval.shape == (self.N,):
                assert eqn.invars[0] not in j.invars   # computed: the book
                assert profiling.QT_EXCHANGE_BUCKET in stack, stack
            if profiling.QT_EXCHANGE_ROUTE in stack:
                route.append(eqn.invars[0].aval.shape)
        assert route == [(per_host,)]

    def test_no_block_of_the_frontiers_size_in_the_compact_program(self, rng):
        """No [H, B] or [H, B, width] value anywhere in the traced
        compact lookup, on any path."""
        hosts, per_host, cap = self.HOSTS, 64, 4
        _, compact = self._stores("plain", cap)
        fn = qv.comm.build_dist_lookup_fn(
            compact.comm.mesh, "host", compact._rows_per_host, per_host,
            exchange_cap=cap)
        jaxpr = jax.make_jaxpr(fn)(
            jnp.zeros((hosts * per_host,), jnp.int32),
            compact.info.global2host, compact.info.global2local,
            compact._spmd_feat)
        from _traffic import _sub_jaxprs

        def shapes(j):
            for eqn in j.eqns:
                for v in eqn.outvars:
                    yield tuple(v.aval.shape)
                for sub in _sub_jaxprs(eqn):
                    yield from shapes(sub)

        seen = set(shapes(jaxpr.jaxpr))
        assert (hosts, cap, self.DIM) in seen
        assert (hosts, per_host, self.DIM) not in seen
        assert (hosts, per_host) in seen    # integer bookkeeping only


class TestFromShards:
    def test_same_lookups_as_from_partition(self, rng):
        """A store built from the shards where they lie answers as the
        one ``from_partition`` staged through the host, with the
        replicated set and the compact exchange too."""
        n, dim, hosts = 200, 8, 8
        feat = rng.standard_normal((n, dim)).astype(np.float32)
        g2h = rng.integers(0, hosts, n).astype(np.int32)
        g2h[:hosts] = np.arange(hosts)
        mesh = Mesh(np.array(jax.devices()), axis_names=("host",))
        comm = qv.TpuComm(rank=0, world_size=hosts, mesh=mesh, axis="host")
        ids = rng.integers(0, n, hosts * 16).astype(np.int32)
        ids[::6] = -1
        for rep in (None, np.array([2, 150], np.int32)):
            info = qv.PartitionInfo(host=0, hosts=hosts, global2host=g2h,
                                    replicate=rep)
            staged = qv.DistFeature.from_partition(feat, info, comm)
            want = np.asarray(staged[jnp.asarray(ids)])
            for cap in (None, 5):
                given = qv.DistFeature.from_shards(
                    staged._spmd_feat, info, comm, exchange_cap=cap)
                assert given._spmd_feat is staged._spmd_feat
                assert given._rows_per_host == staged._rows_per_host
                np.testing.assert_array_equal(
                    np.asarray(given[jnp.asarray(ids)]), want)

    def test_a_book_of_the_callers_own_and_its_checks(self, rng):
        """``PartitionInfo(global2local=...)`` takes the layout as given:
        rows dealt to owners in any order come back by the book."""
        n, dim, hosts = 64, 4, 8
        rows = n // hosts
        table = rng.standard_normal((n, dim)).astype(np.float32)
        slot = rng.permutation(n).astype(np.int32)   # node -> slot of store
        g2h, g2l = slot // rows, slot % rows
        store = np.zeros((n, dim), np.float32)
        store[slot] = table
        mesh = Mesh(np.array(jax.devices()), axis_names=("host",))
        comm = qv.TpuComm(rank=0, world_size=hosts, mesh=mesh, axis="host")
        info = qv.PartitionInfo(hosts=hosts, global2host=g2h,
                                global2local=g2l)
        assert info.local_sizes == [rows] * hosts
        shards = jax.device_put(store, NamedSharding(mesh, P("host")))
        dist = qv.DistFeature.from_shards(shards, info, comm, exchange_cap=3)
        ids = rng.integers(0, n, hosts * 8).astype(np.int32)
        np.testing.assert_array_equal(np.asarray(dist[jnp.asarray(ids)]),
                                      table[ids])
        with pytest.raises(ValueError, match="rows on its fullest host"):
            qv.DistFeature.from_shards(shards[:hosts * (rows - 1)], info,
                                       comm)
        with pytest.raises(ValueError, match="replicate"):
            qv.PartitionInfo(hosts=hosts, global2host=g2h,
                             global2local=g2l, replicate=np.array([1]))
