"""The static invariant verifier (quiver_tpu.analysis + qt_verify).

Three layers of pins:

1. SEEDED VIOLATIONS — one per rule (a ``jax.debug.print`` inside a
   metered train step, a cond-guarded ``all_to_all`` whose predicate is
   not mesh-reduced, a donated state whose dtype drifts across the
   step, a cold gather exceeding its dedup budget, an unbounded cap
   lattice, plus the three host-AST bug classes): each must be flagged
   with the RIGHT rule id, and ``qt_verify`` must exit 1 with the
   finding in its ``lint`` JSONL.
2. CLEAN PASS — the real entry-point registry (and the host lint over
   the real tree) produces zero ERROR findings.
3. CENSUS == OBSERVED — the ``executable_census`` count for the
   serve-ladder / compact-dist-exchange / metered-lookup entries equals
   the executable-cache size check_leak's phases 6/4/9 observe after
   driving the same paths (tiny scale here): the static census is the
   dynamic probe's number, derived without running anything.
"""

import importlib.util
import json
import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

from quiver_tpu.analysis import host_lint
from quiver_tpu.analysis.findings import ERROR, Finding
from quiver_tpu.analysis.jaxpr_lint import (CensusSpec, EntrySpec,
                                            divergent_cond_collectives,
                                            host_sync_eqns, run_rules)
from quiver_tpu.analysis import registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules_of(findings):
    return {f.rule for f in findings if f.level == ERROR}


def _load_qt_verify():
    spec = importlib.util.spec_from_file_location(
        "qt_verify", os.path.join(ROOT, "scripts", "qt_verify.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# seeded violations — the jaxpr rules
# ---------------------------------------------------------------------------


class TestSeededJaxprViolations:
    def test_debug_print_in_metered_step_flagged(self):
        # the regression the absorbed no_host_sync rule must catch: a
        # stray jax.debug.print inside a metered train step is a
        # per-step host round trip (debug_callback), not a freebie
        import optax
        from quiver_tpu.parallel import build_train_step
        fx = registry._fixture()

        def chatty_loss(logits, labels):
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
            jax.debug.print("loss={l}", l=loss)
            return loss

        step = build_train_step(fx.model, fx.tx, fx.sizes, fx.bs,
                                loss_fn=chatty_loss,
                                collect_metrics=True)
        args = (fx.state, fx.feat, None, fx.indptr, fx.indices,
                fx.seeds, fx.labels[fx.seeds], jax.random.key(9))
        spec = EntrySpec(name="seeded_sync", fn=step.jitted_fns[0],
                         args=args)
        findings = run_rules(spec, ("no_host_sync",))
        assert _rules_of(findings) == {"no_host_sync"}
        assert "debug_print" in findings[0].msg

    def test_unreduced_cond_collective_flagged(self):
        # PR 4's deadlock class: an all_to_all inside a lax.cond whose
        # predicate is LOCAL (not pmax/psum-reduced over the mesh) —
        # shards can take different branches and hang the collective
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()), ("host",))
        h = len(jax.devices())

        def body(x):
            flag = jnp.sum(x) > 0          # per-shard, NOT reduced

            def swap(_):
                return jax.lax.all_to_all(
                    x.reshape(1, h, -1), "host", 1, 0).reshape(x.shape)

            return jax.lax.cond(flag, swap, lambda _: x, None)

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("host"),),
                               out_specs=P("host"), check_vma=False))
        x = jnp.ones((h, h * 2), jnp.float32)
        spec = EntrySpec(name="seeded_divergence", fn=fn, args=(x,))
        findings = run_rules(spec, ("collective_divergence",))
        assert _rules_of(findings) == {"collective_divergence"}
        assert "all_to_all" in findings[0].msg

    def test_reduced_cond_collective_clean(self):
        # the same program with the predicate pmax-reduced passes
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()), ("host",))
        h = len(jax.devices())

        def body(x):
            flag = jax.lax.pmax((jnp.sum(x) > 0).astype(jnp.int32),
                                "host") > 0

            def swap(_):
                return jax.lax.all_to_all(
                    x.reshape(1, h, -1), "host", 1, 0).reshape(x.shape)

            return jax.lax.cond(flag, swap, lambda _: x, None)

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("host"),),
                               out_specs=P("host"), check_vma=False))
        x = jnp.ones((h, h * 2), jnp.float32)
        assert divergent_cond_collectives(
            jax.make_jaxpr(fn)(x)) == []

    def test_donation_shape_drift_flagged(self):
        # a "donated" state whose dtype drifts across the step: XLA
        # would silently copy every buffer instead of reusing them
        state = {"w": jnp.ones((8, 8), jnp.float32),
                 "b": jnp.ones((8,), jnp.float32)}

        def drifting_step(state, x):
            new = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16), state)
            return new, jnp.sum(x)

        spec = EntrySpec(name="seeded_drift", fn=drifting_step,
                         args=(state, jnp.ones((4,))),
                         donate_argnums=(0,))
        findings = run_rules(spec, ("donation_honored",))
        assert _rules_of(findings) == {"donation_honored"}
        assert len(findings[0].detail["unmatched"]) == 2

    def test_donation_honored_clean(self):
        state = {"w": jnp.ones((8, 8), jnp.float32)}

        def stable_step(state, x):
            return {"w": state["w"] + 1.0}, jnp.sum(x)

        spec = EntrySpec(name="stable", fn=stable_step,
                         args=(state, jnp.ones((4,))),
                         donate_argnums=(0,))
        assert run_rules(spec, ("donation_honored",)) == []

    def test_over_budget_cold_gather_flagged(self):
        # the real tiered lookup, with the declared budget HALVED: the
        # narrow path's [budget, dim] host gather now exceeds it
        spec = registry.build_entry("lookup_tiered")
        tier, budget, depth = spec.tier_budgets[0]
        spec.tier_budgets = ((tier, budget // 2, depth),)
        findings = run_rules(spec, ("traffic_budget",))
        assert _rules_of(findings) == {"traffic_budget"}
        assert findings[0].detail["rows"] == budget

    def test_carry_chain_laundering_flagged(self):
        # a while loop rotating axis_index through THREE carries: one
        # narrowing pass per hop is not enough — the walk must iterate
        # to a true fix-point or the cond below looks uniform
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()), ("host",))
        h = len(jax.devices())

        def body(x):
            def body_f(c):
                i, a, b, cc = c
                return (i + 1, b, cc,
                        jax.lax.axis_index("host").astype(jnp.int32))

            z = jnp.int32(0)
            _, a, _, _ = jax.lax.while_loop(
                lambda c: c[0] < 3, body_f, (z, z, z, z))

            def swap(_):
                return jax.lax.all_to_all(
                    x.reshape(1, h, -1), "host", 1, 0).reshape(x.shape)

            return jax.lax.cond(a > 0, swap, lambda _: x, None)

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("host"),),
                               out_specs=P("host"), check_vma=False))
        div = divergent_cond_collectives(
            jax.make_jaxpr(fn)(jnp.ones((h, h * 2))))
        assert len(div) == 1 and div[0][0] == ["all_to_all"]

    def test_split_gather_total_still_flagged(self):
        # the budget bounds SUMMED tier rows: splitting one
        # budget-sized gather into two halves doubles traffic and
        # must still flag (the tier_read_bytes semantics, kept)
        tier = jnp.zeros((100, 8), jnp.float32)
        ids = jnp.arange(64, dtype=jnp.int32) % 100

        def fn(t, i):
            return t[i[:32]] + t[i[32:]]

        spec = EntrySpec(name="seeded_split", fn=fn, args=(tier, ids),
                         tier_budgets=((tier, 48, 0),))
        findings = run_rules(spec, ("traffic_budget",))
        assert _rules_of(findings) == {"traffic_budget"}
        assert findings[0].detail["rows"] == 64
        assert findings[0].detail["gathers"] == 2

    def test_oversized_exchange_cap_flagged(self):
        # a ballooned exchange_cap ships most of the dense payload
        # through the "compact" collectives — the narrow-fraction
        # bound must fire even though those collectives sit INSIDE
        # the lax.cond (beside the dense fallback)
        from jax.sharding import Mesh
        from quiver_tpu.comm import build_dist_lookup_fn
        h = len(jax.devices())
        rows, batch, cap, dim = 32, 64, 48, 16
        mesh = Mesh(np.array(jax.devices()), ("host",))
        fn = build_dist_lookup_fn(mesh, "host", rows, batch,
                                  exchange_cap=cap,
                                  collect_metrics=True,
                                  merge_counters=True)
        total = h * rows
        rng = np.random.default_rng(3)
        ids = jnp.asarray(
            rng.integers(0, total, h * batch, dtype=np.int32))
        g2h = jnp.asarray((np.arange(total) // rows).astype(np.int32))
        loc = jnp.asarray((np.arange(total) % rows).astype(np.int32))
        feat = jnp.asarray(
            rng.standard_normal((total, dim)).astype(np.float32))
        dense_bytes = h * batch * 4 + h * batch * dim * 4
        spec = EntrySpec(
            name="seeded_fat_cap", fn=fn, args=(ids, g2h, loc, feat),
            exchange={"prims": ("all_to_all",),
                      "dense_bytes": dense_bytes, "max_frac": 0.25,
                      "dense_shapes": ((h, batch), (h, batch, dim))})
        findings = run_rules(spec, ("traffic_budget",))
        assert _rules_of(findings) == {"traffic_budget"}
        assert findings[0].detail["narrow_bytes"] > \
            0.25 * dense_bytes

    def test_unbounded_cap_set_flagged(self):
        spec = EntrySpec(
            name="seeded_unbounded", fn=lambda x: x,
            args=(jnp.ones(4),),
            census=CensusSpec({"exchange_cap": None}, max_programs=8))
        findings = run_rules(spec, ("executable_census",))
        assert _rules_of(findings) == {"executable_census"}
        assert "UNBOUNDED" in findings[0].msg

    def test_census_bare_string_axis_is_unbounded(self):
        # a typo'd one-element tuple ("fused" instead of ("fused",))
        # must refuse, not count the string's characters as a lattice
        spec = EntrySpec(
            name="seeded_string_axis", fn=lambda x: x,
            args=(jnp.ones(4),),
            census=CensusSpec({"program": "fused"}, max_programs=8))
        findings = run_rules(spec, ("executable_census",))
        assert _rules_of(findings) == {"executable_census"}
        assert "UNBOUNDED" in findings[0].msg

    def test_census_over_bound_flagged(self):
        spec = EntrySpec(
            name="seeded_overcount", fn=lambda x: x,
            args=(jnp.ones(4),),
            census=CensusSpec({"cap": (64, 128, 256), "variant": 2},
                              max_programs=4))
        findings = run_rules(spec, ("executable_census",))
        assert "executable_census" in _rules_of(findings)
        assert findings[0].detail["count"] == 6


# ---------------------------------------------------------------------------
# seeded violations — the host AST rules
# ---------------------------------------------------------------------------


class TestSeededHostViolations:
    def test_lock_held_emit(self):
        src = (
            "class Hub:\n"
            "    def flush(self):\n"
            "        with self._lock:\n"
            "            for rec in self._pending:\n"
            "                self._sink.emit(rec, kind='anomaly')\n")
        findings = host_lint.check_source(src, "seeded.py")
        assert [f.rule for f in findings] == ["lock_held_emit"]
        assert findings[0].entry == "seeded.py:5"

    def test_non_lock_context_named_block_clean(self):
        # "lock" is a substring of "block": the matcher must be
        # word-boundary aware or profiler blocks would count as locks
        src = (
            "class T:\n"
            "    def run(self):\n"
            "        with self.profiler.block():\n"
            "            self._sink.emit({'x': 1})\n")
        assert host_lint.check_source(src) == []

    def test_emit_after_lock_release_clean(self):
        src = (
            "class Hub:\n"
            "    def flush(self):\n"
            "        with self._lock:\n"
            "            pending = list(self._pending)\n"
            "        for rec in pending:\n"
            "            self._sink.emit(rec, kind='anomaly')\n")
        assert host_lint.check_source(src) == []

    def test_thread_without_close_or_finalizer(self):
        src = (
            "import threading\n"
            "class W:\n"
            "    def start(self):\n"
            "        self._t = threading.Thread(target=self._run)\n")
        findings = host_lint.check_source(src, "seeded.py")
        assert [f.rule for f in findings] == ["resource_finalizer"]
        # close() alone is not enough for a non-daemon thread
        src2 = src + "    def close(self):\n        self._t.join()\n"
        findings = host_lint.check_source(src2, "seeded.py")
        assert [f.rule for f in findings] == ["resource_finalizer"]
        assert "finalize" in findings[0].msg

    def test_scoped_worker_not_flagged(self):
        # a thread created, joined and DROPPED inside one method never
        # outlives the object — only self-stored resources count
        src = (
            "import threading\n"
            "class W:\n"
            "    def run_once(self):\n"
            "        t = threading.Thread(target=self._work)\n"
            "        t.start()\n"
            "        t.join()\n")
        assert host_lint.check_source(src) == []

    def test_local_then_self_stored_flagged(self):
        # the repo's own idiom (serving.start): local first, stored on
        # self a few statements later — still a tracked resource
        src = (
            "import threading\n"
            "class W:\n"
            "    def start(self):\n"
            "        t = threading.Thread(target=self._run)\n"
            "        t.start()\n"
            "        self._t = t\n")
        findings = host_lint.check_source(src, "seeded.py")
        assert [f.rule for f in findings] == ["resource_finalizer"]

    def test_nested_class_resources_not_double_attributed(self):
        # the inner class owns (and closes+finalizes) its thread; the
        # outer class creates nothing and must not be flagged
        src = (
            "import threading, weakref\n"
            "class Outer:\n"
            "    class Inner:\n"
            "        def start(self):\n"
            "            self._t = threading.Thread(target=f)\n"
            "            self._fin = weakref.finalize(self._t, g)\n"
            "        def close(self):\n"
            "            self._t.join()\n")
        assert host_lint.check_source(src) == []

    def test_daemon_thread_with_close_clean(self):
        src = (
            "import threading\n"
            "class W:\n"
            "    def start(self):\n"
            "        self._t = threading.Thread(target=self._run,\n"
            "                                   daemon=True)\n"
            "    def close(self):\n"
            "        self._t.join()\n")
        assert host_lint.check_source(src) == []

    def test_hot_path_blocking_sync(self):
        src = (
            "import numpy as np\n"
            "from quiver_tpu.profiling import hot_path\n"
            "@hot_path\n"
            "def gather(store, ids):\n"
            "    rows = store.lookup(ids)\n"
            "    rows.block_until_ready()\n"
            "    return np.asarray(rows)\n")
        findings = host_lint.check_source(src, "seeded.py")
        assert [f.rule for f in findings] == ["hot_path_blocking"] * 2

    def test_unmarked_function_not_checked(self):
        src = ("import numpy as np\n"
               "def edge(rows):\n"
               "    return np.asarray(rows)\n")
        assert host_lint.check_source(src) == []

    def test_swallowed_worker_exception(self):
        # the class the fault injector keeps finding: a worker loop's
        # over-broad except that neither counts, logs, nor re-raises
        src = ("def worker(q):\n"
               "    while True:\n"
               "        try:\n"
               "            q.get()\n"
               "        except Exception:\n"
               "            continue\n")
        findings = host_lint.check_source(src, "seeded.py")
        assert [f.rule for f in findings] == \
            ["swallowed_worker_exception"]
        bare = ("def worker(q):\n"
                "    while True:\n"
                "        try:\n"
                "            q.get()\n"
                "        except:\n"
                "            pass\n")
        assert [f.rule for f in host_lint.check_source(bare)] == \
            ["swallowed_worker_exception"]

    def test_swallow_that_counts_logs_or_reraises_is_clean(self):
        counts = ("def worker(q, stats):\n"
                  "    while True:\n"
                  "        try:\n"
                  "            q.get()\n"
                  "        except Exception:\n"
                  "            stats['errors'] += 1\n")
        logs = ("import logging\n"
                "def worker(q):\n"
                "    while True:\n"
                "        try:\n"
                "            q.get()\n"
                "        except Exception:\n"
                "            logging.exception('worker step failed')\n")
        reraises = ("def worker(q):\n"
                    "    while True:\n"
                    "        try:\n"
                    "            q.get()\n"
                    "        except Exception:\n"
                    "            raise\n")
        narrow = ("import queue\n"
                  "def worker(q):\n"
                  "    while True:\n"
                  "        try:\n"
                  "            q.get_nowait()\n"
                  "        except queue.Empty:\n"
                  "            continue\n")
        outside_loop = ("def once(q):\n"
                        "    try:\n"
                        "        q.get()\n"
                        "    except Exception:\n"
                        "        pass\n")
        bounded_for = ("def sweep(procs):\n"
                       "    for p in procs:\n"
                       "        try:\n"
                       "            p.kill()\n"
                       "        except Exception:\n"
                       "            pass\n")
        for src in (counts, logs, reraises, narrow, outside_loop,
                    bounded_for):
            assert host_lint.check_source(src) == [], src


# ---------------------------------------------------------------------------
# clean pass over the real tree + registry
# ---------------------------------------------------------------------------


class TestCleanPass:
    def test_host_lint_tree_clean(self):
        findings = host_lint.run_host_lint(root=ROOT)
        assert [str(f) for f in findings] == []

    def test_registry_quick_clean(self):
        findings, ran = registry.run_registry(quick=True)
        errors = [str(f) for f in findings if f.level == ERROR]
        assert errors == []
        assert set(ran) >= {"train_step", "lookup_tiered",
                            "dist_lookup", "serve_step",
                            "fused_hot_hop", "fused_multihop"}

    def test_fused_hot_hop_entry(self):
        # the fused sample+gather kernel's contract, as cost-model
        # output: the entry traces sync-free, its census enumerates
        # both storage variants, and the FUSED hop moves ZERO gather
        # indexing bytes while the split train step's frontier-id
        # round trip prices at 1280 B — the exact traffic the kernel
        # deletes (+ 4 B that are not frontier ids: optax 0.2.6's
        # cross-entropy squeezes its take_along_axis result through a
        # one-element constant-index gather). It was 2080 B while the
        # mean's backward gathered by target id (24 slots x 4 B that
        # the dense reduce's transpose, a broadcast, does not read),
        # and 1984 B while the draw read its own write log by gather
        # (2k reads a hop of one index a seed: 2*3*8 + 2*2*32 = 176
        # indices x 4 B that the selects over the log's columns do not
        # make)
        specs = registry.build_entry_specs("fused_hot_hop")
        assert len(specs) == specs[0].census.count() == 2
        from quiver_tpu.analysis.costmodel import cost_of
        fused_cost = cost_of(specs[0])
        assert fused_cost.gather_index_bytes == 0
        assert fused_cost.gather_bytes > 0       # real DMA traffic
        split_cost = cost_of(registry.build_entry("train_step"))
        assert split_cost.gather_index_bytes == 1280 + 4
        findings = run_rules(specs[0], ("no_host_sync",))
        assert [str(f) for f in findings] == []

    def test_fused_multihop_entry(self):
        # qt-fuse-deep: the WHOLE fanout walk — interior sampling-only
        # hops, leaf sample+gather, compaction, reassembly — still
        # models ZERO gather indexing bytes (in-kernel indptr at every
        # hop; the split train step's per-hop frontier round trips
        # price at 1280 B), while the leaf's tier DMAs show up as real
        # gather traffic
        specs = registry.build_entry_specs("fused_multihop")
        assert len(specs) == specs[0].census.count() == 2
        from quiver_tpu.analysis.costmodel import cost_of
        for spec in specs:
            c = cost_of(spec)
            assert c.gather_index_bytes == 0, spec.name
            assert c.gather_bytes > 0, spec.name
        findings = run_rules(specs[0], ("no_host_sync",))
        assert [str(f) for f in findings] == []

    def test_every_census_lattice_point_is_traced(self):
        # the rules must walk EVERY reachable program, not one
        # representative: 3 serve variants, both shard_map arities
        serve = registry.build_entry_specs("serve_step")
        assert len(serve) == serve[0].census.count() == 3
        assert len({id(s.fn) for s in serve}) == 3
        for name in ("e2e_train_step", "dist_train_step"):
            specs = registry.build_entry_specs(name)
            assert len(specs) == specs[0].census.count() == 2
            assert len({id(s.fn) for s in specs}) == 2

    def test_traffic_shim_is_the_one_implementation(self):
        import _traffic
        from quiver_tpu.analysis import jaxpr_lint
        assert _traffic.host_sync_eqns is jaxpr_lint.host_sync_eqns
        assert _traffic.gather_reads is jaxpr_lint.gather_reads
        assert _traffic.collective_payloads is \
            jaxpr_lint.collective_payloads
        assert _traffic.tier_read_bytes is jaxpr_lint.tier_read_bytes

    def test_hot_path_marker_is_transparent(self):
        from quiver_tpu.profiling import hot_path

        def f(x):
            return x + 1

        g = hot_path(f)
        assert g is f and g.__qt_hot_path__ is True


# ---------------------------------------------------------------------------
# census == the executable-cache sizes check_leak observes (phases 4/6/9)
# ---------------------------------------------------------------------------


class TestCensusMatchesObserved:
    def test_serve_ladder_census_matches_cache(self):
        # phase-6 analogue: the fanout-ladder census must equal the
        # compiled-program count after warmup — shedding swaps
        # programs, never compiles one
        from quiver_tpu.serving import ServeEngine
        fx = registry._fixture()
        census = registry.build_entry("serve_step").census
        engine = ServeEngine(fx.model, fx.state.params,
                             (fx.indptr, fx.indices), fx.feat,
                             sizes_variants=[[3, 2], [2, 1], [1, 1]],
                             batch_cap=16, dedup_gather=True,
                             collect_metrics=True).warmup()
        observed = sum(f._cache_size() for f in engine.jitted_fns)
        assert census.count() == observed == 3

    def test_compact_exchange_census_matches_cache(self):
        # phase-4 analogue: narrow and fallback batches both run
        # through ONE compiled program (both cond branches inside it)
        from quiver_tpu.comm import build_dist_lookup_fn
        from jax.sharding import Mesh
        h = len(jax.devices())
        rows, batch, cap = 32, 64, 8
        mesh = Mesh(np.array(jax.devices()), ("host",))
        fn = build_dist_lookup_fn(mesh, "host", rows, batch,
                                  exchange_cap=cap,
                                  collect_metrics=True,
                                  merge_counters=True)
        total = h * rows
        rng = np.random.default_rng(0)
        g2h = jnp.asarray((np.arange(total) // rows).astype(np.int32))
        loc = jnp.asarray((np.arange(total) % rows).astype(np.int32))
        feat = jnp.asarray(
            rng.standard_normal((total, 16)).astype(np.float32))
        # duplicate-heavy (narrow branch) then bucket-overflowing
        # (dense fallback): 8 distinct ids can never overflow a cap-8
        # bucket; 64 distinct ids owned by TWO hosts put 32 in each
        from quiver_tpu import metrics as qm
        pool = rng.integers(0, total, 8)
        narrow_ids = jnp.asarray(
            pool[rng.integers(0, pool.size, h * batch)].astype(np.int32))
        dense_ids = jnp.asarray(
            np.tile(np.arange(2 * rows, dtype=np.int32), h))
        fallbacks = []
        for ids in (narrow_ids, dense_ids):
            out, counters = fn(ids, g2h, loc, feat)
            jax.block_until_ready(out)
            fallbacks.append(int(np.asarray(counters)[qm.EXCH_FALLBACK]))
        # the phase premise, observed: first batch narrow, second
        # dense (the merged flag psums over shards: h, not 1)
        assert fallbacks == [0, h]
        census = registry.build_entry("dist_lookup").census
        assert census.count() == fn._cache_size() == 1

    def test_metered_lookup_census_matches_cache(self):
        # phase-9 analogue: the metered tiered lookup is ONE program
        spec = registry.build_entry("lookup_tiered")
        from quiver_tpu.feature import Feature
        from quiver_tpu.utils import CSRTopo
        fx = registry._fixture()
        topo = CSRTopo(indptr=fx.indptr_np, indices=fx.indices_np)
        store = Feature(device_cache_size=(fx.n // 4) * fx.dim * 4,
                        csr_topo=topo, dedup_cold=True, cold_budget=64)
        store.from_cpu_tensor(np.asarray(fx.feat))
        host = jnp.asarray(store.host_part)
        ids = jnp.asarray(np.arange(128, dtype=np.int32))
        for _ in range(2):
            rows, counters = store._lookup_tiered(
                store.device_part, host, ids, store.feature_order,
                False, True)
            jax.block_until_ready(rows)
        assert spec.census.count() == \
            store._lookup_tiered._cache_size() == 1


# ---------------------------------------------------------------------------
# the CLI contract (in-process — jax is already up)
# ---------------------------------------------------------------------------


class TestQtVerifyCli:
    def test_clean_entry_exits_zero_with_jsonl(self, tmp_path):
        qtv = _load_qt_verify()
        out = tmp_path / "lint.jsonl"
        rc = qtv.main(["--entry", "lookup_tiered", "--jsonl", str(out),
                       "--no-color", "--no-host"])
        assert rc == 0
        recs = [json.loads(l) for l in out.read_text().splitlines()]
        recs = [r for r in recs if r["kind"] != "meta"]  # sink header
        assert recs and all(r["kind"] == "lint" for r in recs)
        assert not any(r["level"] == "ERROR" for r in recs)

    def test_seeded_violation_exits_one_with_finding(self, tmp_path):
        # the acceptance pin: a registered entry with a divergent
        # cond collective makes qt_verify exit 1 and emit the
        # rule-identified lint finding
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        h = len(jax.devices())

        def build():
            mesh = Mesh(np.array(jax.devices()), ("host",))

            def body(x):
                flag = jnp.sum(x) > 0      # NOT mesh-reduced

                def swap(_):
                    return jax.lax.all_to_all(
                        x.reshape(1, h, -1), "host", 1,
                        0).reshape(x.shape)

                return jax.lax.cond(flag, swap, lambda _: x, None)

            fn = jax.jit(shard_map(
                body, mesh=mesh, in_specs=(P("host"),),
                out_specs=P("host"), check_vma=False))
            return EntrySpec(name="seeded_divergent_entry", fn=fn,
                             args=(jnp.ones((h, h * 2)),))

        qtv = _load_qt_verify()
        out = tmp_path / "lint.jsonl"
        registry.register_entry("seeded_divergent_entry", build)
        try:
            rc = qtv.main(["--entry", "seeded_divergent_entry",
                           "--jsonl", str(out), "--no-color",
                           "--no-host"])
        finally:
            registry._REGISTRY.pop("seeded_divergent_entry")
        assert rc == 1
        recs = [json.loads(l) for l in out.read_text().splitlines()]
        bad = [r for r in recs if r.get("level") == "ERROR"]
        assert bad and bad[0]["rule"] == "collective_divergence"
        assert bad[0]["entry"] == "seeded_divergent_entry"

    def test_host_only_exits_zero(self, capsys):
        qtv = _load_qt_verify()
        assert qtv.main(["--host-only", "--no-color"]) == 0
        assert "host lint: 0" in capsys.readouterr().out

    def test_subprocess_forces_8_device_cpu_mesh(self):
        # the regression that matters for lint.sh / chip_suite (which
        # set no XLA_FLAGS): qt_verify must force the virtual 8-device
        # CPU platform BEFORE jax comes up, or the mesh entries verify
        # a degenerate 1-device axis
        import subprocess
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts",
                                          "qt_verify.py"),
             "--entry", "dist_lookup", "--no-host", "--no-color"],
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=300)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "on 8 cpu device(s)" in out.stdout, out.stdout

    def test_host_only_never_imports_jax(self):
        import subprocess
        code = (
            "import sys\n"
            "sys.argv = ['qt_verify', '--host-only', '--no-color']\n"
            "import runpy\n"
            "try:\n"
            "    runpy.run_path('scripts/qt_verify.py',\n"
            "                   run_name='__main__')\n"
            "except SystemExit as e:\n"
            "    assert (e.code or 0) == 0, e.code\n"
            "assert 'jax' not in sys.modules, 'host-only imported jax'\n"
            "print('HOST_ONLY_JAX_FREE')\n")
        out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                             capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "HOST_ONLY_JAX_FREE" in out.stdout

    def test_findings_sort_errors_first(self):
        from quiver_tpu.analysis.findings import sort_findings
        fs = [Finding("r", "INFO", "b", "m"),
              Finding("r", "ERROR", "z", "m"),
              Finding("r", "WARN", "a", "m")]
        assert [f.level for f in sort_findings(fs)] == \
            ["ERROR", "WARN", "INFO"]
