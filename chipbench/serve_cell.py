"""What the entry ``micro_batch_server`` is made of:
``MicroBatchServer.submit`` over a ``ServeEngine``.

The traffic's ``kind`` chooses the loop. ``open_loop`` sends on the mix's
schedule whatever the server does, and times every request from when it
was DUE to when its row is back on the host. ``closed_loop`` keeps
``callers`` requests outstanding: each caller waits for its reply, then
asks for the next node. One generator thread drives either.
"""

from __future__ import annotations

import queue
import time

import numpy as np

from . import check, reference, traffic, world
from .train_cell import program_tree

WAIT_PAST_CLOSE_S = 60.0


class RecordingEngine:
    """The engine the server drives, with every dispatch's start and seed
    block noted on the way through (a list append a batch). ``fault``
    alters an answer where it is produced, for `correct`'s own test."""

    def __init__(self, engine, fault=None):
        self._engine = engine
        self._fault = fault
        self.log = []

    def run(self, seeds, variant: int = 0):
        self.log.append((time.perf_counter(), np.array(seeds, np.int32)))
        out = self._engine.run(seeds, variant)
        if self._fault == "answer_altered":
            out = out.at[0, 0].add(0.05)
        return out

    def __getattr__(self, name):
        return getattr(self._engine, name)


class _Tally:
    """The fate of a window's requests, kept without keeping the requests:
    a future is let go as soon as it has answered, so the process's live
    objects do not grow with the window (a million kept futures make every
    full garbage collection a 100 ms stall of all threads). The callback
    runs on the server's worker, right where the answer is set."""

    def __init__(self, n: int):
        self.rows = [None] * n
        self.index, self.t, self.errors = [], [], []

    def callback(self, i: int, then=None):
        rows, index, t, errors = self.rows, self.index, self.t, self.errors
        clock = time.perf_counter

        def done(fut):
            try:
                rows[i] = fut.result()
            except BaseException:
                errors.append(i)
            index.append(i)
            t.append(clock())
            if then is not None:
                then(i)

        return done


class ServeRun:
    """The engine, its server and the loops that load it; ``run.py``'s
    interface (``setup``, ``window``, ``stop``, ``program_text``, ``free``,
    ``outcome``) and ``prove.py``'s (``readings``; ``start_server`` and a
    ``window`` that takes a mix, for the sweep)."""

    def __init__(self, cell, seed: int, devices, faults=()):
        import jax
        import quiver_tpu as qv
        from quiver_tpu.models import GraphSAGE
        cfg = cell.config
        self.cell, self.seed = cell, seed
        self.ref = cell.reference
        self.devices = devices[:1]
        self.server_cfg = dict(cell.cell["server"])
        self.cap = int(self.server_cfg.pop("batch_cap"))
        self.fanout = list(cfg["fanout"])
        self.nodes = int(cfg["nodes"])
        self.world = world.make_world(cfg, seed)
        self.layers = jax.jit(
            lambda k: self.ref.init_layers(k, cell.dims))(
                jax.random.fold_in(world.seed_key(seed), 7))
        model = GraphSAGE(hidden_dim=cfg["hidden_dim"],
                          out_dim=cfg["num_classes"],
                          num_layers=cfg["num_layers"], dropout=cfg["dropout"])
        self.engine_seed = int(seed) & 0x7FFFFFFF
        engine = qv.ServeEngine(
            model, program_tree(self.layers),
            (self.world["indptr"], self.world["indices"]), self.world["feat"],
            sizes_variants=[self.fanout], batch_cap=self.cap,
            seed=self.engine_seed).warmup()
        self.warm_dispatches = len(engine.variants)
        self.engine = RecordingEngine(
            engine, "answer_altered" if "answer_altered" in faults else None)
        self.qv = qv
        self.server = None

    def start_server(self):
        self.server = self.qv.MicroBatchServer(
            self.engine, self.qv.ServeConfig(**self.server_cfg))
        return self.server

    def setup(self):
        """A few full batches through the server itself, so that the
        window's first requests find every thread and path warm."""
        srv = self.start_server()
        ids = traffic.node_ids({"dist": "uniform"}, 4 * self.cap, self.nodes,
                               self.seed + 1)
        for chunk in np.split(ids, 4):
            futs = [srv.submit(int(i)) for i in chunk]
            for f in futs:
                f.result(timeout=600)

    # -- the two loops ---------------------------------------------------
    def window(self, seconds: float, mix=None) -> dict:
        mix = mix or self.cell.traffic
        if mix["kind"] == "open_loop":
            return self._open(seconds, mix)
        if mix["kind"] == "closed_loop":
            return self._closed(seconds, mix)
        raise SystemExit(f"chipbench: unknown serve traffic {mix['kind']!r}")

    def _open(self, seconds, mix):
        OverloadError = self.qv.OverloadError
        srv = self.server
        due, ids = traffic.open_loop(mix, self.nodes, self.seed, seconds)
        n = ids.shape[0]
        tally = _Tally(n)
        late = np.zeros(n)
        rejected = 0
        submit, clock, sleep = srv.submit, time.perf_counter, time.sleep
        first_batch = len(self.engine.log)
        t0 = clock()
        for i in range(n):
            target = t0 + due[i]
            now = clock()
            if target > now:
                sleep(target - now)
                now = clock()
            late[i] = now - target
            try:
                submit(int(ids[i])).add_done_callback(tally.callback(i))
            except OverloadError:
                rejected += 1
        rest = t0 + seconds - clock()
        if rest > 0:
            sleep(rest)
        return self._collect(t0, seconds, ids, tally, n - rejected, rejected,
                             first_batch, due=due, late=late)

    def _closed(self, seconds, mix):
        srv = self.server
        ids = traffic.closed_loop(mix, self.nodes, self.seed)
        pool = ids.shape[0]
        tally = _Tally(0)
        free = queue.SimpleQueue()
        clock = time.perf_counter
        first_batch = len(self.engine.log)
        asked = 0

        def ask():
            nonlocal asked
            i = asked
            asked += 1
            tally.rows.append(None)
            srv.submit(int(ids[i % pool])).add_done_callback(
                tally.callback(i, free.put))

        t0 = clock()
        for _ in range(int(mix["callers"])):
            ask()
        end = t0 + seconds
        while True:
            rest = end - clock()
            if rest <= 0:
                break
            try:
                free.get(timeout=rest)
            except queue.Empty:
                break
            ask()
        return self._collect(t0, seconds, ids[np.arange(asked) % pool], tally,
                             asked, 0, first_batch)

    def _collect(self, t0, seconds, ids, tally, admitted, rejected,
                 first_batch, due=None, late=None):
        """Wait for what is outstanding (a minute past the close at most),
        then put every request's fate into arrays."""
        t_close = t0 + seconds
        give_up = time.perf_counter() + WAIT_PAST_CLOSE_S
        while len(tally.t) < admitted and time.perf_counter() < give_up:
            time.sleep(0.002)
        n = len(ids)
        t_done = np.full(n, np.inf)
        t_done[np.asarray(tally.index[:len(tally.t)], int)] = tally.t
        t_done[np.asarray(tally.errors, int)] = np.inf
        unanswered = admitted - len(tally.t) + len(tally.errors)
        log = self.engine.log[first_batch:]
        out = {
            "t0": t0, "seconds": seconds, "ids": ids, "rows": tally.rows,
            "t_done": t_done, "attempted": n,
            "failed": rejected + unanswered,
            "rejected": rejected, "unanswered": unanswered,
            "answered_in_window": int((t_done <= t_close).sum()),
            "first_batch": first_batch,
            "batch_start": np.array([t for t, _ in log]),
            "batch_seeds": [s for _, s in log],
            "batches": len(log),
        }
        if due is not None:
            latency = t_done - (t0 + due)
            out["latency_s"] = latency
            out["gen_late_s"] = late.tolist()
            which = np.searchsorted(out["batch_start"], t_done, "right") - 1
            ok = np.isfinite(t_done) & (which >= 0)
            out["queue_wait_s"] = (out["batch_start"][which[ok]]
                                   - (t0 + due[ok])).tolist()
        return out

    def stop(self):
        """Close the server and hand over its counters."""
        snap = self.server.snapshot()["serving"]
        self.server.close()
        self.server = None
        return snap

    def program_text(self) -> str:
        """The compiled text of the serve step the window drove (the
        persistent cache has it)."""
        import jax
        import jax.numpy as jnp
        w = self.world
        fn = self.engine.jitted_fns[0]
        return fn.lower(program_tree(self.layers), jax.random.key(0),
                        w["feat"], None, w["indptr"], w["indices"],
                        jnp.zeros((self.cap,), jnp.int32)).compile().as_text()

    def free(self):
        self.engine = None

    def outcome(self, win: dict) -> dict:
        """A sample of the window's batches against the reference, and
        what the window counted."""
        numbers = compare(self, win, int(self.cell.cell["check_batches"]))
        shown = numbers.pop("facts")
        values = {"serve_req_per_s": win["answered_in_window"] / win["seconds"]}
        if "latency_s" in win:
            values["serve_p95_ms"] = p95_ms(win["latency_s"])
            shown.update(latency_facts(win))
        return {"numbers": numbers, "shown": shown, "values": values,
                "attempted": win["attempted"], "failed": win["failed"],
                "facts": {"batches": win["batches"],
                          "gen_late_s": win.get("gen_late_s"),
                          "queue_wait_s": win.get("queue_wait_s")}}

    def readings(self, seconds: float, control: bool):
        """``(kind, numbers, shown)`` of a short window at the cell's own
        load and, with ``control``, of the bfloat16 reference put in the
        program's place on the same batches."""
        self.setup()
        win = self.window(seconds)
        self.stop()
        out = compare(self, win, int(self.cell.cell["check_batches"]),
                      control=control)
        facts = out.pop("facts")
        low = out.pop("control_gap", None)
        yield "program", out, dict(
            facts, p95_ms=p95_ms(win["latency_s"]) if "latency_s" in win
            else None, req_per_s=win["answered_in_window"] / win["seconds"])
        if low is not None:
            yield "control_bfloat16", dict(out, row_gap=low), {}


def p95_ms(latency_s) -> float:
    """The 95th percentile over ALL requests; one that failed or never
    came is over any limit."""
    xs = np.sort(np.asarray(latency_s))
    v = xs[min(len(xs) - 1, int(np.ceil(0.95 * len(xs))) - 1)]
    return float(v * 1e3) if np.isfinite(v) else 1e9


def latency_facts(win: dict) -> dict:
    """What the tail was made of, for the run's record: the percentiles
    over all requests and the p95 of each second of the window."""
    lat = np.where(np.isfinite(win["latency_s"]), win["latency_s"], 1e6) * 1e3
    due_s = (win["t_done"] - win["latency_s"] - win["t0"])
    secs = np.clip(np.nan_to_num(due_s, nan=0.0, posinf=0.0).astype(int), 0,
                   int(win["seconds"]))
    by_second = [round(float(np.percentile(lat[secs == k], 95)), 2)
                 for k in range(int(np.ceil(win["seconds"])))
                 if (secs == k).any()]
    q = lambda p: float(np.percentile(lat, p))
    starts = win["batch_start"]
    return {"p50_ms": q(50), "p99_ms": q(99), "max_ms": float(lat.max()),
            "p95_by_second_ms": by_second, "rejected": win["rejected"],
            "gen_late_max_ms": float(np.max(win["gen_late_s"]) * 1e3),
            "batch_gap_max_ms": float(np.diff(starts).max() * 1e3)
            if len(starts) > 1 else 0.0}


def compare(run: ServeRun, win: dict, batches: int, *, control=False) -> dict:
    """Hold a sample of the window's batches, drawn from the seed with the
    fullest in it, against the reference: every request those batches
    answered. Returns the numbers `correct` compares; with ``control``
    also ``control_gap``, the bfloat16 reference put in the program's
    place on the same batches."""
    import jax
    import jax.numpy as jnp
    w = run.world
    rng = np.random.default_rng([run.seed, 5])
    t_done, ids, rows = win["t_done"], win["ids"], win["rows"]
    which = np.searchsorted(win["batch_start"], t_done, "right") - 1
    answered = np.flatnonzero(np.isfinite(t_done))
    malformed = sum(
        1 for i in answered
        if rows[i] is None or np.shape(rows[i]) != (run.cell.dims[-1],)
        or not np.isfinite(rows[i]).all())
    by_batch = {}
    for i in answered:
        by_batch.setdefault(int(which[i]), []).append(i)
    by_batch.pop(-1, None)
    if not by_batch:
        return {"unanswered": float(win["unanswered"]),
                "malformed": float(malformed),
                "sample_bad": 0.0, "draw_skew": float("nan"),
                "wrong_node": 0.0, "row_gap": float("nan"),
                "facts": {"rows_compared": 0}}
    fullest = max(by_batch, key=lambda b: len(by_batch[b]))
    others = [b for b in sorted(by_batch) if b != fullest]
    picked = [fullest] + list(rng.permutation(others)[:max(0, batches - 1)])
    indptr_host, row_values = check.graph_reader(w["indptr"], w["indices"])
    replay = check.sampler_replay(run.fanout)

    def fwd(dtype):
        def f(layers, feat, sample):
            hops = sample.hops
            targets = [run.cap] + [h.n_id.shape[0] for h in hops[:-1]]
            x = run.ref.gather_rows(feat, hops[-1].n_id)
            return run.ref.forward(layers, x, hops, targets, dtype=dtype)
        return jax.jit(f)

    ref_fwd = fwd(jnp.float32)
    low_fwd = fwd(jnp.bfloat16) if control else None
    wrong = compared = 0
    facts = check.SampleFacts()
    got, want, low = [], [], []
    for b in picked:
        seeds = win["batch_seeds"][b]
        key = check.serve_key(run.engine_seed,
                              win["first_batch"] + b + run.warm_dispatches)
        sample = replay(w["indptr"], w["indices"], jnp.asarray(seeds), key)
        facts.add(reference.check_sample(jax.device_get(sample), run.fanout,
                                         indptr_host, row_values, rng))
        ref_rows = np.asarray(ref_fwd(run.layers, w["feat"], sample))
        low_rows = np.asarray(low_fwd(run.layers, w["feat"], sample),
                              np.float32) if control else None
        slot_of = {int(v): s for s, v in enumerate(seeds) if v >= 0}
        for i in by_batch[b]:
            s = slot_of.get(int(ids[i]))
            if s is None:
                wrong += 1
                continue
            got.append(rows[i])
            want.append(ref_rows[s])
            if control:
                low.append(low_rows[s])
            compared += 1
    out = {"unanswered": float(win["unanswered"]),
           "malformed": float(malformed),
           "sample_bad": float(facts.bad), "draw_skew": facts.draw_skew,
           "wrong_node": float(wrong),
           "row_gap": check.row_gap(got, want) if got else float("nan"),
           "facts": {"rows_compared": compared, "batches_compared": len(picked),
                     "picks_placed": facts.position_n}}
    if control and low:
        out["control_gap"] = check.row_gap(low, want)
    return out
