"""Mixed (device+host) sampler adaptivity benchmark.

Measures what the reference published for its hybrid GPU+CPU mode
(reference pyg/sage_sampler.py:272-288 ``decide_task_num`` and the
mixed-mode tables in docs/): device-only SEPS vs the mixed scheduler
with the native C++ host engine, plus the quota split the EMA
adaptation converges to.

The host share should converge toward the honest device:host speed
ratio; the converged split is recorded, so the number documents the
adaptation itself.

Usage: python benchmarks/bench_mixed.py [--nodes N] [--batches K]
       [--workers W] [--sampling rotation|exact|window]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class PermutationJob:
    """Minimal SampleJob: a reshuffled batch stream over train ids."""

    def __init__(self, train_idx, batch, seed=0):
        self.train_idx = np.asarray(train_idx)
        self.batch = batch
        self.rng = np.random.default_rng(seed)
        self.perm = self.train_idx

    def shuffle(self):
        self.perm = self.rng.permutation(self.train_idx)

    def __len__(self):
        return len(self.perm) // self.batch

    def __getitem__(self, i):
        return self.perm[i * self.batch:(i + 1) * self.batch].astype(
            np.int32)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--nodes", type=int, default=600_000)
    p.add_argument("--avg-deg", type=int, default=15)
    p.add_argument("--batch", type=int, default=1024)
    p.add_argument("--batches", type=int, default=96)
    p.add_argument("--sizes", type=int, nargs="+", default=[15, 10, 5])
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--sampling", default="rotation",
                   choices=["exact", "rotation", "window"])
    p.add_argument("--weighted", action="store_true",
                   help="attention-weighted draws on BOTH engines "
                        "(forces sampling=exact; r5 native weighted path)")
    args = p.parse_args()
    if args.weighted:
        args.sampling = "exact"

    from _common import configure_jax
    jax = configure_jax()
    import quiver_tpu as qv
    from quiver_tpu.native import get_lib

    rng = np.random.default_rng(0)
    n = args.nodes
    deg = np.minimum(
        rng.lognormal(np.log(args.avg_deg), 1.0, n).astype(np.int64),
        10_000)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, n, int(indptr[-1]), dtype=np.int32)
    topo = qv.CSRTopo(indptr=indptr, indices=indices)
    train_idx = rng.choice(n, args.batches * args.batch,
                           replace=False).astype(np.int32)
    print(f"graph: {n} nodes, {int(indptr[-1])} edges; "
          f"native host engine: {'yes' if get_lib() is not None else 'numpy fallback'}")

    dev_kwargs = dict(sampling=args.sampling)
    if args.sampling in ("rotation", "window"):
        dev_kwargs.update(layout="overlap", shuffle="butterfly")
    if args.weighted:
        dev_kwargs.update(
            edge_weight=rng.exponential(1.0, int(indptr[-1]))
            .astype(np.float32))

    def run_device_only():
        s = qv.GraphSageSampler(topo, args.sizes, mode="HBM", seed=0,
                                **dev_kwargs)
        job = PermutationJob(train_idx, args.batch, seed=1)
        job.shuffle()
        # warmup (compile)
        out = s.sample(job[0])
        jax.block_until_ready(out[0])
        t0 = time.perf_counter()
        edges = 0
        for i in range(len(job)):
            n_id, bs, adjs = s.sample(job[i])
            edges += sum(int(np.asarray(a.mask).sum()) for a in adjs)
        dt = time.perf_counter() - t0
        return edges, dt

    def run_mixed():
        job = PermutationJob(train_idx, args.batch, seed=1)
        m = qv.MixedGraphSageSampler(job, args.sizes, topo,
                                     device_mode="HBM",
                                     num_workers=args.workers, seed=0,
                                     **dev_kwargs)
        # warmup on a short DEDICATED job, iterated to exhaustion:
        # compile + let the EMAs see both engines. Breaking out of the
        # real epoch's generator instead would abandon in-flight host
        # futures that keep occupying workers into the timed run and
        # leave the EMAs mid-epoch (r4 advisor finding).
        warm_batches = 2 * args.workers + 2
        m.job = PermutationJob(train_idx[:args.batch * warm_batches],
                               args.batch, seed=2)
        for _ in m:
            pass
        m.job = job
        t0 = time.perf_counter()
        edges = 0
        batches = 0
        for n_id, bs, adjs in m:
            edges += sum(int(np.asarray(a.mask).sum()) for a in adjs)
            batches += 1
        dt = time.perf_counter() - t0
        dq, cq = m.decide_task_num()
        return edges, dt, batches, dq, cq, m._device_time, m._cpu_time

    d_edges, d_dt = run_device_only()
    d_seps = d_edges / d_dt
    print(f"[device-only {args.sampling}] {d_edges} edges in {d_dt:.2f}s "
          f"-> SEPS = {d_seps / 1e6:.2f} M")

    m_edges, m_dt, m_batches, dq, cq, ema_d, ema_c = run_mixed()
    m_seps = m_edges / m_dt
    print(f"[mixed {args.sampling} w={args.workers}] {m_edges} edges in "
          f"{m_dt:.2f}s over {m_batches} batches -> SEPS = "
          f"{m_seps / 1e6:.2f} M")
    print(f"[mixed] converged quota device:host = {dq}:{cq} "
          f"(EMA device {ema_d * 1e3:.1f} ms/task, "
          f"host {ema_c * 1e3:.1f} ms/task)"
          if ema_d and ema_c else
          f"[mixed] quota device:host = {dq}:{cq} (EMAs incomplete)")
    print(f"[mixed-vs-device] {m_seps / d_seps:.3f}x")


if __name__ == "__main__":
    main()
