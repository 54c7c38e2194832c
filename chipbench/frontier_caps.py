"""The frontier caps of a per-relation walk, sized from the world's own
sample. Not part of a run: it is run on the chip once, when a
configuration whose entry walks a graph with ``frontier_caps`` is sized.

    python3 -m chipbench.frontier_caps --workload <cell> --seed <n>
        [--batches N]

It makes the cell's world, walks one epoch of the cell's batches (or
``--batches`` of them) with every type's frontier capped at the type's
count (so that no pick is ever dropped), and prints, as one JSON line a
hop and one at the end: each type's largest frontier after each hop,
the caps the configuration's ``frontier_caps_rule`` makes of them
(``MARGIN`` times the largest count, rounded up to ``ROUND``, at most the
type's count), the picks a cap dropped (0) and the walk's device time a
batch.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

MARGIN, ROUND = 1.1, 1024


def rule(largest: int, count: int) -> int:
    """The cap of a type after a hop whose largest frontier was
    ``largest`` (0: the type has none there)."""
    if largest <= 0:
        return 0
    return min(-(-int(MARGIN * largest) // ROUND) * ROUND, count)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--batches", type=int, default=0)
    args = ap.parse_args(argv)
    from . import harness, spec, traffic, world
    cell = spec.Cell(args.workload)
    devices, _, _ = harness.claim_devices(1)
    import jax
    import numpy as np
    from quiver_tpu.ops import sample_typed_multihop
    cfg = cell.config
    entry = spec.plugin("entries", cell.entry)
    names = list(cfg["node_types"])
    counts = [int(cfg["node_types"][n]) for n in names]
    hops = len(cfg["fanout"])
    w = world.make_world(cfg, args.seed, sharding=jax.sharding
                         .SingleDeviceSharding(devices[0]))
    graph = entry.relation_indices(cfg, w["indices"],
                                   [[c] * hops for c in counts])

    @jax.jit
    def walk(indptrs, graph, seeds, key):
        _, layers = sample_typed_multihop(indptrs, graph, seeds,
                                          list(cfg["fanout"]), key)
        return ([[-1 if c is None else c for c in l.count] for l in layers],
                sum(l.drops for l in layers))

    per_epoch = int(cfg["train_nodes"]) // cell.batch
    batches = traffic.train_batches(cell.traffic, dict(cfg, nodes=counts[0]),
                                    args.seed, cell.batch)
    n = args.batches or per_epoch
    key = jax.random.fold_in(world.seed_key(args.seed), 11)
    out = [walk(w["indptr"], graph, next(batches), jax.random.fold_in(key, 0))]
    jax.block_until_ready(out)
    t = time.perf_counter()
    for b in range(1, n):
        out.append(walk(w["indptr"], graph, next(batches),
                        jax.random.fold_in(key, b)))
    jax.block_until_ready(out)
    took = (time.perf_counter() - t) / max(n - 1, 1)
    got = np.asarray(jax.device_get([o[0] for o in out]))   # [n, hops, types]
    drops = int(sum(int(o[1]) for o in out))
    largest = got.max(axis=0)
    caps = {name: [rule(int(largest[h, t]), counts[t]) for h in range(hops)]
            for t, name in enumerate(names)}
    for h in range(hops):
        print(json.dumps({"hop": h, "largest": dict(zip(
            names, largest[h].tolist())), "mean": dict(zip(
                names, got[:, h].mean(axis=0).round(1).tolist()))}))
    print(json.dumps({"batches": n, "drops": drops, "walk_ms": 1e3 * took,
                      "frontier_caps": caps}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
