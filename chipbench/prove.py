"""The readings the limits of `correct` are set from, and the knee sweep.
Not part of a run: the builder of a benchmark PR calls it on the chip.

    python3 -m chipbench.prove readings --workload <cell> --seeds 1,2,3 --control-seeds 1,2,3
    python3 -m chipbench.prove sweep --workload <serve cell> --seed 1

``readings`` sets one cell up once per seed in ONE process (its programs
compile once; what is read is the entry's ``Run.readings``) and prints,
for each seed, the numbers of a sound run (the lower readings) and, for
the control seeds, the control's (the float32 reference rerun in
bfloat16 and put in the program's place) and each fault's, planted in
the reference put in the program's place, each with what
``harness.finish`` says of it under the cell's limits (``correct``,
``over``): the control and the faults have to come out not correct. Training
reads its first three steps; serving reads a short window at the cell's
own load.

``sweep`` finds the knee of an open-loop serve cell (an entry whose ``Run``
has ``start_server`` and a ``window(seconds, mix)``): rates doubling from
``--start``, then bisecting; the highest rate with no refusal, no backlog
left at the close and p95 under 5 x the median latency of a lone request.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np


def _say(**kw):
    print(json.dumps(kw), flush=True)


def _judged(cell, numbers: dict) -> dict:
    """The numbers with what ``harness.finish`` would say of them under
    the cell's limits: ``correct`` and which are ``over``."""
    from . import harness
    correct, over = harness.judge(
        {k: (v, float(cell.limits[k])) for k, v in numbers.items()})
    return dict(numbers, correct=correct, over=over)


def readings(args):
    from . import harness, spec
    cell = spec.Cell(args.workload)
    devices, _, _ = harness.claim_devices(cell.chips)
    import jax
    jax.config.update("jax_default_matmul_precision",
                      cell.config["precision"]["matmul"])
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",")} \
        if args.control_seeds else set()
    entry = spec.plugin("entries", cell.entry)
    for seed in seeds:
        t = time.perf_counter()
        run = entry.Run(cell, seed, devices)
        for kind, numbers, shown in run.readings(args.seconds,
                                                 seed in control):
            _say(seed=seed, kind=kind, **shown, **_judged(cell, numbers))
        del run
        gc.collect()
        _say(seed=seed, took_s=time.perf_counter() - t)


def sweep(args):
    from . import harness, serve_cell as sc, spec
    cell = spec.Cell(args.workload)
    devices, _, _ = harness.claim_devices(cell.chips)
    import jax
    jax.config.update("jax_default_matmul_precision",
                      cell.config["precision"]["matmul"])
    run = spec.plugin("entries", cell.entry).Run(cell, args.seed, devices)
    run.setup()
    run.stop()

    def trial(rate, seconds):
        mix = dict(cell.traffic, rate_per_s=rate)
        run.start_server()
        win = run.window(seconds, mix)
        snap = run.stop()
        lat = np.asarray(win["latency_s"])
        fin = lat[np.isfinite(lat)]
        # a backlog that grows: the second half's median well over the first's
        half = len(lat) // 2
        grow = float(np.median(lat[half:]) / max(np.median(lat[:half]), 1e-9))
        row = {"rate": rate, "sent": win["attempted"],
               "rejected": win["rejected"], "failed": win["failed"],
               "p50_ms": float(np.median(fin) * 1e3) if len(fin) else None,
               "p95_ms": sc.p95_ms(lat), "second_half_over_first": grow,
               "fill": snap["mean_batch_fill"], "batches": snap["batches"],
               "late_p95_ms": float(np.percentile(win["gen_late_s"], 95) * 1e3)}
        _say(**row)
        return row

    lone = trial(20.0, 3.0)
    limit_ms = 5.0 * lone["p50_ms"]
    _say(lone_p50_ms=lone["p50_ms"], limit_ms=limit_ms)
    ok = lambda r: (r["failed"] == 0 and r["p95_ms"] < limit_ms
                    and r["second_half_over_first"] < 1.5)
    rate, good, bad = float(args.start), None, None
    while bad is None and rate < 1e6:
        if ok(trial(rate, args.seconds)):
            good, rate = rate, rate * 2
        else:
            bad = rate
    for _ in range(args.bisect):
        if good is None:
            break
        mid = 0.5 * (good + bad)
        if ok(trial(mid, args.seconds)):
            good = mid
        else:
            bad = mid
    _say(knee=good, first_failing=bad, limit_ms=limit_ms,
         offered=None if good is None else 0.8 * good)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("readings")
    r.add_argument("--workload", required=True)
    r.add_argument("--seeds", required=True)
    r.add_argument("--control-seeds", default="")
    r.add_argument("--seconds", type=float, default=3.0)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--seed", type=int, default=1)
    s.add_argument("--start", type=float, default=250.0)
    s.add_argument("--seconds", type=float, default=5.0)
    s.add_argument("--bisect", type=int, default=4)
    args = ap.parse_args(argv)
    {"readings": readings, "sweep": sweep}[args.cmd](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
