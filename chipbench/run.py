"""One run of one cell:

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (world, weights, the cell's own program, its first steps or warm
batches), then a measured window of ``--seconds``, then the reference.
The last line of standard output is the result. With ``--trace 1`` the
window is the cell's ``trace_seconds`` at most, the profiler is on, and
the metrics are the per-layer ones. It needs the chip: on any other
backend it exits 3 and prints no result.

What runs is the cell's entry, ``entries/<entry>.py``, found by name
(``spec.plugin``). This file drives ONE interface and knows no entry:

    Run(cell, seed, devices, faults)   the world, the weights, the program
    .setup()                           first steps or warm batches
    .window(seconds) -> win            the measured window; ``win`` is the entry's own
    .stop() -> counters or None        stop what runs beside the loop
    .program_text() -> str             compiled text of what the window drove
    .free()                            drop the program's state, keep the world
    .outcome(win) -> dict              ``numbers`` (compared, each under a limit of
                                       the cell file), ``values`` (end-to-end, by
                                       metric name), ``attempted``, ``failed``,
                                       ``facts`` (what the reducers read) and
                                       ``shown`` (the run's record, optional)
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import gc                # noqa: E402
import sys               # noqa: E402


def _metrics(cell, values: dict) -> dict:
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}


def run_cell(name: str, seed: int, seconds: float, traced: bool, *,
             allow_cpu=False, faults=()):
    """The whole run; returns ``(result, compared)`` for ``harness.finish``.
    ``allow_cpu`` and ``faults`` are the tests' own."""
    from . import harness, readers, spec, trace
    cell = spec.Cell(name)
    devices, peaks, _ = harness.claim_devices(cell.chips, allow_cpu=allow_cpu)
    import jax
    jax.config.update("jax_default_matmul_precision",
                      cell.config["precision"]["matmul"])
    compiles = harness.CompileCounter()
    window_s = min(seconds, float(cell.cell["trace_seconds"])) if traced \
        else seconds
    profile = harness.Profile(traced)

    run = spec.plugin("entries", cell.entry).Run(cell, seed, devices, faults)
    run.setup()
    # what set-up built stays out of the collector's way: a full collection
    # over jax's million objects would stall every thread of the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_PROCESS
    before = compiles.count

    profile.start()
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        win = run.window(window_s)
    profile.stop()
    in_window = compiles.count - before
    counters = run.stop()
    device = harness.device_block(devices, cell.chips)

    hlo = run.program_text() if traced else None
    run.free()
    out = run.outcome(win)
    numbers = dict(out["numbers"], compiles_in_window=float(in_window))
    compared = {k: (v, float(cell.limits[k])) for k, v in numbers.items()}
    values = dict(out["values"], setup_s=setup_s)

    result = {"attempted": int(out["attempted"]), "failed": int(out["failed"])}
    if traced:
        tr = trace.Trace(profile.xplane(), trace.scopes_of(hlo),
                         chips=cell.chips)
        ctx = {"trace": tr, "facts": out["facts"], "counters": counters,
               "cell": cell, "peaks": peaks, "chips": cell.chips}
        result["metrics"] = readers.read_all(ctx)
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.idle_gaps()}
        profile.cleanup()
    else:
        result["metrics"] = _metrics(cell, values)
    result["device"] = device
    stats = devices[0].memory_stats() or {}
    result["run"] = dict(out.get("shown", {}), workload=name, seed=seed,
                         window_s=window_s,
                         memory_stats={k: int(v) for k, v in stats.items()
                                       if isinstance(v, (int, float))},
                         **values)
    return result, compared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from . import harness
    result, compared = run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace))
    return harness.finish(result, compared)


if __name__ == "__main__":
    sys.exit(main())
