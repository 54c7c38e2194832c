"""One run of one cell:

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (world, weights, the cell's own program, its first steps or warm
batches), then a measured window of ``--seconds``, then the reference.
The last line of standard output is the result. With ``--trace 1`` the
window is the cell's ``trace_seconds`` at most, the profiler is on, and
the metrics are the per-layer ones. It needs the chip: on any other
backend it exits 3 and prints no result.
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
    is_train = cell.entry in ("train_step", "dp_train_step")
    extra = {}

    if is_train:
        from . import train_cell
        run = train_cell.TrainRun(cell, seed, devices, faults)
        kept = run.first_steps()
        for _ in range(2):              # settle: the loop's own rhythm
            run.call(run.feed())
        jax.block_until_ready(run.state)
    else:
        from . import serve_cell
        run = serve_cell.ServeRun(cell, seed, devices, faults)
        run.warm()
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
    counters = None if is_train else run.stop_server()
    device = harness.device_block(devices, cell.chips)

    hlo = _program_text(run, is_train) if traced else None
    run.free()
    if is_train:
        numbers = train_cell.compare(run, kept)
        values = {"train_seeds_per_s": win["seeds_per_s"], "setup_s": setup_s}
        attempted, failed = win["steps"], win["nonfinite"]
        facts = {"steps": win["steps"], "enqueue_s": win["enqueue_s"]}
        numbers["nonfinite_losses"] = float(win["nonfinite"])
    else:
        numbers = serve_cell.compare(run, win, int(cell.cell["check_batches"]))
        values = {"setup_s": setup_s,
                  "serve_req_per_s": win["answered_in_window"] / win["seconds"]}
        if "latency_s" in win:
            values["serve_p95_ms"] = serve_cell.p95_ms(win["latency_s"])
            extra = serve_cell.latency_facts(win)
        attempted, failed = win["attempted"], win["failed"]
        facts = {"batches": win["batches"],
                 "gen_late_s": win.get("gen_late_s"),
                 "queue_wait_s": win.get("queue_wait_s")}
    numbers["compiles_in_window"] = float(in_window)
    shown = dict(numbers.pop("facts", {}), **extra)
    compared = {k: (v, float(cell.limits[k])) for k, v in numbers.items()}

    result = {"attempted": int(attempted), "failed": int(failed)}
    if traced:
        tr = trace.Trace(profile.xplane(), trace.scopes_of(hlo),
                         chips=cell.chips)
        ctx = {"trace": tr, "facts": facts, "counters": counters,
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
    result["run"] = dict(shown, workload=name, seed=seed, window_s=window_s,
                         memory_stats={k: int(v) for k, v in stats.items()
                                       if isinstance(v, (int, float))},
                         setup_s=setup_s,
                         **{k: v for k, v in values.items() if k != "setup_s"})
    return result, compared


def _program_text(run, is_train):
    """The compiled text of the program the window drove, for the scopes
    of the trace's instructions (the persistent cache has it)."""
    import jax.numpy as jnp
    w = run.world
    if is_train:
        fed = run.feed()
        fn = run.step.jitted_fns[-1] if hasattr(run.step, "jitted_fns") \
            else None
        if fn is None:
            return ""
        return fn.lower(run.state, w["feat"], None, w["indptr"], w["indices"],
                        fed[1], fed[2], fed[3]).compile().as_text()
    import jax
    from .train_cell import program_tree
    fn = run.engine.jitted_fns[0]
    return fn.lower(program_tree(run.layers), jax.random.key(0), w["feat"],
                    None, w["indptr"], w["indices"],
                    jnp.zeros((run.cap,), jnp.int32)).compile().as_text()


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
