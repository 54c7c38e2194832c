"""qt_prof — per-stage time attribution + roofline efficiency for
every registered hot path.

The attribution leg of the observability triad (qt-verify = the static
contract, the telemetry hub = runtime health, qt-prof = where the time
goes). Drives ``quiver_tpu.profile.StageProfiler`` over the entry-point
registry — best-of-N ``block_until_ready`` timing of each entry's
jitted program and each census lattice point (shed variants, rows
arities), the analytic cost model on the same shared trace qt-verify
walks, and a one-shot machine probe (achieved memcpy / random-gather /
host<->device bandwidth on THIS box) — and prints one line per stage:

    stage | mean ms | modeled bytes | achieved GB/s | % of probe
          | % of step

Runs entirely OFF the hot path on the CPU backend (same forced
platform dance as qt_verify: CPU + 8 virtual devices BEFORE jax
imports, so mesh entries profile the full multi-host program). With
``--jsonl``, results land as ``profile``-kind records in the shared
MetricsSink schema — ``scripts/qt_top.py`` renders the latest per
(entry, stage) and ``benchmarks/chip_suite.sh``'s ``prof`` section
feeds the shared history. Exit status 0 unless profiling itself fails:
slow is a number here, not a verdict (``bench_regress.py`` owns
verdicts).

Usage: python scripts/qt_prof.py [--quick] [--entry NAME ...]
           [--jsonl PATH] [--reps N] [--no-probe] [--no-pipeline]
           [--no-color]

``--quick`` profiles the mini entry matrix (< 60 s on CPU, what
``chip_suite.sh prof`` runs); the default covers the full registry.
"""

import argparse
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)


def _ensure_cpu_platform():
    """Profiling attribution never needs the accelerator: force the
    CPU backend + the virtual 8-device platform BEFORE jax imports
    (the tests/conftest.py convention — mesh entries must profile the
    full multi-host program, not a degenerate 1-device axis). A caller
    that already imported jax (the in-process test path) keeps its own
    platform."""
    if "jax" in sys.modules:
        return
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    # share the bench/test persistent compile cache: qt_prof runs as a
    # subprocess in tier-1 CLI tests, and its stage programs are
    # identical run to run
    from quiver_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--quick", action="store_true",
                    help="mini entry matrix + small probe (<60s, "
                         "chip_suite's prof section)")
    ap.add_argument("--entry", action="append", default=[],
                    help="profile only this entry point (repeatable)")
    ap.add_argument("--jsonl", default=None,
                    help="append profile-kind records to this "
                         "MetricsSink JSONL")
    ap.add_argument("--reps", type=int, default=None,
                    help="timed reps per stage (default 5; 3 under "
                         "--quick)")
    ap.add_argument("--no-probe", action="store_true",
                    help="skip the machine probe (no efficiency "
                         "column)")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="skip the sample/gather/step pipeline "
                         "decomposition group")
    ap.add_argument("--no-color", action="store_true")
    args = ap.parse_args(argv)
    color = not args.no_color and bool(
        sys.stdout.isatty() or os.environ.get("FORCE_COLOR"))

    _ensure_cpu_platform()
    import jax
    from quiver_tpu.profile import (StageProfiler, machine_probe,
                                    render_records)

    reps = args.reps or (3 if args.quick else 5)
    probe = None if args.no_probe else machine_probe(quick=args.quick)
    sink = None
    if args.jsonl:
        from quiver_tpu.metrics import MetricsSink
        sink = MetricsSink(args.jsonl)

    profiler = StageProfiler(reps=reps, probe=probe, sink=sink)
    profiler.add_registry(names=args.entry or None, quick=args.quick)
    if not args.no_pipeline and not args.entry:
        profiler.add_pipeline()

    n_groups = len(profiler.groups)
    n_stages = sum(len(g.stages) for g in profiler.groups)
    # the device line is load-bearing (same reason as qt_verify): mesh
    # entries profiled over a 1-device axis would time a trivial
    # exchange
    print(f"qt_prof: {n_groups} entry group(s), {n_stages} stage(s), "
          f"best-of-{reps} on {jax.device_count()} "
          f"{jax.default_backend()} device(s)")
    records = profiler.run()
    print(render_records(records, color=color))
    if sink is not None:
        sink.close()
        print(f"qt_prof: {len(records)} profile record(s) -> "
              f"{args.jsonl}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
