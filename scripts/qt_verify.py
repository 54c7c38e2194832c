"""qt_verify — static invariant verifier for every jitted hot path.

Drives both halves of ``quiver_tpu.analysis`` over the entry-point
registry (train/e2e/dist step builders, the fused serve step, the
tiered lookup, the compact dist exchange):

- the HOST lint (stdlib AST): lock-held sink emission, unfinalized
  thread/Pipeline resources, blocking syncs inside ``@hot_path``
  functions;
- the JAXPR rules (one trace per entry, no compile, CPU):
  ``no_host_sync``, ``donation_honored``, ``collective_divergence``,
  ``traffic_budget``, ``executable_census``.

Findings print human-readably (ERROR red on a tty) and, with
``--jsonl``, land as ``lint``-kind records in the shared MetricsSink
schema (``{ts, kind: "lint", rule, level, entry, msg[, detail]}``) —
``scripts/qt_top.py`` renders them. Exit status 1 iff any ERROR.

Usage: python scripts/qt_verify.py [--quick] [--entry NAME ...]
           [--jsonl PATH] [--host-only] [--no-host] [--list]

``--quick`` runs the mini entry-point matrix (what ``scripts/lint.sh``
gates on, < 60 s on CPU); the default runs the full registry (the
``verify`` section of ``benchmarks/chip_suite.sh``). ``--host-only``
never imports jax at all (the AST half is stdlib).
"""

import argparse
import importlib
import json
import os
import sys
import time
import types

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

RED = "\x1b[31m"
YELLOW = "\x1b[33m"
GREEN = "\x1b[32m"
DIM = "\x1b[2m"
RESET = "\x1b[0m"


def _ensure_cpu_platform():
    """Static analysis never needs an accelerator: force the CPU
    backend and the virtual 8-device platform (the tests/conftest.py
    convention, so mesh entries trace the full multi-host path) —
    BEFORE jax is imported; importing ``quiver_tpu`` imports jax, so
    this must run before ANY quiver_tpu import. A caller that already
    imported jax (the in-process test path) keeps its own platform."""
    if "jax" in sys.modules:
        return
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    # share the bench/test persistent compile cache: qt_verify runs as
    # a subprocess in several tier-1 tests, and its census compiles
    # are identical run to run
    from quiver_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()


def _stdlib_analysis():
    """Load ``analysis.findings`` + ``analysis.host_lint`` WITHOUT
    importing the ``quiver_tpu`` package (whose ``__init__`` imports
    jax): a synthetic parent package pointed at the analysis directory
    keeps ``--host-only`` genuinely jax-free."""
    name = "_qt_verify_stdlib_analysis"
    if name not in sys.modules:
        pkg = types.ModuleType(name)
        pkg.__path__ = [os.path.join(_ROOT, "quiver_tpu", "analysis")]
        sys.modules[name] = pkg
    return (importlib.import_module(name + ".findings"),
            importlib.import_module(name + ".host_lint"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--quick", action="store_true",
                    help="mini entry-point matrix (lint.sh's gate)")
    ap.add_argument("--entry", action="append", default=[],
                    help="verify only this entry point (repeatable)")
    ap.add_argument("--jsonl", default=None,
                    help="append lint-kind findings to this "
                         "MetricsSink JSONL")
    ap.add_argument("--host-only", action="store_true",
                    help="AST rules only (no jax import)")
    ap.add_argument("--no-host", action="store_true",
                    help="skip the AST rules")
    ap.add_argument("--list", action="store_true",
                    help="list registered entry points and exit")
    ap.add_argument("--no-color", action="store_true")
    args = ap.parse_args(argv)
    color = not args.no_color and bool(
        sys.stdout.isatty() or os.environ.get("FORCE_COLOR"))

    if args.host_only:
        findings_mod, host_lint = _stdlib_analysis()
    else:
        _ensure_cpu_platform()
        from quiver_tpu.analysis import findings as findings_mod
        from quiver_tpu.analysis import host_lint

    if args.list:
        # listing needs the registry (and therefore jax) even under
        # --host-only: force the CPU platform first, or a bare TPU box
        # would claim the chip just to print names
        _ensure_cpu_platform()
        from quiver_tpu.analysis.registry import entry_names
        quick = set(entry_names(quick=True))
        for n in entry_names():
            print(f"{n}{'  [quick]' if n in quick else ''}")
        return 0

    findings = []
    if not args.no_host:
        findings += host_lint.run_host_lint(root=_ROOT)
        print(f"host lint: {len(findings)} finding(s) over "
              "quiver_tpu/ + scripts/")

    if not args.host_only:
        import jax
        from quiver_tpu.analysis.registry import run_registry
        fs, entries = run_registry(names=args.entry or None,
                                   quick=args.quick)
        findings += fs
        # the device line is load-bearing: mesh entries traced over a
        # degenerate 1-device axis would verify a trivial exchange
        print(f"jaxpr rules: {len(entries)} entry point(s) on "
              f"{jax.device_count()} {jax.default_backend()} "
              f"device(s) ({', '.join(entries)})")

    findings = findings_mod.sort_findings(findings)
    tint = {findings_mod.ERROR: RED, findings_mod.WARN: YELLOW,
            findings_mod.INFO: DIM}
    for f in findings:
        line = str(f)
        print(f"{tint.get(f.level, '')}{line}{RESET}" if color else line)

    if args.jsonl:
        if args.host_only:
            # same {ts, kind: "lint", ...} schema, written with stdlib
            # json so the host-only path stays jax-free (MetricsSink
            # lives in quiver_tpu.metrics, which imports jax)
            with open(args.jsonl, "a") as fh:
                for f in findings:
                    fh.write(json.dumps(
                        {"ts": round(time.time(), 3), **f.record()})
                        + "\n")
        else:
            from quiver_tpu.metrics import MetricsSink
            with MetricsSink(args.jsonl) as sink:
                for f in findings:
                    # kind= keyword (not just the record's own field)
                    # so lint.sh's AST drift check ties `lint` to docs
                    sink.emit(f.record(), kind="lint")

    n_err = sum(1 for f in findings if f.level == findings_mod.ERROR)
    n_warn = sum(1 for f in findings if f.level == findings_mod.WARN)
    verdict = "FAIL" if n_err else "OK"
    vcol = RED if n_err else GREEN
    msg = (f"qt_verify: {verdict} — {n_err} error(s), {n_warn} "
           f"warning(s), {len(findings)} finding(s) total")
    print(f"{vcol}{msg}{RESET}" if color else msg)
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
