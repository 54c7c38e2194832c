"""Bench regression sentinel: fail on a >15% drop vs the best prior run.

Reads the committed ``BENCH_r*.json`` round trajectory (driver records:
``{"n", "cmd", "rc", "tail"}`` where ``tail`` holds the bench's one
JSON measurement line) plus, when present, a ``QT_METRICS_JSONL``
history (``{"ts", "kind": "bench", ...}`` records from ``bench.py`` /
``benchmarks/bench_serving.py``), and walks each metric's values in
round order:

- records with ``"skipped": true`` or ``value: null`` are SKIPPED, not
  failed — the committed r03-r05 records carry no number, which is
  no evidence of a regression (``bench.py`` itself no longer writes
  such a record: without a chip it fails);
- values are grouped by ``(metric, platform)`` so a ``cpu-smoke`` run
  is never compared against a TPU number; beside the headline
  ``value``, the auxiliary rate keys in ``SUB_METRICS``
  (``cold_rows_per_s``, ``prefetch_hit_rate`` — the cold-tier
  prefetch figures bench.py emits) form their own groups;
- the verdict judges each group's LATEST non-skipped value against the
  best prior one: more than ``--threshold`` (default 15%) below it is
  a regression — reported and exit code 1 (``chip_suite.sh`` exports
  ``QT_METRICS_JSONL`` and runs this as its final section, so the
  sweep that just ran is the latest record and a silent slowdown
  fails loudly). Only the latest is judged: a real regression is
  still low *now*, while an old dip that has since recovered is
  yesterday's news, not a reason to fail today's sweep forever.

The JSONL history is append-only and outlives committed rounds, and
its records sort AFTER the whole committed trajectory here (its ``ts``
and the rounds' ``n`` share no clock) — so a stale history line would
otherwise masquerade as "the latest value" forever, even once a
committed improvement supersedes it. ``--since EPOCH`` scopes the
JSONL to records with ``ts >= EPOCH``: ``chip_suite.sh`` captures its
start time and passes it, so the final regress section judges exactly
what this sweep measured, against everything before it.

Values are rates (edges/s, requests/s, rows/s) — higher is better.

``--reanchor METRIC`` (repeatable) is the box-drift escape hatch: the
named metric's trajectory RESTARTS at this run — its latest value is
recorded as the new anchor instead of being judged against the best
prior one (three rounds running had to skip committing ``BENCH_r*.json``
because host-state drift on one metric — ``sampled-edges/sec`` — kept
failing the 15% gate against a number a differently-loaded box set).
A reanchor is visible, not silent: the verdict record carries
``reanchored: true``, and every verdict notes the ``box`` fingerprint
(``platform.node()``) so a cross-box comparison can be recognized for
what it is when the trajectory is read later. The durable form lives
in the committed round itself: a ``BENCH_r*.json`` record carrying
``"reanchor": [metric, ...]`` restarts those metrics' history at that
round for EVERY later invocation — the flag answers "judge this run
leniently", the field answers "the trajectory restarts here"
(``BENCH_r22.json`` does this for ``sampled-edges/sec`` and
``fused_vs_split_steps_per_s`` after the box moved under both).

Beside the stdout report and the exit code, the verdict is also
emitted as ``regress`` JSONL records (one per judged group: metric,
platform, latest, best, ratio, regressed) appended to ``--emit-jsonl``
(default: the ``--jsonl`` history when one is in use) — the
machine-readable trajectory-health feed ``scripts/qt_top.py`` and the
telemetry hub surface. The exit-code contract is unchanged.

Stdlib only (no jax import): the sentinel must run instantly anywhere,
including as the last step of an on-chip sweep and inside tier-1 tests.

Usage: python scripts/bench_regress.py [--threshold 0.15]
           [--bench-dir DIR] [--jsonl PATH] [--since EPOCH]
           [--emit-jsonl PATH]
"""

import argparse
import glob
import json
import os
import platform
import sys


def parse_tail_records(tail):
    """Every JSON measurement object embedded in a driver record's
    captured ``tail`` (one per line; traceback noise ignored)."""
    out = []
    for line in tail.splitlines():
        line = line.strip()
        if not (line.startswith("{") and line.endswith("}")):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "metric" in rec:
            out.append(rec)
    return out


def load_trajectory(bench_dir):
    """``[(label, record)]`` in round order from BENCH_r*.json files."""
    runs = []
    for path in glob.glob(os.path.join(bench_dir, "BENCH_r*.json")):
        try:
            with open(path) as f:
                run = json.load(f)
        except ValueError as e:
            print(f"WARN {os.path.basename(path)}: unreadable ({e})")
            continue
        runs.append((run.get("n", 0), os.path.basename(path), run))
    runs.sort(key=lambda r: (r[0], r[1]))
    out = []
    for _, name, run in runs:
        # a committed round may carry "reanchor": [metric, ...] — the
        # durable form of the --reanchor flag: the walk forgets those
        # metrics' history BEFORE this round, so one committed record
        # restarts the trajectory for every later invocation instead
        # of needing the flag on each sweep (the r19-r21 box-drift
        # skips end here)
        ra = run.get("reanchor")
        if ra:
            out.append((name, {"__reanchor__": [str(m) for m in ra]}))
        for rec in parse_tail_records(run.get("tail", "")):
            out.append((name, rec))
    return out


def load_jsonl(path, since=None):
    """``[(label, record)]`` from a shared-schema metrics JSONL file —
    only ``kind: bench`` measurement records (other kinds — step_stats,
    serving, slo... — are not trajectory points), and only
    those with ``ts >= since`` when a scope is given. Reads across the
    ``MetricsSink`` rollover seam: the rolled-over ``<path>.1`` (older
    half) is consumed before ``<path>``, so a size-bounded sink loses
    no trajectory points at the seam."""
    out = []
    if not path:
        return out
    for p in (path + ".1", path):
        if not os.path.exists(p):
            continue
        with open(p) as f:
            for i, line in enumerate(f):
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") != "bench" or "metric" not in rec:
                    continue
                if since is not None and rec.get("ts", 0) < since:
                    continue
                out.append((f"{os.path.basename(p)}:{i + 1}", rec))
    return out


def emit_verdicts(path, records, kind="regress"):
    """Append one ``regress`` JSONL record per judged trajectory group
    (metric, platform, latest, best, ratio, regressed) plus the overall
    verdict — the machine-readable mirror of the stdout report, so the
    telemetry hub / ``qt_top.py`` can surface trajectory health without
    scraping text. Hand-rolled append (this script must stay jax-free);
    same ``{ts, kind, ...}`` schema as ``metrics.MetricsSink``."""
    import time
    with open(path, "a") as f:
        for rec in records:
            f.write(json.dumps({"ts": round(time.time(), 3),
                                "kind": kind, **rec}) + "\n")


def is_skipped(rec):
    """An explicitly skipped round (older records), or one that
    produced no number at all, is not evidence of a regression."""
    return bool(rec.get("skipped")) or rec.get("value") is None


#: auxiliary per-record rate keys tracked as their OWN (metric,
#: platform) trajectory groups beside the headline ``value`` — all
#: higher-is-better (rows/s; the hit rate is a fraction), judged with
#: the same latest-vs-best-prior rule. Absent keys (older rounds
#: predate them) simply contribute no point.
#: ``cold_staged_rows_per_s`` (parallel-IO staging throughput) joins
#: in round 13 — the QD/coalescing win is regression-tracked from
#: the round that shipped it. ``gather_efficiency`` (qt-prof's
#: roofline figure: modeled gather bytes / timed wall / probed
#: random-gather peak, a 0..1 fraction) joins in round 14 — a stage
#: drifting away from the hardware's limits fails the sweep even when
#: absolute rows/s still looks plausible on a faster box.
#: ``chaos_*`` (qt-chaos's resilience figures from
#: ``bench_serving.py --chaos-only``) join in round 16 — these are
#: LOWER-is-better (see ``INVERTED_METRICS``): accepted-p99 ratio
#: under a seeded kill, typed-error rate, kill->staleness detection
#: latency, kill->serving-again recovery time.
#: ``tail_rps_ratio`` (qt-tail's always-on-vs-detached completed-rps
#: ratio from ``bench_serving.py``'s ``tail_ab`` block) joins in
#: round 17 — the sampler's overhead claim, regression-tracked; its
#: sibling ``tail_kept_frac`` (fraction of traces KEPT) is
#: LOWER-is-better: a growing kept fraction means the keep policies
#: drifted toward full capture.
#: ``fused_vs_split_steps_per_s`` / ``fused_gather_index_bytes``
#: (qt-fuse's single-kernel sample+gather hop, from ``bench.py`` and
#: ``benchmarks/bench_fused.py``) join in round 18: the fused/split
#: throughput ratio (higher is better), and the fused hop's modeled
#: gather indexing bytes — 0 by construction and LOWER-is-better, so
#: a regression that reintroduces the frontier-id HBM round trip
#: (any nonzero value) fails the sweep.
#: ``adaptive_hit_rate`` / ``adaptive_served_p99_ms`` (qt-act's
#: adaptive-vs-static A/B on the drifting trace, from
#: ``benchmarks/bench_actuation.py``) join in round 19: the adaptive
#: arm's post-drift hot-tier hit rate (higher is better — losing it
#: means the rotation loop stopped winning), and its served p99
#: (LOWER-is-better: actuation that buys hit rate by flapping knobs
#: into latency is a regression, not a win).
#: ``sharded_agg_rps`` / ``sharded_p99_ms`` / ``locality_hit_rate``
#: (qt-shard's serving pass over the partition-sharded store, from
#: ``bench.py``) join in round 20: aggregate seeds/sec through the
#: jitted shard_map serve step (higher is better), its per-batch
#: dispatch p99 (LOWER-is-better), and the observed fraction of the
#: frontier resident in the home partition's tier under
#: locality-routed arrivals — losing it means the exchange is
#: shipping rows the router was supposed to keep home.
#: ``fused_multihop_vs_split_steps_per_s`` (qt-fuse-deep's whole-ladder
#: A/B at the production fanouts, from ``bench.py``) joins in round
#: 21: the one-program fused walk vs the per-hop split composition,
#: higher is better; ``fused_gather_index_bytes`` keeps its zero-slack
#: INVERTED gate so a reintroduced per-hop id round trip still fails
#: the sweep.
#: ``capacity_abs_err_frac`` (qt-capacity's prediction honesty, from
#: ``benchmarks/bench_capacity.py``: |predicted/measured - 1| for the
#: replay-verified capacity model) joins in round 22 — LOWER-is-better:
#: the model drifting away from what the proving ground measures is a
#: regression even while both numbers individually look plausible.
#: Only non-smoke runs emit it (smoke-scale error isn't comparable).
SUB_METRICS = ("cold_rows_per_s", "prefetch_hit_rate",
               "cold_staged_rows_per_s", "gather_efficiency",
               "chaos_accepted_p99_ratio", "chaos_error_rate",
               "chaos_detection_s", "chaos_recovery_s",
               "tail_rps_ratio", "tail_kept_frac",
               "fused_vs_split_steps_per_s",
               "fused_gather_index_bytes",
               "fused_multihop_vs_split_steps_per_s",
               "adaptive_hit_rate", "adaptive_served_p99_ms",
               "sharded_agg_rps", "sharded_p99_ms",
               "locality_hit_rate", "capacity_abs_err_frac")

#: trajectory groups where LOWER is better: "best prior" is the
#: minimum, and the regression rule inverts — the latest value more
#: than ``threshold`` ABOVE the best prior (plus the metric's
#: absolute slack) fails the sweep.
INVERTED_METRICS = ("chaos_accepted_p99_ratio", "chaos_error_rate",
                    "chaos_detection_s", "chaos_recovery_s",
                    "tail_kept_frac", "fused_gather_index_bytes",
                    "adaptive_served_p99_ms", "sharded_p99_ms",
                    "capacity_abs_err_frac")

#: per-metric absolute slack for the inverted rule: several of these
#: bottom out at 0.0 (a chaos run with EVERY request recovered records
#: error rate 0), where a purely multiplicative threshold is
#: degenerate — any nonzero later value would "regress". The slack is
#: the noise floor a healthy run may sit inside; a drift past
#: best*(1+threshold)+slack is a real degradation on this box.
INVERTED_ABS_SLACK = {"chaos_error_rate": 0.02,
                      "chaos_detection_s": 0.5,
                      "chaos_recovery_s": 2.0,
                      "chaos_accepted_p99_ratio": 0.75,
                      # a healthy run keeps only the p99-busting tail
                      # (~1-3%); the slack absorbs box-noise latency
                      # keeps without letting "keep everything" pass
                      "tail_kept_frac": 0.05,
                      # a CPU-box p99 wobbles by a few ms between
                      # otherwise-identical serving runs
                      "adaptive_served_p99_ms": 5.0,
                      "sharded_p99_ms": 5.0,
                      # the replay gate itself tolerates ±25% error;
                      # the trajectory slack sits just under it so a
                      # within-tol run never double-fails here while a
                      # model drifting past the gate still does
                      "capacity_abs_err_frac": 0.2}


def _points(rec):
    """Every (metric name, value) trajectory point one record carries:
    the headline ``value`` under its ``metric`` string, plus each
    present ``SUB_METRICS`` key under its own name."""
    pts = []
    v = rec.get("value")
    if isinstance(v, (int, float)):
        pts.append((rec.get("metric", "?"), v))
    for sub in SUB_METRICS:
        sv = rec.get(sub)
        if isinstance(sv, (int, float)):
            pts.append((sub, sv))
    return pts


def _walk(records):
    """Fold ``[(label, rec)]`` in order into per-(metric, platform)
    group state: (best-prior (value, label), latest (value, label),
    points counted)."""
    best = {}          # (metric, platform) -> (value, label)
    latest = {}        # (metric, platform) -> (value, label)
    checked = 0
    for label, rec in records:
        ra = rec.get("__reanchor__")
        if ra:
            # trajectory restart marker (a committed round's
            # "reanchor" list): drop the named metrics' history so the
            # next point — this round's own — is the new anchor
            for key in [k for k in set(best) | set(latest)
                        if k[0] in ra]:
                best.pop(key, None)
                latest.pop(key, None)
            continue
        if is_skipped(rec):
            continue
        platform = rec.get("platform", "")
        for metric, value in _points(rec):
            key = (metric, platform)
            checked += 1
            prev = latest.get(key)
            if prev is not None:
                prior = best.get(key)
                lower = metric in INVERTED_METRICS
                if prior is None or (prev[0] < prior[0] if lower
                                     else prev[0] > prior[0]):
                    best[key] = prev
            latest[key] = (value, label)
    return best, latest, checked


def verdicts(records, threshold, reanchor=()):
    """One verdict dict per trajectory group — the LATEST value vs the
    best PRIOR one, the ratio, and whether it regressed past
    ``threshold`` (the payload both the stdout report and the
    ``regress`` JSONL records render) — plus the measured-point count.
    Metrics named in ``reanchor`` restart their trajectory at the
    latest value: never regressed, flagged ``reanchored`` in the
    verdict. Every verdict carries the ``box`` fingerprint so a later
    reader can tell a cross-box comparison from a same-box drop.
    Returns ``(groups, checked)``; ONE walk of the history serves
    every consumer."""
    best, latest, checked = _walk(records)
    box = platform.node() or "unknown"
    out = []
    for key, (value, label) in sorted(latest.items()):
        prior = best.get(key)
        lower = key[0] in INVERTED_METRICS
        if key[0] in reanchor:
            regressed = False
        elif lower:
            slack = INVERTED_ABS_SLACK.get(key[0], 0.0)
            regressed = bool(prior and value >
                             (1.0 + threshold) * prior[0] + slack)
        else:
            regressed = bool(prior
                             and value < (1.0 - threshold) * prior[0])
        v = {
            "metric": key[0], "platform": key[1] or "default",
            "value": value, "run": label,
            "best": prior[0] if prior else None,
            "best_run": prior[1] if prior else None,
            "ratio": (value / prior[0] if prior and prior[0] else None),
            "direction": "lower" if lower else "higher",
            "regressed": regressed,
            "box": box,
        }
        if key[0] in reanchor:
            v["reanchored"] = True
        if prior:
            v["drop_frac"] = ((value / prior[0] - 1.0) if lower
                              else 1.0 - value / prior[0]) \
                if prior[0] else None
        out.append(v)
    return out, checked


def check(records, threshold):
    """Walk ``[(label, rec)]`` in order; judge each group's LATEST
    value against the best PRIOR one. Returns (regressions, checked)
    where each regression is a dict naming the drop."""
    groups, checked = verdicts(records, threshold)
    regressions = [
        {k: v[k] for k in ("metric", "platform", "value", "best",
                           "best_run", "run", "drop_frac")}
        for v in groups if v["regressed"]]
    return regressions, checked


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--threshold", type=float, default=0.15,
                    help="max tolerated fractional drop vs the best "
                         "prior value (default 0.15)")
    ap.add_argument("--bench-dir",
                    default=os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__))),
                    help="directory holding BENCH_r*.json")
    ap.add_argument("--jsonl", default=os.environ.get("QT_METRICS_JSONL"),
                    help="metrics JSONL history to append to the "
                         "trajectory (default: $QT_METRICS_JSONL)")
    ap.add_argument("--since", type=float, default=None, metavar="EPOCH",
                    help="only include JSONL records with ts >= EPOCH "
                         "(chip_suite.sh passes its start time so the "
                         "verdict judges this sweep's records, not "
                         "stale history)")
    ap.add_argument("--emit-jsonl", default=None, metavar="PATH",
                    help="append one `regress` JSONL record per judged "
                         "group to PATH (default: the --jsonl history "
                         "when one is in use), so the dashboard/hub "
                         "can surface trajectory health; the exit code "
                         "is unchanged")
    ap.add_argument("--reanchor", action="append", default=[],
                    metavar="METRIC",
                    help="restart METRIC's trajectory at this run "
                         "(repeatable): its latest value becomes the "
                         "new anchor instead of being judged against "
                         "the best prior one — the escape hatch for "
                         "host-state drift; the verdict record is "
                         "flagged `reanchored` and carries the box "
                         "fingerprint, so the reset stays visible")
    args = ap.parse_args(argv)

    records = (load_trajectory(args.bench_dir)
               + load_jsonl(args.jsonl, args.since))
    if not records:
        print(f"bench_regress: no bench records under {args.bench_dir}; "
              "nothing to check")
        return 0
    skipped = sum(1 for _, r in records
                  if "__reanchor__" not in r and is_skipped(r))
    reanchor = frozenset(args.reanchor)
    groups, checked = verdicts(records, args.threshold, reanchor)
    regressions = [v for v in groups if v["regressed"]]
    print(f"bench_regress: {checked} measured values "
          f"({skipped} skipped/unavailable rounds ignored), "
          f"threshold {args.threshold:.0%}")
    for v in groups:
        if v.get("reanchored"):
            print(f"REANCHOR {v['metric']} [{v['platform']}]: "
                  f"trajectory restarts at {v['value']:.3f} "
                  f"({v['run']}, box {v['box']})"
                  + (f" — prior best {v['best']:.3f} "
                     f"({v['best_run']}) set aside"
                     if v.get("best") is not None else ""))
    for r in regressions:
        word = "above" if r["direction"] == "lower" else "below"
        frac = ("" if r.get("drop_frac") is None
                else f"{r['drop_frac']:.1%} ")
        print(f"REGRESSION {r['metric']} [{r['platform']}]: "
              f"{r['value']:.3f} in {r['run']} is {frac}"
              f"{word} best {r['best']:.3f} ({r['best_run']})")
    emit_path = args.emit_jsonl or args.jsonl
    if emit_path:
        try:
            emit_verdicts(emit_path, groups)
        except OSError as e:            # the verdict must still print
            print(f"WARN could not append regress records to "
                  f"{emit_path}: {e}")
    if regressions:
        return 1
    print("bench_regress: trajectory clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
