"""What every cell's run shares: the device check, the compile cache and
the compile counter, the profiler window, the result line."""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys

from . import spec


class NoChip(SystemExit):
    pass


def claim_devices(chips: int, *, allow_cpu: bool = False):
    """The devices of this run, and the peaks of their kind. Exits with a
    non-zero code, printing no result, where JAX finds no TPU or fewer
    chips than the cell asks for. ``allow_cpu`` is the tests' rehearsal
    only: no command line reaches it."""
    import jax
    from quiver_tpu.utils.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        print(f"chipbench: needs a TPU, JAX found {devices[0].platform!r}; "
              "nothing is measured on another backend", file=sys.stderr)
        raise NoChip(3)
    if len(devices) < chips:
        print(f"chipbench: the cell asks for {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        raise NoChip(3)
    peaks = None if allow_cpu and devices[0].platform != "tpu" \
        else spec.peaks(devices[0].device_kind)
    return devices, peaks, cache_dir


class CompileCounter:
    """Counts programs handed to the backend compiler (``jax.monitoring``,
    as ``chip_smoke.Phases`` does). The event also fires where the
    persistent cache answers, which stalls a window all the same."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == self.EVENT:
            self.count += 1


def device_block(devices, used: int) -> dict:
    peak = 0
    for d in devices[:used]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class Profile:
    """The profiler around a window, writing under the checkout's
    ``.chipbench_trace/`` (emptied first, removed after it is read)."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = os.path.join(spec.ROOT, ".chipbench_trace")

    def start(self):
        if self.on:
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            # the Python tracer records every call of every thread: it slows
            # the host the serve cells are bound by, and bloats the trace
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self):
        if self.on:
            import jax
            jax.profiler.stop_trace()

    def xplane(self):
        files = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return files[-1] if files else None

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def judge(compared: dict):
    """``(correct, over)`` of ``{name: (value, limit)}``: a number is over
    where it passes its limit or is no number at all."""
    over = [name for name, (value, limit) in compared.items()
            if not (value is not None and value <= limit)]
    return not over, over


def finish(result: dict, compared: dict) -> int:
    """Print each number compared beside its limit as the last lines of
    standard error, and the result as the last line of standard output,
    with the comparison under a key of its own that comes last."""
    correct, over = judge(compared)
    rows = {}
    for name, (value, limit) in compared.items():
        # JSON has no NaN: a number that is none reads null, and is over
        rows[name] = {"value": value if value is not None
                      and abs(value) < float("inf") else None, "limit": limit}
        print(f"chipbench compared {name}: {value!r} limit {limit!r}"
              f"{'   <-- over' if name in over else ''}", file=sys.stderr)
    out = {"correct": correct}
    out.update(result)
    out["compared"] = rows
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
