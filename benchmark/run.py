"""One run of one cell: ``python3 -m benchmark.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

Set-up (imports, data from the seed, the program's own builds, compiles or
cache loads, one warm unit) is timed from the process's start. The window then
runs whole units until ``--seconds`` have passed, closing at a unit boundary.
After it: the peak memory is read, the program's state is dropped, and the
plain reference judges what the last unit produced. The last line of standard
output is the result.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time

from benchmark import manifest
from benchmark.work.common import least_seconds


def _since_process_start() -> float:
    """Seconds since this process started (interpreter start-up and imports
    included), from ``/proc``."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def p95_nearest_rank(values: list[float]) -> float:
    ordered = sorted(values)
    return ordered[max(math.ceil(0.95 * len(ordered)), 1) - 1]


def _say(kind: str, payload: dict) -> None:
    print(f"{kind}: {json.dumps(payload)}", flush=True)


def _fail(message: str, code: int) -> int:
    print(f"benchmark: {message}", file=sys.stderr, flush=True)
    return code


def _window(cell, limit: float) -> tuple[list[float], float]:
    """Whole units until ``limit`` seconds have passed: every unit's wall,
    and the wall from the first unit's start to the last one's barrier."""
    import jax

    walls: list[float] = []
    with jax.profiler.TraceAnnotation("bench.window"):
        begin = time.perf_counter()
        while True:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.unit"):
                cell.unit()
            now = time.perf_counter()
            walls.append(now - t)
            if now - begin >= limit:
                return walls, now - begin


def main(argv=None, *, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="FILE",
                    help="copy the traced run's .xplane.pb here (how the "
                         "selfcheck's recorded trace was made)")
    args = ap.parse_args(argv)

    entry, workload, config = manifest.cell(args.workload)
    try:
        from photon_ml_tpu import compile_cache
        from photon_ml_tpu.telemetry import profiling
    except ImportError as e:
        return _fail(f"the program is not in this checkout: {e}", 3)

    import jax

    # every program in the cache after a cell's first run, small ones too
    compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    chips = int(entry["chips"])
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        return _fail(f"no accelerator: JAX reports {devices[0].platform!r}", 2)
    if len(devices) < chips:
        return _fail(f"the cell needs {chips} chips, JAX reports "
                     f"{len(devices)}", 2)
    devices = devices[:chips]
    peaks = manifest.peaks(devices[0].device_kind) if args.trace else None

    cell = manifest.family(config).setup(args.seed, config, workload, devices)
    cell.unit()  # the warm unit: through the window's own call
    cell.reset_counts()
    compiles_before = profiling.total_compiles()
    setup_s = _since_process_start()

    reduced = None
    if args.trace:
        from benchmark import trace

        limit = min(args.seconds, float(workload["trace_seconds"]))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        with tempfile.TemporaryDirectory() as trace_dir:
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                walls, window_s = _window(cell, limit)
            finally:
                jax.profiler.stop_trace()
            xplane = trace.find_xplane(trace_dir)
            if args.keep_trace:
                shutil.copyfile(xplane, args.keep_trace)
            reduced = trace.reduce(xplane, chips)
    else:
        walls, window_s = _window(cell, args.seconds)

    compiles = profiling.total_compiles() - compiles_before
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    _say("info", {"units": len(walls), "unit_walls_s": walls,
                  "window_s": window_s, "rows_per_unit": cell.rows_per_unit,
                  "compiles_in_window": compiles})

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": chips, "memory_peak_bytes": memory_peak}
    values: dict[str, float] = {}
    breakdown: dict = {}
    if args.trace:
        run = {"trace": reduced, "window_s": window_s, "chips": chips,
               "peaks": peaks, "counters": cell.counters(),
               "work": cell.required_work(), "compiles_in_window": compiles,
               "memory_peak_bytes": memory_peak}
        _say("info", {"paths": cell.describe(), "work": run["work"],
                      "bound": least_seconds(
                          run["work"]["flops_per_chip"],
                          run["work"]["bytes_per_chip"], peaks)[1]})
        for m in manifest.metrics_of(args.workload, "per_layer"):
            spec = manifest.metric_file(m["name"])
            value = manifest.reader(spec["reader"]).read(
                run, spec.get("params", {}))
            if value is not None:
                values[m["name"]] = float(value)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"breakdown": {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}}
    else:
        values = {
            "train_rows_per_s": cell.rows_per_unit * len(walls) / window_s,
            "fit_p95_s": p95_nearest_rank(walls),
            "setup_s": setup_s,
        }

    cell.release()
    compared = cell.check()
    correct = all(c.ok for c in compared)
    units = {m["name"]: m["unit"] for kind in ("end_to_end", "per_layer")
             for m in manifest.metrics_of(args.workload, kind)}
    result = {
        "correct": correct, "attempted": len(walls), "failed": 0,
        "metrics": {n: {"value": v, "unit": units[n]}
                    for n, v in values.items()},
        "device": device, **breakdown,
        # a gap that is not finite (a step the program never took) is
        # written as a number JSON can hold
        "compared": {c.name: {"value": c.value if math.isfinite(c.value)
                              else 1e300, "limit": c.limit}
                     for c in compared},
    }
    print(json.dumps(result), flush=True)
    for c in compared:
        print(f"compared {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'OVER'}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
