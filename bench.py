"""Benchmark suite: the reference's headline workloads on one TPU chip.

Emits one JSON line per metric — the HEADLINE metric (config-1-shaped GLM
L-BFGS throughput) first, then the GAME-path metrics (BASELINE configs 4–5
shapes), mixed precision, and ingest:

1. ``glm_logistic_lbfgs_samples_to_convergence_per_sec`` — L2 logistic via
   the on-device compiled L-BFGS loop with the fused Pallas value+grad
   kernel; ``vs_baseline`` = speedup over a same-host scipy L-BFGS-B solve
   of the identical problem (the closest stand-in for the reference's
   breeze/JVM solve; the reference publishes no numbers —
   BASELINE.json published:{}).
2. ``glm_logistic_bf16_design_...`` — the same solve with the design stored
   bfloat16 (the ``--design-dtype bfloat16`` product path): half the HBM
   traffic on the dominant payload; value parity asserted loosely (the
   design itself is rounded).
3. ``re_bucketed_solve_entities_per_sec`` — the random-effect hot loop
   (reference ``algorithm/RandomEffectCoordinate.scala``): 10^5+ power-law
   entities / 10^7 rows bucketed into fixed shapes and solved by vmapped
   compiled L-BFGS; ``vs_baseline`` = speedup over per-entity scipy solves
   (measured on a sample, scaled — the per-entity solves are independent).
4. ``game_cd_sweep_samples_per_sec`` — a full coordinate-descent sweep
   (fixed effect + two random effects, Yahoo!-Music-shaped) through
   GameEstimator, residual accounting and all (reference
   ``algorithm/CoordinateDescent.scala``); ``vs_baseline`` = speedup over a
   numpy/scipy implementation of the same sweep on a proportional slice
   (per-sample work is linear, documented inline).
5. ``avro_ingest_rows_per_sec`` — Avro container → columnar GameData
   through the C++ native decoder (reference ``AvroDataReader.scala``);
   ``vs_baseline`` = speedup over the pure-Python codec on the same data.
6. ``avro_scoring_write_rows_per_sec`` — columnar scores →
   ``ScoringResultAvro`` through the C++ native writer (reference
   ``GameScoringDriver.scala`` output); ``vs_baseline`` = speedup over the
   pure-Python record encoder at the same (null) codec.
7. ``game_end_to_end_rows_per_sec`` — the full GAME training driver on a
   music-shaped Avro file: ingest → index maps → bucket build → CD sweeps →
   model + metadata written (reference ``GameTrainingDriver.scala`` "Read
   data"→"Save models" wall — the number the north-star 200-executor-Spark
   comparison is actually about); ``vs_baseline`` = speedup over a composite
   of the SAME run's measured host rates (pure-Python ingest + host
   numpy/scipy CD sweep), i.e. 1/rate_e2e vs 1/rate_py_ingest +
   1/rate_host_cd — each component measured in this process, composition
   documented inline.

NOTE device: every mode but ``--only ingest`` fails at start unless JAX's
default backend is ``tpu`` — these are device metrics, and a CPU run must
not print them.

NOTE timing sync: timed regions end in a device→host transfer
(``float(x)``), which waits for the value. With libtpu
``jax.block_until_ready`` blocks as well (measured on the v5e, PR 21:
CHANGES.md), so either is a sound barrier.

NOTE compile budget: the suite compiles ~20 distinct shapes; main() places
JAX's persistent compilation cache by ``photon_ml_tpu.compile_cache`` (where
``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``), and the
big Avro fixtures are content-cached under the system temp dir so reruns
skip the pure-Python encode.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time

import numpy as np

N_SAMPLES = 200_000
N_FEATURES = 1024
NNZ_PER_ROW = 64
L2 = 1.0
MAX_ITERS = 50

# random-effect benchmark shape: "hundreds of millions of entities" is the
# reference's claim; 10^5+ entities / 10^7 rows is what one chip's bench
# minute buys while exercising the same bucketing machinery
RE_ENTITIES = 150_000
RE_ROWS = 10_000_000
RE_DIM = 8
RE_SCIPY_SAMPLE = 150  # entities timed on host, scaled (solves independent)

# CD-sweep shape (music-like: global + per-user + per-song)
CD_ROWS = 1_000_000
CD_D_FIXED = 32
CD_D_RE = 8
CD_USERS = 30_000
CD_SONGS = 10_000
CD_HOST_ROWS = 50_000  # host-baseline slice (scaled proportionally)

INGEST_ROWS = 120_000
INGEST_PY_ROWS = 12_000  # pure-Python codec rows (30x slower; scaled)

# end-to-end driver shape (music-like, sized so the TRAIN stage carries
# real compute — at 200k rows the metric measured driver fixed costs, not
# the pipeline; round-5 raised it to 1M rows / 55k entities)
E2E_ROWS = 1_000_000
E2E_USERS = 40_000
E2E_SONGS = 15_000


def _probe_op():
    """One trivial round-trip on the TPU. Any other default backend is an
    error: the suite's numbers are device metrics, and no chip means no
    run — not a CPU run under device metric names."""
    import jax
    import jax.numpy as jnp

    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"bench.py measures the TPU, and JAX's default backend here is "
            f"{backend!r}; only `--only ingest` runs without a chip")
    return float(jax.jit(lambda a: a * 2.0)(jnp.float32(1.0)))


def _probe_device(deadline_s: "float | None" = None):
    """Fail LOUDLY if there is no TPU, or if it is unreachable, instead of
    measuring something else or hanging.

    A first device call that blocks forever in native code keeps the
    SIGTERM handler from running (the main thread never re-enters Python),
    and the harness kill then leaves an EMPTY artifact — `_emit_summary`
    has nothing to replay. This runs a trivial round-trip on the main
    thread under a watchdog thread. On deadline the watchdog prints a
    terminal suite_summary line that NAMES the environment failure — the
    structured `error` + rc=3 shape `tools/bench_gate.py` classifies as
    `infra-failure` — then exits 3.

    The deadline defaults to 90 s (`PHOTON_BENCH_PROBE_TIMEOUT_S` to
    override); a process reaches the chip in about 15 s."""
    if deadline_s is None:
        deadline_s = float(os.environ.get(
            "PHOTON_BENCH_PROBE_TIMEOUT_S", 90.0))
    done = threading.Event()

    def _watch():
        if done.wait(deadline_s):
            return
        _emit_summary(error=(
            "device unreachable: a trivial device round-trip did not "
            f"complete within {deadline_s:.0f}s; nothing was measured"))
        os._exit(3)

    watchdog = threading.Thread(target=_watch, daemon=True)
    watchdog.start()
    try:
        value = _probe_op()
        assert value == 2.0, f"device probe computed {value}, expected 2.0"
    except Exception as e:
        # fail-FAST mode (connection refused, backend-init error): the
        # raise never reaches a try/finally that emits the summary, so
        # name the failure in a terminal line here before propagating
        _emit_summary(error=(
            f"device probe failed: {type(e).__name__}: {e}"))
        raise
    except BaseException as e:
        # SystemExit/KeyboardInterrupt (e.g. the SIGTERM handler's
        # SystemExit(124) from a harness timeout) is NOT a device
        # failure — label it as the interruption it is, then propagate
        _emit_summary(error=(
            f"interrupted during device probe: {type(e).__name__}: {e}"))
        raise
    finally:
        # cancel the watchdog on EVERY outcome: it exists to catch the
        # probe never returning. Leaving it armed after a fail-fast
        # exception would have it os._exit(3) in whatever the process
        # does next (observed: it hard-killed a pytest run 30 s later)
        done.set()


def _generator_tag(fn, args) -> str:
    """Cache key for a generator function, in two parts: an args hash
    (identifies the fixture VARIANT — several can be live at once, e.g.
    the big and small ingest files) then a code hash over bytecode +
    CONSTANTS (identifies the GENERATION — ``co_code`` alone stores only
    indices into ``co_consts``, so editing a literal like a seed or a
    scale would otherwise silently reuse stale data). The split lets the
    fixture cache GC dead generations of one variant without touching
    its siblings."""
    import hashlib

    ahash = hashlib.sha1(repr(args).encode()).hexdigest()[:8]
    chash = hashlib.sha1(
        fn.__code__.co_code + b"|"
        + repr(fn.__code__.co_consts).encode()).hexdigest()[:8]
    return f"{ahash}-{chash}"


def _fixture_path(name: str, fn, args, ext: str) -> "tuple[str, bool]":
    """Resolve the cache path for (name, fn, args) and return
    ``(path, exists)``; on a cache miss, first GC stale files so dead
    generations don't accumulate (20-500 MB each — dozens were found
    hoarding ~5 GB of /tmp). Collected: other GENERATIONS of this
    variant (same args hash, different code hash) and legacy pre-split
    names (no dash in the tag — all dead by construction under the
    current naming). Sibling variants sharing a name — the big and small
    ingest files — survive.

    NOTE single-writer assumption: the GC unlinks files another bench
    process could in principle still be reading, if a run of an OLDER
    bench.py overlaps a run of an edited one. Benches run one at a time
    on these boxes (1 CPU; the suite cannot share it), so the trade is
    taken for the disk space; per-uid naming still isolates users, and
    the unique staging file keeps same-version runs race-free."""
    import glob

    tag = _generator_tag(fn, args)
    ahash, _chash = tag.split("-")
    prefix = f"photon_bench_{os.getuid()}_{name}_"
    path = os.path.join(tempfile.gettempdir(), f"{prefix}{tag}{ext}")
    if os.path.exists(path):
        return path, True
    for old in glob.glob(os.path.join(tempfile.gettempdir(),
                                      f"{prefix}*{ext}")):
        base_tag = os.path.basename(old)[len(prefix):-len(ext)]
        if base_tag.startswith(f"{ahash}-") or "-" not in base_tag:
            try:
                os.unlink(old)
            except OSError:
                pass  # another process may have raced the same cleanup
    return path, False


def _cached_fixture(name: str, fn, *args) -> str:
    """Deterministic Avro fixtures cached across bench runs (the pure-Python
    encode of a 1e5-row file costs ~10 s — prep, not measurement).

    ``fn(path, *args)`` generates the file. The cache key folds in ``args``
    and ``fn``'s own bytecode, so editing the generator or its parameters
    invalidates the cached file instead of silently benchmarking stale
    data (see :func:`_fixture_path` for the naming and GC rules)."""
    path, exists = _fixture_path(name, fn, args, ".avro")
    if not exists:
        fd, tmp = tempfile.mkstemp(dir=tempfile.gettempdir(),
                                   suffix=".avro.tmp")
        os.close(fd)
        try:
            fn(tmp, *args)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        _heartbeat()  # a cold 1M-row encode is minutes of pre-metric prep
    return path


def _cached_npz(name: str, fn, *args) -> dict:
    """Deterministic numpy fixtures cached across bench runs (generating
    the 10M-row random-effect problem costs ~40 s of rng/alias-sampling —
    prep, not measurement). Same keying discipline as
    :func:`_cached_fixture`: args + the generator's bytecode."""
    path, exists = _fixture_path(name, fn, args, ".npz")
    if not exists:
        arrays = fn(*args)
        fd, tmp = tempfile.mkstemp(dir=tempfile.gettempdir(),
                                   suffix=".npz.tmp")
        os.close(fd)
        try:
            with open(tmp, "wb") as f:
                np.savez(f, **arrays)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    return dict(np.load(path))


_T0 = time.perf_counter()

# every _emit line, in order — the terminal summary line replays them all
_RESULTS: list[dict] = []
# the winning e2e run's perf report + overlap numbers (filled by
# bench_end_to_end via _stash_perf_report; the gate attaches the report
# to a regression verdict so the slowdown arrives with its critical path)
_E2E_PERF_REPORT: list[str] = []
# perf_counter of the latest emit — the stall watchdog's heartbeat
_LAST_PROGRESS: list[float] = [0.0]
# set once the terminal summary has printed; keeps the main thread's
# finally and a firing watchdog from double-printing it
_SUMMARY_LOCK = threading.Lock()
_SUMMARY_DONE: list[bool] = [False]


def _heartbeat():
    """Tell the stall watchdog the suite is making progress. Called from
    `_emit` and from known-long silent stretches (fixture encodes, the
    e2e warm/measured runs) so a healthy cold run — whose FIRST metric
    can be 15-20 min away — is never mistaken for a hang."""
    _LAST_PROGRESS[0] = time.perf_counter()


def _emit(metric: str, value: float, unit: str, vs_baseline: float, **extra):
    line = {"metric": metric, "value": round(value, 1), "unit": unit,
            "vs_baseline": round(vs_baseline, 3)}
    line.update(extra)
    # suite-elapsed stamp: makes the per-bench budget visible in the
    # artifact (the round-2 harness run timed out with 3/6 metrics and no
    # way to see where the time went)
    line["t_s"] = round(time.perf_counter() - _T0, 1)
    _RESULTS.append(line)
    _heartbeat()
    print(json.dumps(line), flush=True)


def _start_stall_watchdog(stall_s: float | None = None):
    """Emit the terminal summary even if a device call hangs MID-suite.

    A device call that hangs between benches leaves the main thread blocked
    in native code: the SIGTERM handler can never run (Python signal
    handlers execute on the main thread), the ``finally`` never executes,
    and the harness SIGKILL would discard every metric measured so far.
    A daemon thread watches the `_emit` heartbeat; past the deadline it
    prints the summary itself — partial results plus an ``error`` naming
    where the suite stalled — and exits 4. The deadline (default 30 min,
    ``PHOTON_BENCH_STALL_S`` to override) was sized before PR 1 at ~2x the
    longest silent stretch then observed (5-15 min); not re-measured on the
    present chip."""
    stall = float(stall_s if stall_s is not None
                  else os.environ.get("PHOTON_BENCH_STALL_S", 1800))
    _heartbeat()

    def _watch():
        while True:
            time.sleep(min(30.0, stall / 4))
            idle = time.perf_counter() - _LAST_PROGRESS[0]
            if idle > stall:
                last = _RESULTS[-1]["metric"] if _RESULTS else "none"
                _emit_summary(error=(
                    f"suite stalled: no metric for {idle:.0f}s "
                    f"(last completed: {last}) — device call hung "
                    "mid-suite; partial results above"))
                os._exit(4)

    threading.Thread(target=_watch, daemon=True).start()


def _tools_module(name: str):
    """Import a module from tools/ (bench.py sits at the repo root)."""
    import importlib
    import sys

    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    return importlib.import_module(name)


def _stash_perf_report(telemetry_dir: "str | None") -> "dict | None":
    """Render the e2e winner's perf report (before its tempdir vanishes),
    stash the text for the gate, and return the async-I/O overlap numbers
    for the metric line. Never fails the bench — telemetry is evidence,
    not a dependency."""
    if not telemetry_dir:
        return None
    try:
        perf_report = _tools_module("perf_report")
        trace_path, prom_path = perf_report.resolve_inputs(telemetry_dir)
        spans = perf_report.load_spans(trace_path)
        prom_text = ""
        if os.path.exists(prom_path):
            with open(prom_path, encoding="utf-8") as f:
                prom_text = f.read()
        _E2E_PERF_REPORT[:] = [perf_report.build_report(spans, prom_text)]
        return perf_report.io_overlap(spans)
    except Exception:
        return None


def _quality_extras(out_dir: "str | None", train_avro: str) -> dict:
    """Model-quality overhead extras for the e2e metric line: the size of
    the published quality-baseline.json (baseline work is train-side and
    background-thread only — the wall already proves it cost ~0) and the
    canary shadow-scoring wall (the activation-time cost a --canary-gate
    deployment pays, measured by reloading the trained model against a
    64-record reservoir drawn from its own training sample). Never fails
    the bench."""
    if not out_dir:
        return {}
    extras: dict = {}
    baseline_path = os.path.join(out_dir, "quality-baseline.json")
    extras["quality_baseline_bytes"] = (
        os.path.getsize(baseline_path)
        if os.path.exists(baseline_path) else 0)
    try:
        from photon_ml_tpu.cli.config import parse_feature_shard_config
        from photon_ml_tpu.io.avro import iter_avro_file
        from photon_ml_tpu.quality import CanaryConfig
        from photon_ml_tpu.serving import ModelRegistry

        shard_configs = tuple(
            parse_feature_shard_config(s)
            for s in "global=g|intercept,item=it|noIntercept".split(","))
        records = []
        for rec in iter_avro_file(train_avro):
            records.append(rec)
            if len(records) >= 64:
                break
        registry = ModelRegistry(shard_configs, canary=CanaryConfig())
        registry.load(out_dir)
        registry.observe_requests(records)
        # reload the same model: the canary shadow-scores the reservoir
        # through both engines (divergence 0 by construction) — its wall
        # is the pure canary-evaluation cost
        sm = registry.load(out_dir)
        if sm.canary is not None:
            extras["canary_eval_s"] = round(sm.canary["seconds"], 4)
            extras["canary_divergence"] = round(
                sm.canary["divergence"], 6)
    except Exception as e:
        extras["canary_eval_error"] = repr(e)[:200]
    return extras


# gate the FULL suite by default; main() flips this off for --only subset
# runs (every unrun metric would read as "vanished" = regression).
# PHOTON_BENCH_GATE=0/1 overrides either way.
_GATE_DEFAULT = [True]


def _find_baseline() -> "tuple[str, dict] | None":
    """The last SOUND bench artifact next to this file (BENCH_rNN.json,
    newest round first; infra-failed rounds — like r05's device outage —
    are skipped). ``PHOTON_BENCH_BASELINE`` overrides the search."""
    import glob

    bench_gate = _tools_module("bench_gate")
    override = os.environ.get("PHOTON_BENCH_BASELINE")
    here = os.path.dirname(os.path.abspath(__file__))
    candidates = ([override] if override else
                  sorted(glob.glob(os.path.join(here, "BENCH_r*.json")),
                         reverse=True))
    for path in candidates:
        art = bench_gate.load_artifact(path)
        if art is not None and bench_gate.infra_failure(art) is None:
            return path, art
    return None


def _gate_line(summary: dict) -> "dict | None":
    """The auto-gate: this suite's summary vs the last sound artifact,
    as one JSON-able line (``tools/bench_gate.py`` semantics). On a
    ``regression`` verdict the e2e run's perf report rides along, so the
    slowdown arrives with its critical path attached. Returns None (and
    gates nothing) when no sound baseline exists or the gate itself
    errors — the gate must never break the terminal summary.
    ``PHOTON_BENCH_GATE=0`` disables it."""
    flag = os.environ.get("PHOTON_BENCH_GATE")
    enabled = (flag != "0") if flag is not None else _GATE_DEFAULT[0]
    if not enabled:
        return None
    try:
        bench_gate = _tools_module("bench_gate")
        found = _find_baseline()
        current = bench_gate.normalize_artifact({"parsed": summary})
        verdict = bench_gate.gate(current,
                                  found[1] if found else None)
        line = {"metric": "bench_gate",
                "baseline": os.path.basename(found[0]) if found else None}
        line.update(verdict)
        if (verdict.get("verdict") == bench_gate.VERDICT_REGRESSION
                and _E2E_PERF_REPORT):
            line["perf_report"] = _E2E_PERF_REPORT[0][:8000]
        return line
    except Exception:
        return None


def _emit_summary(error: str | None = None):
    """The LAST stdout line: one JSON object holding EVERY metric.

    Two consecutive harness runs produced half-empty official scoreboards
    (round 2: rc=124 truncation; round 3: rc=0 but only the output TAIL is
    preserved, and five of seven metric lines scrolled out of it). The
    driver parses the final JSON line of the tail, so a terminal
    aggregate line makes the artifact complete by construction — including
    each metric's extras (bucket_build_s, per-stage e2e seconds, ...).
    Headline value/vs_baseline = the end-to-end driver metric (the
    north-star-shaped number) when present, else the first metric.

    ``error`` marks an environment failure (device unreachable, mid-suite
    stall): the summary then prints even with zero results, so the
    artifact names the failure instead of being empty. The lock/flag keep
    the main thread's ``finally`` and a firing watchdog thread from
    printing two terminal lines."""
    with _SUMMARY_LOCK:
        if _SUMMARY_DONE[0] or (not _RESULTS and error is None):
            return
        _SUMMARY_DONE[0] = True
    # a retried/process-group SIGTERM landing mid-print would truncate the
    # very line this function exists to guarantee — ignore further TERMs
    # for the final write
    import signal

    try:
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
    except (ValueError, OSError):
        pass  # non-main thread or exotic platform: emit anyway
    head = next((r for r in _RESULTS
                 if r["metric"] == "game_end_to_end_rows_per_sec"),
                _RESULTS[0] if _RESULTS else
                {"metric": "none", "value": 0.0, "unit": "no metrics",
                 "vs_baseline": 0.0})
    summary = {
        "metric": "suite_summary",
        "value": head["value"],
        "unit": head["unit"] + " (headline: " + head["metric"] + ")",
        "vs_baseline": head["vs_baseline"],
        "n_metrics": len(_RESULTS),
        "suite_wall_s": round(time.perf_counter() - _T0, 1),
        "metrics": {r["metric"]: {k: v for k, v in r.items()
                                  if k != "metric"}
                    for r in _RESULTS},
    }
    if error is not None:
        summary["error"] = error
    else:
        # auto-gate against the last sound artifact: the verdict prints as
        # its own JSON line AND rides the summary under "gate" (the
        # summary must stay the FINAL line — the harness parses the last
        # line of the tail as the artifact, and future gates read that
        # artifact's metric set)
        gate_line = _gate_line(summary)
        if gate_line is not None:
            summary["gate"] = {k: v for k, v in gate_line.items()
                               if k not in ("metric", "perf_report")}
            print(json.dumps(gate_line), flush=True)
    print(json.dumps(summary), flush=True)


# --------------------------------------------------------------------------
# 1+2. headline GLM solve (f32 fused kernel; bf16-design variant)
# --------------------------------------------------------------------------

def _make_problem(seed=0):
    """Sparse-generated logistic data, densified (dense is the TPU-first
    layout at this dim — SURVEY.md §7 hard-parts #2). Feature columns carry
    a log-uniform scale spread (~3 decades) so the solve runs the full
    iteration budget and measures sustained per-iteration throughput."""
    rng = np.random.default_rng(seed)
    n, d, k = N_SAMPLES, N_FEATURES, NNZ_PER_ROW
    rows = np.repeat(np.arange(n, dtype=np.int32), k)
    cols = rng.integers(0, d, size=n * k, dtype=np.int32)
    col_scale = np.power(10.0, rng.uniform(-2.0, 1.0, size=d)).astype(np.float32)
    vals = (rng.normal(size=n * k).astype(np.float32) / np.sqrt(k)
            * col_scale[cols])
    x = np.zeros((n, d), np.float32)
    np.add.at(x, (rows, cols), vals)
    w_true = (rng.normal(size=d).astype(np.float32) / col_scale)
    margins = x @ w_true
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margins))).astype(np.float32)
    return x, y


def _scipy_baseline(x, y):
    import scipy.optimize

    xx = x.astype(np.float64)
    yy = y.astype(np.float64)

    def f(w):
        m = xx @ w
        ym = np.where(yy > 0.5, m, -m)
        loss = np.logaddexp(0.0, -ym).sum() + 0.5 * L2 * w @ w
        p = 1.0 / (1.0 + np.exp(-m))
        g = xx.T @ (p - yy) + L2 * w
        return loss, g

    t0 = time.perf_counter()
    res = scipy.optimize.minimize(
        f, np.zeros(N_FEATURES), jac=True, method="L-BFGS-B",
        options={"maxiter": MAX_ITERS, "ftol": 0.0, "gtol": 1e-12})
    return time.perf_counter() - t0, float(res.fun)


def _tpu_solve(x, y, dtype=None):
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.design import DenseDesign
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.objective import GLMData, GLMObjective
    from photon_ml_tpu.optimize import OptimizerConfig, minimize_lbfgs
    from photon_ml_tpu.types import TaskType

    n = x.shape[0]
    xd = jnp.asarray(x, dtype or jnp.float32)
    data = GLMData(
        design=DenseDesign(x=xd),
        labels=jnp.asarray(y),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
    )
    objective = GLMObjective(loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
                             fused=True)
    cfg = OptimizerConfig(max_iterations=MAX_ITERS, tolerance=1e-12,
                          track_states=False)

    @jax.jit
    def solve(data):
        fun = lambda w: objective.value_and_grad(w, data, L2)
        return minimize_lbfgs(fun, jnp.zeros((N_FEATURES,), jnp.float32), cfg)

    result = solve(data)
    _ = float(result.value)  # compile + first run; D2H is the real barrier
    best = float("inf")
    for _rep in range(3):
        t0 = time.perf_counter()
        result = solve(data)
        val = float(result.value)
        best = min(best, time.perf_counter() - t0)
    return best, val, int(result.iterations)


def bench_glm():
    import jax.numpy as jnp

    x, y = _make_problem()
    tpu_s, tpu_val, _iters = _tpu_solve(x, y)
    _heartbeat()  # fresh kernel compiles can be many minutes of silence
    base_s, base_val = _scipy_baseline(x, y)
    rel = abs(tpu_val - base_val) / max(abs(base_val), 1.0)
    assert rel < 5e-3, f"objective mismatch: tpu={tpu_val} scipy={base_val}"
    _emit("glm_logistic_lbfgs_samples_to_convergence_per_sec",
          N_SAMPLES / tpu_s, "samples/s", base_s / tpu_s)

    bf_s, bf_val, _ = _tpu_solve(x, y, dtype=jnp.bfloat16)
    rel_bf = abs(bf_val - base_val) / max(abs(base_val), 1.0)
    assert rel_bf < 3e-2, f"bf16 objective drift: {bf_val} vs {base_val}"
    _emit("glm_logistic_bf16_design_samples_to_convergence_per_sec",
          N_SAMPLES / bf_s, "samples/s", base_s / bf_s,
          value_rel_err=round(rel_bf, 5))


# --------------------------------------------------------------------------
# 3. random-effect bucketed solve at scale
# --------------------------------------------------------------------------

def _gen_re_arrays(n, n_entities, d, seed):
    prng = np.random.default_rng(4242)
    u = (1.2 * prng.normal(size=(n_entities, d))).astype(np.float32)
    rng = np.random.default_rng(seed)
    xr = rng.normal(size=(n, d)).astype(np.float32)
    # power-law entity sizes (the straggler distribution the bucketing
    # machinery exists for)
    probs = 1.0 / np.arange(1, n_entities + 1, dtype=np.float64)
    probs /= probs.sum()
    ent = rng.choice(n_entities, size=n, p=probs).astype(np.int64)
    margin = np.einsum("nd,nd->n", xr, u[ent])
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)
    return {"xr": xr, "y": y, "ent": ent}


def _make_re_problem(n=None, n_entities=None, d=RE_DIM, seed=0):
    from photon_ml_tpu.game.data import GameData
    from photon_ml_tpu.testing import dense_shard

    n = RE_ROWS if n is None else n
    n_entities = RE_ENTITIES if n_entities is None else n_entities
    a = _cached_npz("re", _gen_re_arrays, n, n_entities, d, seed)
    xr, y, ent = a["xr"], a["y"], a["ent"]
    data = GameData.build(
        labels=y, shards={"re": dense_shard(xr)},
        id_columns={"entityId": ent})
    return data, xr, y, ent


def bench_random_effect():
    from photon_ml_tpu.game.data import RandomEffectDataset, RandomEffectDatasetConfig
    from photon_ml_tpu.game.random_effect import RandomEffectSolver
    from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.ops.regularization import L2Regularization
    from photon_ml_tpu.optimize import OptimizerConfig
    from photon_ml_tpu.types import TaskType

    data, xr, y, ent = _make_re_problem()
    # histogram bucketing: ≤5 padded shapes (vs ~10 geometric) — every
    # distinct shape is a fresh XLA compile, a cold-run cost of a
    # fresh-process bench
    cfg = RandomEffectDatasetConfig("entityId", "re",
                                    bucket_strategy="histogram",
                                    max_sample_buckets=5)
    t0 = time.perf_counter()
    dataset = RandomEffectDataset.build("perEntity", data, cfg)
    build_s = time.perf_counter() - t0
    _heartbeat()  # the 10M-row build + upload precede a long compile

    lam = 1.0
    solver = RandomEffectSolver(
        task=TaskType.LOGISTIC_REGRESSION,
        config=GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=25,
                                             tolerance=1e-6,
                                             track_states=False)))
    offsets = np.zeros(data.n_samples, np.float32)
    model, scores = solver.train(dataset, offsets, lam)  # compile + warm
    _ = float(np.asarray(scores[:1])[0])
    _heartbeat()
    t0 = time.perf_counter()
    model, scores = solver.train(dataset, offsets, lam)
    _ = float(np.asarray(scores[:1])[0])
    solve_s = time.perf_counter() - t0
    n_entities = dataset.n_active_entities

    # host baseline: scipy L-BFGS-B per entity on a sample, scaled (the
    # per-entity solves are independent — per-entity mean time is the
    # honest scaling unit; sample spans the size distribution)
    import scipy.optimize

    order = np.argsort(ent, kind="stable")
    bounds = np.searchsorted(ent[order], np.arange(RE_ENTITIES))
    sizes = np.diff(np.append(bounds, len(ent)))
    live = np.flatnonzero(sizes > 0)
    # UNIFORM random draw over live entities: the sample mean then estimates
    # the true per-entity mean cost. (Spacing the sample over the
    # size-sorted id axis looks stratified but left-weights the power-law
    # head — that inflated the measured host cost ~80x when first tried.)
    sample = np.random.default_rng(7).choice(
        live, size=min(RE_SCIPY_SAMPLE, len(live)), replace=False)
    t0 = time.perf_counter()
    for e in sample:
        sel = order[bounds[e]:bounds[e] + sizes[e]]
        xe, ye = xr[sel].astype(np.float64), y[sel].astype(np.float64)

        def f(w):
            m = xe @ w
            loss = (np.logaddexp(0.0, -np.where(ye > 0.5, m, -m)).sum()
                    + 0.5 * lam * w @ w)
            p = 1.0 / (1.0 + np.exp(-m))
            return loss, xe.T @ (p - ye) + lam * w

        scipy.optimize.minimize(f, np.zeros(RE_DIM), jac=True,
                                method="L-BFGS-B",
                                options={"maxiter": 25})
    host_per_entity = (time.perf_counter() - t0) / len(sample)
    host_entities_per_sec = 1.0 / host_per_entity

    tpu_entities_per_sec = n_entities / solve_s
    _emit("re_bucketed_solve_entities_per_sec", tpu_entities_per_sec,
          "entities/s", tpu_entities_per_sec / host_entities_per_sec,
          n_entities=int(n_entities), n_rows=int(RE_ROWS),
          bucket_build_s=round(build_s, 2))


# --------------------------------------------------------------------------
# 3b. fused Pallas RE sweep kernel vs the XLA per-bucket solve
# --------------------------------------------------------------------------

#: (rows, entities, dim) mixes for the re_sweep microbench: the power-law
#: small-dim default shape, and a fewer-but-fatter mix so the kernel's
#: wider-lane blocks get exercised too
RE_SWEEP_SHAPES = [
    (1_500_000, 25_000, 8),
    (750_000, 4_000, 32),
]


def bench_re_sweep():
    """Microbench the fused Pallas random-effect sweep kernel
    (``ops/pallas_re.py``, engaged by ``RandomEffectSolver(fused=True)``)
    against the XLA ``_solve_bucket`` two-pass path on identical datasets,
    at the ``RE_SWEEP_SHAPES`` bucket mixes × {float32, bfloat16} design
    dtypes. One ``re_sweep_entities_per_sec_*`` line per dtype (aggregate
    entities/s across shapes); ``vs_baseline`` = XLA wall / fused wall on
    the same shapes — >1 means the single-pass kernel is winning. Off-TPU
    both paths lower to the same XLA closed form (the kernel gate is
    inert), so the ratio degenerates to ~1 by construction.
    """
    import dataclasses

    from photon_ml_tpu.game.data import (
        RandomEffectDataset,
        RandomEffectDatasetConfig,
    )
    from photon_ml_tpu.game.random_effect import RandomEffectSolver
    from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.ops.regularization import L2Regularization
    from photon_ml_tpu.optimize import OptimizerConfig
    from photon_ml_tpu.types import TaskType

    base = RandomEffectSolver(
        task=TaskType.LOGISTIC_REGRESSION,
        config=GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=25,
                                             tolerance=1e-6,
                                             track_states=False)))

    def timed_train(solver, dataset, offsets):
        model, scores = solver.train(dataset, offsets, 1.0)  # compile + warm
        _ = float(np.asarray(scores[:1])[0])
        _heartbeat()
        best = float("inf")
        for _rep in range(2):
            t0 = time.perf_counter()
            model, scores = solver.train(dataset, offsets, 1.0)
            _ = float(np.asarray(scores[:1])[0])
            best = min(best, time.perf_counter() - t0)
        return best

    for dtype_tag, design_dtype in (("f32", "float32"), ("bf16", "bfloat16")):
        fused_s = xla_s = 0.0
        entities = 0
        extras = {}
        for (n, n_ent, d) in RE_SWEEP_SHAPES:
            data, _xr, _y, _ent = _make_re_problem(n, n_ent, d, seed=1)
            cfg = RandomEffectDatasetConfig("entityId", "re",
                                            bucket_strategy="histogram",
                                            max_sample_buckets=4)
            # one dataset per path: the device bucket cache keys by design
            # dtype, not by solver, so sharing one would hide the second
            # path's upload cost asymmetrically
            walls = {}
            offsets = np.zeros(data.n_samples, np.float32)
            for tag, fused in (("fused", True), ("xla", False)):
                dataset = RandomEffectDataset.build("perEntity", data, cfg)
                solver = dataclasses.replace(base, fused=fused,
                                             design_dtype=design_dtype)
                walls[tag] = timed_train(solver, dataset, offsets)
            entities += dataset.n_active_entities
            extras[f"s{n_ent}x{d}_fused_s"] = round(walls["fused"], 3)
            extras[f"s{n_ent}x{d}_xla_s"] = round(walls["xla"], 3)
            fused_s += walls["fused"]
            xla_s += walls["xla"]
        _emit(f"re_sweep_entities_per_sec_{dtype_tag}",
              entities / fused_s, "entities/s", xla_s / fused_s, **extras)


# --------------------------------------------------------------------------
# 4. full coordinate-descent sweep (fixed + 2 random effects)
# --------------------------------------------------------------------------

def _gen_cd_arrays(n, users, songs, seed, d_fixed, d_re):
    prng = np.random.default_rng(777)
    w_fixed = prng.normal(size=d_fixed).astype(np.float32)
    uu = (1.0 * prng.normal(size=(users, d_re))).astype(np.float32)
    us = (0.7 * prng.normal(size=(songs, d_re))).astype(np.float32)
    rng = np.random.default_rng(seed)
    xf = rng.normal(size=(n, d_fixed)).astype(np.float32)
    xi = rng.normal(size=(n, d_re)).astype(np.float32)
    pu = 1.0 / np.arange(1, users + 1); pu /= pu.sum()
    ps = 1.0 / np.arange(1, songs + 1); ps /= ps.sum()
    user = rng.choice(users, size=n, p=pu).astype(np.int64)
    song = rng.choice(songs, size=n, p=ps).astype(np.int64)
    margin = (xf @ w_fixed + np.einsum("nd,nd->n", xi, uu[user])
              + np.einsum("nd,nd->n", xi, us[song]))
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)
    return {"xf": xf, "xi": xi, "user": user, "song": song, "y": y}


def _make_cd_problem(n, users, songs, seed=0):
    from photon_ml_tpu.game.data import GameData
    from photon_ml_tpu.testing import dense_shard

    a = _cached_npz("cd", _gen_cd_arrays, n, users, songs, seed,
                    CD_D_FIXED, CD_D_RE)
    xf, xi, user, song, y = a["xf"], a["xi"], a["user"], a["song"], a["y"]
    data = GameData.build(
        labels=y,
        shards={"fixed": dense_shard(xf),
                "item": dense_shard(xi)},
        id_columns={"userId": user, "songId": song})
    return data, (xf, xi, user, song, y)


def _host_cd_sweep(xf, xi, user, song, y, lam_fixed, lam_re, sweeps=1):
    """numpy/scipy CD sweep: fixed scipy L-BFGS-B + per-entity Newton-ish
    scipy solves, residual-offset accounting — the same algorithm the
    device path runs, in plain host code."""
    import scipy.optimize

    n = len(y)
    yy = y.astype(np.float64)
    scores = {"global": np.zeros(n), "perUser": np.zeros(n),
              "perSong": np.zeros(n)}

    def logistic(xd, yl, off, lam, w0):
        def f(w):
            m = xd @ w + off
            loss = (np.logaddexp(0.0, -np.where(yl > 0.5, m, -m)).sum()
                    + 0.5 * lam * w @ w)
            p = 1.0 / (1.0 + np.exp(-m))
            return loss, xd.T @ (p - yl) + lam * w

        return scipy.optimize.minimize(
            f, w0, jac=True, method="L-BFGS-B",
            options={"maxiter": 25}).x

    w_f = np.zeros(CD_D_FIXED)
    re_models = {"perUser": {}, "perSong": {}}
    for _ in range(sweeps):
        # fixed effect
        off = scores["perUser"] + scores["perSong"]
        w_f = logistic(xf.astype(np.float64), yy, off, lam_fixed, w_f)
        scores["global"] = xf @ w_f
        # random effects
        for cid, ids in (("perUser", user), ("perSong", song)):
            off_all = sum(s for k, s in scores.items() if k != cid)
            order = np.argsort(ids, kind="stable")
            srt = ids[order]
            starts = np.searchsorted(srt, np.unique(srt))
            uniq = np.unique(srt)
            new_scores = np.zeros(n)
            for k, e in enumerate(uniq):
                lo = starts[k]
                hi = starts[k + 1] if k + 1 < len(starts) else n
                sel = order[lo:hi]
                xd = xi[sel].astype(np.float64)
                w0 = re_models[cid].get(e, np.zeros(CD_D_RE))
                w_e = logistic(xd, yy[sel], off_all[sel], lam_re, w0)
                re_models[cid][e] = w_e
                new_scores[sel] = xd @ w_e
            scores[cid] = new_scores
    return w_f


# host baselines for the e2e composite, measured in the e2e bench's own
# process slot (first, cleanest) and cached for reuse WITHIN that bench.
# The cd-sweep/ingest benches deliberately do NOT reuse these: each
# bench's vs_baseline divides a numerator by a baseline measured in the
# SAME process state (``fresh=True``), because host-bound walls on this
# box swing with inter-bench residue — a clean-slot baseline against a
# late-slot numerator would skew the ratio and break round-over-round
# comparability.
_SHARED_RATES: dict[str, float] = {}


def _py_ingest_rate(fresh: bool = False) -> float:
    """Pure-Python Avro ingest rate on the documented INGEST_PY_ROWS slice
    (the read leg of a reference-style host pipeline)."""
    if fresh or "py_ingest" not in _SHARED_RATES:
        from photon_ml_tpu.cli.config import parse_feature_shard_config
        from photon_ml_tpu.io.data_reader import AvroDataReader

        small = _cached_fixture("ingest", _write_ingest_file,
                                INGEST_PY_ROWS)
        t0 = time.perf_counter()
        pdata, _, _ = AvroDataReader(
            shard_configs=(parse_feature_shard_config("f=f|intercept"),),
            use_native=False).read(small, id_columns=["userId"])
        rate = INGEST_PY_ROWS / (time.perf_counter() - t0)
        assert pdata.n_samples == INGEST_PY_ROWS
        _SHARED_RATES["py_ingest"] = rate
    return _SHARED_RATES["py_ingest"]


def _host_cd_rate(fresh: bool = False) -> float:
    """Host numpy/scipy CD sweep rate on a proportional slice (rows AND
    entities scaled by the same factor so per-entity sizes match;
    per-sample work in a CD sweep is linear in rows — documented
    extrapolation)."""
    if fresh or "host_cd" not in _SHARED_RATES:
        frac = CD_HOST_ROWS / CD_ROWS
        _, (hxf, hxi, huser, hsong, hy) = _make_cd_problem(
            CD_HOST_ROWS, max(int(CD_USERS * frac), 1),
            max(int(CD_SONGS * frac), 1), seed=1)
        t0 = time.perf_counter()
        _host_cd_sweep(hxf, hxi, huser, hsong, hy, 1e-3, 1.0)
        _SHARED_RATES["host_cd"] = (
            CD_HOST_ROWS / (time.perf_counter() - t0))
    return _SHARED_RATES["host_cd"]


def bench_cd_sweep():
    from photon_ml_tpu.game.data import RandomEffectDatasetConfig
    from photon_ml_tpu.game.estimator import (
        FixedEffectCoordinateConfig,
        GameEstimator,
        GameOptimizationConfiguration,
        RandomEffectCoordinateConfig,
    )
    from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.ops.regularization import L2Regularization
    from photon_ml_tpu.optimize import OptimizerConfig
    from photon_ml_tpu.types import TaskType

    data, _ = _make_cd_problem(CD_ROWS, CD_USERS, CD_SONGS)
    opt = GLMOptimizationConfiguration(
        regularization=L2Regularization,
        optimizer_config=OptimizerConfig(max_iterations=25, tolerance=1e-6,
                                         track_states=False))
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs={
            "global": FixedEffectCoordinateConfig(
                feature_shard_id="fixed", optimization=opt),
            "perUser": RandomEffectCoordinateConfig(
                dataset=RandomEffectDatasetConfig(
                    "userId", "item", bucket_strategy="histogram",
                    max_sample_buckets=4),
                optimization=opt),
            "perSong": RandomEffectCoordinateConfig(
                dataset=RandomEffectDatasetConfig(
                    "songId", "item", bucket_strategy="histogram",
                    max_sample_buckets=4),
                optimization=opt),
        },
        update_sequence=["global", "perUser", "perSong"],
        n_cd_iterations=1)
    config = GameOptimizationConfiguration(
        {"global": 1e-3, "perUser": 1.0, "perSong": 1.0})
    datasets = est.prepare(data)

    def timed_fit():
        t0 = time.perf_counter()
        r = est.fit(data, [config], datasets=datasets)[0]
        # D2H on a result scalar: the only reliable barrier on this
        # platform (see module NOTE) — the last coordinate's score scatter
        # may still be in flight when est.fit returns
        _ = float(np.asarray(
            r.model.coordinates["global"].model.coefficients.means[0]))
        return time.perf_counter() - t0

    timed_fit()  # compile + warm
    _heartbeat()
    tpu_s = timed_fit()
    tpu_rate = CD_ROWS / tpu_s

    # fresh=True: the comparator must share THIS bench's process state
    # (see the note at _SHARED_RATES)
    host_rate = _host_cd_rate(fresh=True)

    _emit("game_cd_sweep_samples_per_sec", tpu_rate, "samples/s",
          tpu_rate / host_rate, n_rows=int(CD_ROWS),
          n_entities=int(CD_USERS + CD_SONGS), sweep_wall_s=round(tpu_s, 2))


# --------------------------------------------------------------------------
# 5. Avro ingest through the native decoder
# --------------------------------------------------------------------------

def _write_ingest_file(path, n):
    from photon_ml_tpu.io.data_reader import write_training_examples

    rng = np.random.default_rng(0)
    d = 40
    recs = []
    for i in range(n):
        idx = rng.choice(d, size=8, replace=False)
        feats = [{"name": f"f.x{j}", "term": "", "value": float(v)}
                 for j, v in zip(idx, rng.normal(size=8))]
        recs.append({"uid": str(i), "response": float(rng.integers(0, 2)),
                     "offset": None, "weight": None, "features": feats,
                     "metadataMap": {"userId": f"u{rng.integers(0, 997)}"}})
    write_training_examples(path, recs)
    return path


def bench_ingest():
    from photon_ml_tpu.cli.config import parse_feature_shard_config
    from photon_ml_tpu.io.data_reader import AvroDataReader

    shard_cfg = (parse_feature_shard_config("f=f|intercept"),)
    big = _cached_fixture("ingest", _write_ingest_file, INGEST_ROWS)
    reader = AvroDataReader(shard_configs=shard_cfg)
    reader.read(big, id_columns=["userId"])  # warm (index build etc.)
    t0 = time.perf_counter()
    reader_n = AvroDataReader(shard_configs=shard_cfg)
    data, _, _ = reader_n.read(big, id_columns=["userId"])
    native_s = time.perf_counter() - t0
    assert data.n_samples == INGEST_ROWS

    native_rate = INGEST_ROWS / native_s
    # fresh=True: the comparator must share THIS bench's process state
    # (see the note at _SHARED_RATES)
    _emit("avro_ingest_rows_per_sec", native_rate, "rows/s",
          native_rate / _py_ingest_rate(fresh=True))

    # scoring OUTPUT: the native columnar writer vs the Python record
    # encoder (the reference's ScoringResultAvro write path)
    from photon_ml_tpu import native
    from photon_ml_tpu.io.avro import write_avro_file
    from photon_ml_tpu.io.schemas import SCORING_RESULT_AVRO

    if native.available():
        rng = np.random.default_rng(1)
        n_w = 400_000
        scores = rng.normal(size=n_w)
        labels = (rng.uniform(size=n_w) < 0.5).astype(np.float64)
        with tempfile.TemporaryDirectory() as tmp:
            t0 = time.perf_counter()
            ok = native.write_scoring_results(
                os.path.join(tmp, "s.avro"), scores, labels)
            nat_w = n_w / (time.perf_counter() - t0)
            if not ok:
                raise RuntimeError("native scoring write failed")
            n_py = 40_000
            recs = ({"uid": str(i), "predictionScore": float(scores[i]),
                     "label": float(labels[i]), "metadataMap": None}
                    for i in range(n_py))
            t0 = time.perf_counter()
            # codec null on BOTH sides: the ratio measures the encoders,
            # not zlib (the native writer emits uncompressed containers)
            write_avro_file(os.path.join(tmp, "p.avro"), recs,
                            SCORING_RESULT_AVRO, codec="null")
            py_w = n_py / (time.perf_counter() - t0)
        _emit("avro_scoring_write_rows_per_sec", nat_w, "rows/s",
              nat_w / py_w)


# --------------------------------------------------------------------------
# 7. end-to-end GAME training driver (Avro in -> model written)
# --------------------------------------------------------------------------

def _write_e2e_file(path, n=E2E_ROWS, users=E2E_USERS, songs=E2E_SONGS,
                    touched_users=0):
    """Music-shaped TrainingExampleAvro: a global bag (6 of 32 features),
    an item bag (4 of 8), user+song ids, labels planted from user/song
    factors so the CD sweep has real structure to recover.  Sampling is
    vectorized per chunk (a per-record rng.choice made the 1M-row prep
    dominate cold bench runs) and the codec is null — the e2e metric
    measures the pipeline, not zlib (the ingest bench keeps deflate).

    ``touched_users`` perturbs the item-bag values on rows of the FIRST k
    user ids (all other rows byte-identical draws) — the refresh bench's
    controlled entity-local change: exactly those users fingerprint as
    touched, everyone else carries."""
    from photon_ml_tpu.io.data_reader import write_training_examples

    rng = np.random.default_rng(99)
    d_fixed, d_item = 32, 8
    w_fixed = rng.normal(size=d_fixed)
    uu = rng.normal(size=(users, d_item))
    us = 0.7 * rng.normal(size=(songs, d_item))
    pu = 1.0 / np.arange(1, users + 1); pu /= pu.sum()
    ps = 1.0 / np.arange(1, songs + 1); ps /= ps.sum()
    user = rng.choice(users, size=n, p=pu)
    song = rng.choice(songs, size=n, p=ps)

    def records():
        chunk = 65536
        for lo in range(0, n, chunk):
            m = min(chunk, n - lo)
            # choice-without-replacement via argsort of uniforms, whole
            # chunk at once
            fi = rng.random((m, d_fixed)).argsort(axis=1)[:, :6]
            fv = rng.normal(size=(m, 6))
            ii = rng.random((m, d_item)).argsort(axis=1)[:, :4]
            iv = rng.normal(size=(m, 4))
            u, s = user[lo:lo + m], song[lo:lo + m]
            if touched_users:
                iv = np.where((u < touched_users)[:, None], iv * 1.05, iv)
            margin = ((np.take_along_axis(
                np.broadcast_to(w_fixed, (m, d_fixed)), fi, 1) * fv).sum(1)
                / np.sqrt(6)
                + (np.take_along_axis(uu[u], ii, 1) * iv).sum(1)
                + (np.take_along_axis(us[s], ii, 1) * iv).sum(1))
            label = rng.uniform(size=m) < 1.0 / (1.0 + np.exp(-margin))
            for j in range(m):
                feats = ([{"name": f"g.x{k}", "term": "", "value": float(v)}
                          for k, v in zip(fi[j], fv[j])]
                         + [{"name": f"it.x{k}", "term": "", "value": float(v)}
                            for k, v in zip(ii[j], iv[j])])
                yield {"uid": str(lo + j), "response": float(label[j]),
                       "offset": None, "weight": None, "features": feats,
                       "metadataMap": {"userId": f"u{u[j]}",
                                       "songId": f"s{s[j]}"}}

    write_training_examples(path, records(), codec="null")


def bench_end_to_end():
    """The whole driver, timed from Avro open to model-on-disk — the
    reference's "Read data"→"Save models" wall (GameTrainingDriver.scala).

    Baseline composition: a reference-style host pipeline pays (at least)
    the pure-Python ingest PLUS the host CD sweep, both measured in this
    same process on this same machine at documented reduced slices
    (`_py_ingest_rate` / `_host_cd_rate`, shared with the cd-sweep and
    ingest benches); serial composition of rates is the lower bound on
    its wall (write/model-IO excluded — favors the baseline)."""
    from photon_ml_tpu.cli import train_game as train_game_cli

    train = _cached_fixture("e2e", _write_e2e_file, E2E_ROWS, E2E_USERS,
                            E2E_SONGS)
    py_ingest_rate = _py_ingest_rate()
    host_cd_rate = _host_cd_rate()
    _heartbeat()

    args = [
        "--training-data", train,
        "--feature-shards", "global=g|intercept,item=it|noIntercept",
        "--coordinates",
        "global=fixed,shard=global,reg=L2,maxIter=25",
        ("perUser=random,entity=userId,shard=item,reg=L2,maxIter=25,"
         "buckets=histogram,maxSampleBuckets=4"),
        ("perSong=random,entity=songId,shard=item,reg=L2,maxIter=25,"
         "buckets=histogram,maxSampleBuckets=4"),
        "--update-sequence", "global,perUser,perSong",
        "--cd-iterations", "1",
        "--grid", "global=0.001", "perUser=1", "perSong=1",
        "--data-validation", "VALIDATE_DISABLED",
        # bfloat16 designs end to end: halves the dominant host→device
        # feed bytes and runs the solves on the MXU's native dtype
        # (recorded rel-err ~3e-4 on the GLM solve, bf16-vs-f32 AUC parity
        # locked by tests/test_game.py)
        "--design-dtype", "bfloat16",
    ]
    def _residue_drain():
        # drop host/device residue before measuring: freed-but-resident
        # heap from a prior run inflates the next run's read stage 2-5x
        # (page-table pressure on the decode/assembly path — same effect
        # the suite-level drain() guards against). malloc_trim returns the
        # freed arenas to the OS; clear_caches is deliberately NOT called
        # (it would discard the warm jit state the warm run exists to
        # build).
        import ctypes
        import gc

        gc.collect()
        try:
            ctypes.CDLL("libc.so.6").malloc_trim(0)
        except OSError:
            pass

    def _stages_of(out):
        # per-stage breakdown from the driver's own metrics.jsonl (the
        # reference logs the same stage walls via Timed.scala)
        stages = {}
        metrics_path = os.path.join(out, "metrics.jsonl")
        if os.path.exists(metrics_path):
            with open(metrics_path) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        continue  # truncated line must not kill the run
                    if "stage" in rec and "seconds" in rec:
                        stages[rec["stage"]] = round(
                            stages.get(rec["stage"], 0.0) + rec["seconds"], 3)
        return stages

    with tempfile.TemporaryDirectory() as tmp:
        train_game_cli.run(args + ["--output-dir", os.path.join(tmp, "w")])
        _heartbeat()  # the warm run's cold compiles can be 15+ min silent
        # measure TWICE (warm jit both times, fresh data path each) and
        # keep the better run: single-run walls on this box swing 1.5-3x
        # with transient host residue/contention, and the cleaner of two
        # is the reproducible property of the code. Each measured run
        # carries --telemetry-dir so the winner ships a span trace: the
        # perf_report async-I/O-overlap section (and a regression gate
        # verdict, see _gate_line) can then PROVE how much of the
        # save/read wall was hidden under train, from artifacts alone.
        wall, stages, best_td, best_out = None, {}, None, None
        for i in range(2):
            _residue_drain()
            out = os.path.join(tmp, f"out{i}")
            td = os.path.join(out, "telemetry")
            t0 = time.perf_counter()
            train_game_cli.run(args + ["--output-dir", out,
                                       "--telemetry-dir", td])
            w = time.perf_counter() - t0
            _heartbeat()
            assert os.path.exists(
                os.path.join(out, "best", "model-metadata.json"))
            if wall is None or w < wall:
                wall, stages, best_td, best_out = w, _stages_of(out), td, out
        overlap = _stash_perf_report(best_td)
        quality_extras = _quality_extras(best_out, train)
    e2e_rate = E2E_ROWS / wall
    base_rate = 1.0 / (1.0 / py_ingest_rate + 1.0 / host_cd_rate)
    extra = {}
    if overlap:
        for cls in ("save", "read"):
            if cls in overlap:
                extra[f"{cls}_io_s"] = round(overlap[cls]["seconds"], 3)
                extra[f"{cls}_hidden_pct"] = round(
                    overlap[cls]["hidden_pct"], 1)
    extra.update(quality_extras)
    # self-describing metric line: the run configuration rides as extras so
    # round-over-round artifacts are comparable without reading this source
    _emit("game_end_to_end_rows_per_sec", e2e_rate, "rows/s",
          e2e_rate / base_rate, n_rows=int(E2E_ROWS),
          n_users=int(E2E_USERS), n_songs=int(E2E_SONGS),
          design_dtype="bfloat16", codec="null", best_of=2,
          wall_s=round(wall, 2), stage_s=stages, **extra)


# --------------------------------------------------------------------------
# 8. open-loop serving latency + p99 SLO gate
# --------------------------------------------------------------------------

SERVING_ROWS = 20_000
SERVING_USERS = 500
SERVING_SONGS = 200
SERVING_REQUESTS = 400
SERVING_TARGET_QPS = 100.0
# the R=2 fleet keeps 2x the hosts resident per core, so its knee sits
# below the single-host target on the bench box; an open-loop target
# past the knee measures queue growth, not the routing machinery —
# aim the fleet workload below it
FLEET_TARGET_QPS = 80.0


def bench_serving_slo():
    """Open-loop serving bench (tools/bench_serving.py machinery): train a
    tiny GAME model, serve it in-process, fire a fixed-schedule load at
    ``SERVING_TARGET_QPS``, and report latency-CORRECTED percentiles (the
    closed-loop client's numbers hide coordinated omission — ROADMAP
    "Tail-latency push"). The metric is achieved requests/s;
    ``vs_baseline`` is the p99 SLO headroom (SLO / corrected p99, >1 =
    inside SLO), and the ``slo_verdict`` extra carries the
    ``tools/bench_gate.py`` ok/regression verdict on that headroom.
    ``PHOTON_SERVING_SLO_P99_MS`` overrides the SLO (default 250 ms —
    sized for this box's CPU-serving tail under 100 QPS, not a production
    claim)."""
    import argparse
    import tempfile

    from photon_ml_tpu.cli import serve_game as serve_game_cli
    from photon_ml_tpu.cli import train_game as train_game_cli

    bench_serving = _tools_module("bench_serving")
    slo_ms = float(os.environ.get("PHOTON_SERVING_SLO_P99_MS", 250.0))
    train = _cached_fixture("serving", _write_e2e_file, SERVING_ROWS,
                            SERVING_USERS, SERVING_SONGS)
    shards = "global=g|intercept,item=it|noIntercept"
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "model")
        train_game_cli.run([
            "--training-data", train,
            "--output-dir", out,
            "--feature-shards", shards,
            "--coordinates",
            "global=fixed,shard=global,reg=L2,maxIter=25",
            ("perUser=random,entity=userId,shard=item,reg=L2,maxIter=25,"
             "buckets=histogram,maxSampleBuckets=4"),
            "--update-sequence", "global,perUser",
            "--grid", "global=0.001", "perUser=1",
            "--data-validation", "VALIDATE_DISABLED",
            "--evaluators", "",
        ])
        _heartbeat()
        server = serve_game_cli.build_server([
            "--model-dir", out, "--feature-shards", shards,
            "--port", "0", "--max-wait-ms", "1",
        ]).start()
        try:
            from photon_ml_tpu.telemetry.saturation import (
                device_busy_seconds,
            )

            pool = bench_serving._request_pool(
                argparse.Namespace(data=None, pool=128), server)
            metrics0 = bench_serving._scrape_metrics(server.url)
            busy0, wall0 = device_busy_seconds(), time.monotonic()
            run = bench_serving.open_loop_run(
                server.url, pool, [1, 1, 1, 2, 4],
                target_qps=SERVING_TARGET_QPS, requests=SERVING_REQUESTS,
                concurrency=16)
            busy1, wall1 = device_busy_seconds(), time.monotonic()
            conn_peak = server.service.connections.stats()["peak"]
            metrics1 = bench_serving._scrape_metrics(server.url)
        finally:
            server.stop()
    corrected_p99 = bench_serving._percentile(run["corrected_ms"], 99)
    verdict = bench_serving.slo_gate_verdict(corrected_p99, slo_ms)
    extras = {
        "corrected_p50_ms": round(
            bench_serving._percentile(run["corrected_ms"], 50), 3),
        "corrected_p99_ms": round(corrected_p99, 3),
        "uncorrected_p99_ms": round(
            bench_serving._percentile(run["uncorrected_ms"], 99), 3),
        "target_qps": SERVING_TARGET_QPS,
        "slo_p99_ms": slo_ms,
        "slo_verdict": verdict["verdict"],
        "n_errors": len(run["errors"]),
        # capacity-plane extras: device duty over the load window (the
        # USE sampler's utilization source) and the connection high
        # watermark — how close the box ran to its socket budget
        "duty_cycle": round((busy1 - busy0)
                            / max(wall1 - wall0, 1e-9), 4),
        "conn_peak": conn_peak,
    }
    if metrics1 is not None:
        stages = bench_serving.stage_breakdown(metrics0, metrics1)
        if stages:
            extras["stage_ms"] = {k: v["p50_ms"] for k, v in stages.items()}
    _emit("serving_open_loop_qps", run["achieved_qps"],
          "req/s (open loop, latency-corrected percentiles)",
          verdict["headroom"], **extras)


def bench_serving_fleet():
    """Open-loop fleet serving bench (the ISSUE 15 workload, grown by
    ISSUE 16): the same tiny GAME model served from two entity-sharded
    shards at TWO replicas each behind the fleet router
    (``cli/serve_fleet.py``), open-loop /score load through the router,
    then one live reshard epoch driven after the timed window. The
    metric is achieved
    requests/s; ``vs_baseline`` is the p99 SLO headroom
    (``PHOTON_FLEET_SLO_P99_MS``, default 250 ms — one extra local HTTP
    hop vs the single-host SLO). This is the number BENCH_r06 sizes the
    fleet against: compare with ``serving_open_loop_qps`` to read the
    router tax, the per-host entity counts in the extras to read the
    table-byte split, and ``hedge_rate``/``reshard_epochs`` to read the
    elasticity machinery's footprint under load. Two retained-plane
    gates ride along: the history-sampler overhead window pair
    (open-loop p99 with 20 Hz sampling on vs off must stay bounded) and
    ``advisor_detect_ticks`` (a synthetic 10x-skewed shard must latch in
    exactly the hysteresis sustain window)."""
    import argparse
    import tempfile

    from photon_ml_tpu.cli import serve_fleet as serve_fleet_cli
    from photon_ml_tpu.cli import train_game as train_game_cli

    bench_serving = _tools_module("bench_serving")
    slo_ms = float(os.environ.get("PHOTON_FLEET_SLO_P99_MS", 250.0))
    train = _cached_fixture("serving", _write_e2e_file, SERVING_ROWS,
                            SERVING_USERS, SERVING_SONGS)
    shards = "global=g|intercept,item=it|noIntercept"
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "model")
        train_game_cli.run([
            "--training-data", train,
            "--output-dir", out,
            "--feature-shards", shards,
            "--coordinates",
            "global=fixed,shard=global,reg=L2,maxIter=25",
            ("perUser=random,entity=userId,shard=item,reg=L2,maxIter=25,"
             "buckets=histogram,maxSampleBuckets=4"),
            "--update-sequence", "global,perUser",
            "--grid", "global=0.001", "perUser=1",
            "--data-validation", "VALIDATE_DISABLED",
            "--evaluators", "",
        ])
        _heartbeat()
        fleet = serve_fleet_cli.build_fleet([
            "--model-dir", out, "--feature-shards", shards,
            "--port", "0", "--max-wait-ms", "1", "--fleet-shards", "2",
            "--replicas", "2",
        ])
        reshard_box = {}

        def _fire_reshard():
            # one live shard-map epoch: move eight buckets that actually
            # hold shard-0 rows, so reshard_epochs and the moved-row
            # counters record the two-phase machinery doing real repack
            # work. Runs AFTER the timed window — the prepare warmup's
            # compile sweep is off the serving path by design, but with
            # four hosts in one process it starves the box's cores and
            # would pollute the qps number (reshard UNDER traffic is the
            # chaos harness's claim, tools/chaos_serving.py --fleet)
            from photon_ml_tpu.fleet.sharding import bucket_of_id
            try:
                smap = fleet.router.shard_map
                donors = sorted({
                    bucket_of_id(str(i))
                    for h in fleet.hosts
                    for store in h.service.registry.active().stores.values()
                    for i in store.row_of_id
                    if smap.shard_of(str(i)) == 0})[:8]
                reshard_box["out"] = bench_serving._http_json(
                    fleet.url + "/reshard",
                    {"moves": {str(b): 1 for b in donors}})
            except Exception as e:
                reshard_box["error"] = repr(e)

        try:
            pool = bench_serving.fleet_request_pool(
                argparse.Namespace(data=None, pool=128), fleet)
            from photon_ml_tpu.telemetry.saturation import (
                device_busy_seconds,
            )

            compiles0 = [bench_serving._http_json(u + "/healthz")["compiles"]
                         for u in fleet.host_urls()]
            folded0 = bench_serving._scrape_metrics(fleet.url)
            metrics0 = bench_serving._scrape_process_metrics()
            busy0, wall0 = device_busy_seconds(), time.monotonic()
            run = bench_serving.open_loop_run(
                fleet.url, pool, [1, 1, 1, 2, 4],
                target_qps=FLEET_TARGET_QPS, requests=SERVING_REQUESTS,
                concurrency=16)
            busy1, wall1 = device_busy_seconds(), time.monotonic()
            # high watermark across the in-process hosts' trackers —
            # the fleet's closest approach to a per-host socket budget
            conn_peak = max(h.service.connections.stats()["peak"]
                            for h in fleet.hosts)
            compiles1 = [bench_serving._http_json(u + "/healthz")["compiles"]
                         for u in fleet.host_urls()]
            folded1 = bench_serving._scrape_metrics(fleet.url)
            proc1 = bench_serving._scrape_process_metrics()
            _fire_reshard()
            metrics1 = bench_serving._scrape_process_metrics()
            entities = [
                sum(s.n_entities
                    for s in h.service.registry.active().stores.values())
                for h in fleet.hosts]
            # retained-plane overhead: the same open-loop window with
            # every history sampler OFF, then ON at an aggressively
            # short period (20 Hz across router + 4 hosts — far past
            # the production default). Sampling is a side thread + one
            # registry render per tick, so the p99 delta it costs the
            # serving path must stay bounded (gated below).
            overhead_n = max(SERVING_REQUESTS // 2, 100)
            run_off = bench_serving.open_loop_run(
                fleet.url, pool, [1, 1, 1, 2, 4],
                target_qps=FLEET_TARGET_QPS, requests=overhead_n,
                concurrency=16)
            fleet.history.start(0.05)
            for h in fleet.hosts:
                h.history.start(0.05)
            run_on = bench_serving.open_loop_run(
                fleet.url, pool, [1, 1, 1, 2, 4],
                target_qps=FLEET_TARGET_QPS, requests=overhead_n,
                concurrency=16)
        finally:
            fleet.stop()
        _heartbeat()
    # fold parity (the fleet observability plane's accounting claim): the
    # router's folded /metrics carries every member's serving-latency
    # histogram once. The in-process hosts share the router's process
    # registry, so the fold sums the SAME histogram (1 + n_hosts) times —
    # the folded count's delta over the load window must be exactly that
    # multiple of the process-registry delta, and the process delta must
    # cover every client-served request (each served request executed on
    # >= 1 host; cross-shard records, hedges and replica retries only ADD
    # host-side observations, never remove them).
    from photon_ml_tpu.telemetry.prometheus import series_value
    lat_count = "photon_serving_request_latency_seconds_count"
    members = 1 + len(entities)  # router + every host, one shared registry
    fold_delta = int(series_value(folded1, lat_count)
                     - series_value(folded0, lat_count))
    proc_delta = int(series_value(proc1, lat_count)
                     - series_value(metrics0, lat_count))
    served = len(run["corrected_ms"]) + run["reconnected"]
    if fold_delta != members * proc_delta:
        raise AssertionError(
            f"fleet /metrics fold parity: folded {lat_count} moved "
            f"{fold_delta} over the load window, expected {members} "
            f"members (router + hosts sharing one registry) x process "
            f"delta {proc_delta} = {members * proc_delta}")
    if proc_delta < served:
        raise AssertionError(
            f"fleet /metrics fold parity: hosts observed {proc_delta} "
            f"admitted /score requests but clients tallied {served} "
            f"served — the fold is missing host observations")
    corrected_p99 = bench_serving._percentile(run["corrected_ms"], 99)
    verdict = bench_serving.slo_gate_verdict(
        corrected_p99, slo_ms,
        shed_rate=run["shed"] / max(run["offered"], 1))
    elastic = bench_serving.fleet_elastic_extras(
        metrics0, metrics1, run["offered"])
    # the sampler-overhead gate: generous (2x + 50 ms) so a noisy
    # 1-core box never flakes it, but a sampler that serializes the
    # request path behind its registry render blows straight through
    sampler_p99_off = bench_serving._percentile(run_off["corrected_ms"], 99)
    sampler_p99_on = bench_serving._percentile(run_on["corrected_ms"], 99)
    if sampler_p99_on > 2.0 * sampler_p99_off + 50.0:
        raise AssertionError(
            f"history-sampler overhead: open-loop p99 went "
            f"{sampler_p99_off:.1f} ms -> {sampler_p99_on:.1f} ms with "
            f"20 Hz sampling on — the retained plane is standing on the "
            f"serving path")
    # hot-shard advisor detection bound: a synthetic 10x-skewed shard
    # fed tick by tick must latch in EXACTLY sustain_ticks ticks —
    # detection latency is the hysteresis design, not heuristics
    from photon_ml_tpu.fleet.advisor import HotShardAdvisor

    class _SynthHistory:
        def __init__(self):
            self.snaps = []

        def snapshots(self, window=0):
            return self.snaps[-window:] if window else list(self.snaps)

    synth = _SynthHistory()
    synth_advisor = HotShardAdvisor(history=synth,
                                    shard_map_fn=lambda: None)
    advisor_detect_ticks = 0
    for t in range(1, 2 * synth_advisor.sustain_ticks + 2):
        synth.snaps.append({"tick": t, "ts": float(t), "series": {
            "shard_p99": {"0": 0.050, "1": 0.005},
            "shard_load": {"0": 6.0, "1": 1.0}}})
        if synth_advisor.tick():
            advisor_detect_ticks = t
            break
    if advisor_detect_ticks != synth_advisor.sustain_ticks:
        raise AssertionError(
            f"hot-shard advisor latched a sustained 10x skew in "
            f"{advisor_detect_ticks} tick(s), want exactly "
            f"{synth_advisor.sustain_ticks} (the sustain window)")
    _emit("serving_fleet_qps", run["achieved_qps"],
          "req/s (open loop /score through the fleet router, 2 local "
          "entity-sharded shards x 2 replicas with hedged fan-out, "
          "latency-corrected percentiles; one live reshard epoch driven "
          "after the window, footprint in the extras)",
          verdict["headroom"],
          corrected_p50_ms=round(
              bench_serving._percentile(run["corrected_ms"], 50), 3),
          corrected_p99_ms=round(corrected_p99, 3),
          target_qps=FLEET_TARGET_QPS,
          n_shards=2,
          replicas=2,
          hedge_rate=elastic["hedge_rate"],
          replica_retries=elastic["replica_retries"],
          reshard_epochs=elastic["reshard_epochs"],
          reshard_moved=(reshard_box.get("out") or {}).get("moved"),
          reshard_error=reshard_box.get("error"),
          entities_per_host=entities,
          recompiles_during_load=[c1 - c0 for c0, c1
                                  in zip(compiles0, compiles1)],
          n_shed=run["shed"], n_errors=len(run["errors"]),
          n_reconnected=run["reconnected"],
          fold_members=members, fold_count_delta=fold_delta,
          host_observations=proc_delta,
          history_p99_off_ms=round(sampler_p99_off, 3),
          history_p99_on_ms=round(sampler_p99_on, 3),
          advisor_detect_ticks=advisor_detect_ticks,
          duty_cycle=round((busy1 - busy0)
                           / max(wall1 - wall0, 1e-9), 4),
          conn_peak=conn_peak,
          slo_p99_ms=slo_ms, slo_verdict=verdict["verdict"])


RANKED_KS = (1, 10, 64)


def bench_serving_ranked():
    """Open-loop ranked-retrieval bench (the `/rank` workload, ISSUE 14):
    train the serving model, serve it with `--rank-item-coordinate`, fire
    a fixed-schedule GET /rank load cycling a k sweep, and report
    latency-corrected percentiles + shed classification. The metric is
    achieved ranked requests/s; ``vs_baseline`` is the p99 SLO headroom
    (``PHOTON_RANK_SLO_P99_MS``, default 250 ms). This is the number
    BENCH_r06 sizes the item-axis sharding claim against: the extras
    carry the item count so rate-per-item is derivable round over
    round."""
    import argparse
    import tempfile

    from photon_ml_tpu.cli import serve_game as serve_game_cli
    from photon_ml_tpu.cli import train_game as train_game_cli

    bench_serving = _tools_module("bench_serving")
    slo_ms = float(os.environ.get("PHOTON_RANK_SLO_P99_MS", 250.0))
    train = _cached_fixture("serving", _write_e2e_file, SERVING_ROWS,
                            SERVING_USERS, SERVING_SONGS)
    shards = "global=g|intercept,item=it|noIntercept"
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "model")
        train_game_cli.run([
            "--training-data", train,
            "--output-dir", out,
            "--feature-shards", shards,
            "--coordinates",
            "global=fixed,shard=global,reg=L2,maxIter=25",
            ("perUser=random,entity=userId,shard=item,reg=L2,maxIter=25,"
             "buckets=histogram,maxSampleBuckets=4"),
            "--update-sequence", "global,perUser",
            "--grid", "global=0.001", "perUser=1",
            "--data-validation", "VALIDATE_DISABLED",
            "--evaluators", "",
        ])
        _heartbeat()
        server = serve_game_cli.build_server([
            "--model-dir", out, "--feature-shards", shards,
            "--port", "0", "--max-wait-ms", "1",
            "--rank-item-coordinate", "perUser", "--rank-max-k", "64",
        ]).start()
        try:
            pool = bench_serving._request_pool(
                argparse.Namespace(data=None, pool=128), server)
            users = bench_serving._rank_users(server, pool)
            health0 = bench_serving._http_json(
                server.url + "/healthz")
            run = bench_serving.mixed_open_loop_run(
                server.url, pool, users, [1],
                target_qps=SERVING_TARGET_QPS, requests=SERVING_REQUESTS,
                ks=RANKED_KS, rank_every=1)
            health1 = bench_serving._http_json(server.url + "/healthz")
        finally:
            server.stop()
        _heartbeat()
    book = run["rank"]
    corrected_p99 = bench_serving._percentile(book["corrected_ms"], 99)
    verdict = bench_serving.slo_gate_verdict(
        corrected_p99, slo_ms,
        shed_rate=book["shed"] / max(book["offered"], 1))
    achieved = (len(book["corrected_ms"]) / run["wall_s"]
                if run["wall_s"] > 0 else 0.0)
    _emit("serving_ranked_qps", achieved,
          "ranked req/s (open loop GET /rank, latency-corrected "
          "percentiles)", verdict["headroom"],
          corrected_p50_ms=round(
              bench_serving._percentile(book["corrected_ms"], 50), 3),
          corrected_p99_ms=round(corrected_p99, 3),
          target_qps=SERVING_TARGET_QPS,
          ks=list(RANKED_KS),
          rank_items=health1["rank"]["items"],
          rank_compiles_during_load=(health1["rank"]["compiles"]
                                     - health0["rank"]["compiles"]),
          n_shed=book["shed"], n_errors=len(book["errors"]),
          slo_p99_ms=slo_ms, slo_verdict=verdict["verdict"])


REFRESH_ROWS = 200_000
REFRESH_USERS = 4_000
REFRESH_SONGS = 2_000


def bench_refresh():
    """Incremental continuous-training refresh (cli/refresh_game.py) at
    1% / 10% / 100% touched-entity fractions: train a base model once,
    then refresh it against datasets where exactly that fraction of users'
    rows changed. The metric is re-solved entities per second of refresh
    wall; ``vs_baseline`` is the speedup of the incremental run's
    per-entity rate over the 100%-touched (full-refit-cost) run's — the
    O(touched) vs O(all entities) claim, measured."""
    from photon_ml_tpu.cli import refresh_game as refresh_game_cli
    from photon_ml_tpu.cli import train_game as train_game_cli

    base = _cached_fixture("refresh-base", _write_e2e_file, REFRESH_ROWS,
                           REFRESH_USERS, REFRESH_SONGS)
    shards = "global=g|intercept,item=it|noIntercept"
    coords = [
        "global=fixed,shard=global,reg=L2,maxIter=25",
        ("perUser=random,entity=userId,shard=item,reg=L2,maxIter=25,"
         "buckets=histogram,maxSampleBuckets=4"),
    ]
    common = [
        "--feature-shards", shards,
        "--coordinates", *coords,
        "--update-sequence", "global,perUser",
        "--grid", "global=0.001", "perUser=1",
        "--data-validation", "VALIDATE_DISABLED",
        "--evaluators", "",
    ]
    _heartbeat()
    with tempfile.TemporaryDirectory() as tmp:
        prior = os.path.join(tmp, "base")
        train_game_cli.run(["--training-data", base,
                            "--output-dir", prior] + common)
        _heartbeat()
        runs = []
        for frac in (0.01, 0.10, 1.00):
            touched = max(1, int(REFRESH_USERS * frac))
            data = _cached_fixture(
                f"refresh-t{int(frac * 100)}", _write_e2e_file,
                REFRESH_ROWS, REFRESH_USERS, REFRESH_SONGS, touched)
            out = os.path.join(tmp, f"refresh-{int(frac * 100)}")
            t0 = time.perf_counter()
            res = refresh_game_cli.run(
                ["--prior-dir", prior, "--training-data", data,
                 "--output-dir", out] + common)
            wall = time.perf_counter() - t0
            _heartbeat()
            runs.append((frac, res, wall))
        # baseline = the 100%-touched run's per-entity rate (full refit
        # cost through the identical code path)
        frac100, res100, wall100 = runs[-1]
        base_rate = max(sum(res100["solved"].values()), 1) / wall100
        for frac, res, wall in runs:
            solved = sum(res["solved"].values())
            rate = max(solved, 1) / wall
            _emit(f"refresh_entities_per_sec_{int(frac * 100)}pct", rate,
                  "entities/s", rate / base_rate,
                  touched_fraction=frac,
                  touched_entities=sum(res["touched"].values()),
                  carried_entities=sum(res["carried"].values()),
                  solved_entities=solved, wall_s=round(wall, 2),
                  n_rows=int(REFRESH_ROWS), n_users=int(REFRESH_USERS))


FRESH_ROWS = 50_000
FRESH_USERS = 1_000
FRESH_SONGS = 500
FRESH_ROWS_PER_USER = 16


def bench_freshness():
    """End-to-end freshness lag of the closed loop (CONTINUOUS.md "The
    closed loop") at 1% / 10% touched-user fractions: log labeled traffic
    for exactly that fraction of users, join it
    (``feedback.join_feedback``), refresh with ``--fleet-shards 2``
    (touched-entity solve, everyone else carried), and activate each
    per-shard patch on a fleet-sharded serving registry. The metric is
    the wall from the NEWEST logged request to BOTH shards serving the
    refreshed lineage — the ``photon_freshness_lag_seconds`` number the
    autopilot gauges, measured through the identical code path without
    the drift-event trigger. ``vs_baseline`` on the 1% line is the 10%
    run's lag over the 1% run's (how sublinearly lag scales with touched
    traffic — the O(touched) claim at loop scope)."""
    from photon_ml_tpu.cli import train_game as train_game_cli
    from photon_ml_tpu.cli import refresh_game as refresh_game_cli
    from photon_ml_tpu.cli.config import parse_feature_shard_config
    from photon_ml_tpu.feedback import join_feedback
    from photon_ml_tpu.serving import ModelRegistry, RequestLog

    base = _cached_fixture("fresh-base", _write_e2e_file, FRESH_ROWS,
                           FRESH_USERS, FRESH_SONGS)
    shards = "global=g|intercept,item=it|noIntercept"
    coords = [
        "global=fixed,shard=global,reg=L2,maxIter=25",
        ("perUser=random,entity=userId,shard=item,reg=L2,maxIter=25,"
         "buckets=histogram,maxSampleBuckets=4"),
    ]
    common = [
        "--feature-shards", shards,
        "--coordinates", *coords,
        "--update-sequence", "global,perUser",
        "--grid", "global=0.001", "perUser=1",
        "--data-validation", "VALIDATE_DISABLED",
        "--evaluators", "",
    ]
    shard_configs = tuple(parse_feature_shard_config(s)
                          for s in shards.split(","))
    rng = np.random.default_rng(17)

    def log_traffic(log_dir, touched):
        """Labeled score traffic for the first ``touched`` user ids —
        the log the joiner turns back into training data."""
        rl = RequestLog(log_dir, sample_rate=1.0, segment_records=64)
        try:
            for u in range(touched):
                records = []
                for _ in range(FRESH_ROWS_PER_USER):
                    s = int(rng.integers(FRESH_SONGS))
                    feats = ([{"name": f"g.x{k}", "term": "",
                               "value": float(rng.normal())}
                              for k in rng.choice(32, 6, replace=False)]
                             + [{"name": f"it.x{k}", "term": "",
                                 "value": float(rng.normal())}
                                for k in rng.choice(8, 4, replace=False)])
                    records.append({
                        "features": feats, "offset": None,
                        "label": float(rng.integers(2)),
                        "metadataMap": {"userId": f"u{u}",
                                        "songId": f"s{s}"}})
                rl.log(request_id=f"fresh-u{u}", records=records,
                       scores=[0.0] * len(records), version=1,
                       lineage=None)
        finally:
            rl.close()  # durable segments before the join reads

    _heartbeat()
    with tempfile.TemporaryDirectory() as tmp:
        prior = os.path.join(tmp, "base")
        train_game_cli.run(["--training-data", base,
                            "--output-dir", prior] + common)
        _heartbeat()
        results = []
        for frac in (0.01, 0.10):
            touched = max(1, int(FRESH_USERS * frac))
            pct = int(frac * 100)
            log_dir = os.path.join(tmp, f"reqlog-{pct}")
            joined = os.path.join(tmp, f"joined-{pct}.avro")
            out = os.path.join(tmp, f"refresh-{pct}")
            # two fresh fleet-sharded registries per fraction: activation
            # cost is part of the lag, measured from a cold patch
            registries = [
                ModelRegistry(shard_configs, max_batch=64, warmup=False,
                              fleet_shard=(i, 2))
                for i in range(2)]
            for reg in registries:
                reg.load(prior)
            log_traffic(log_dir, touched)
            join = join_feedback([log_dir], None, joined)
            assert join.joined == touched * FRESH_ROWS_PER_USER, \
                f"join lost rows: {join.as_dict()}"
            res = refresh_game_cli.run(
                ["--prior-dir", prior, "--training-data", joined,
                 "--output-dir", out, "--fleet-shards", "2"] + common)
            for i, reg in enumerate(registries):
                reg.reload(os.path.join(out, f"patch-shard-{i}"))
            lag = time.time() - join.last_ts
            _heartbeat()
            solved = sum(res["solved"].values())
            results.append((frac, lag, solved, res))
        (f1, lag1, solved1, _), (f10, lag10, solved10, _) = results
        _emit("freshness_lag_s", lag1, "s", lag10 / max(lag1, 1e-9),
              touched_fraction=f1, touched_users=int(FRESH_USERS * f1),
              solved_entities=solved1,
              joined_rows=int(FRESH_USERS * f1) * FRESH_ROWS_PER_USER,
              fleet_shards=2, n_users=int(FRESH_USERS))
        _emit("freshness_lag_s_10pct", lag10, "s", 1.0,
              touched_fraction=f10, touched_users=int(FRESH_USERS * f10),
              solved_entities=solved10,
              joined_rows=int(FRESH_USERS * f10) * FRESH_ROWS_PER_USER,
              fleet_shards=2, n_users=int(FRESH_USERS))


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--only",
                   choices=["glm", "re", "re_sweep", "cd", "ingest", "e2e",
                            "refresh", "freshness", "serving", "ranked",
                            "fleet"],
                   help="run a single benchmark instead of the full suite")
    args = p.parse_args(argv)
    from photon_ml_tpu import compile_cache

    compile_cache.configure()
    # a harness timeout delivers SIGTERM, whose default disposition kills
    # the process without running finally blocks — convert it to SystemExit
    # so the summary still prints (the round-2 rc=124 artifact would have
    # been complete with this)
    import signal

    def _sigterm(signum, frame):
        raise SystemExit(124)

    signal.signal(signal.SIGTERM, _sigterm)
    if args.only != "ingest":
        # the ingest/write bench is host-only (native Avro codecs, no
        # device leg) — the one mode that runs without a chip
        _probe_device()
    _start_stall_watchdog()
    _GATE_DEFAULT[0] = not args.only
    if args.only:
        try:
            {"glm": bench_glm, "re": bench_random_effect,
             "re_sweep": bench_re_sweep, "cd": bench_cd_sweep,
             "ingest": bench_ingest, "e2e": bench_end_to_end,
             "refresh": bench_refresh,
             "freshness": bench_freshness,
             "serving": bench_serving_slo,
             "ranked": bench_serving_ranked,
             "fleet": bench_serving_fleet}[args.only]()
        finally:
            _emit_summary()
        return
    # Order = protecting the headline: the e2e metric runs FIRST, in the
    # cleanest process state — residue from earlier benches (10M-row CD
    # fixtures, host scipy baselines) measured 2-6x inflation on its
    # host-bound read stage. It measures its own baseline components at
    # the documented reduced slices (the standalone path). The
    # random-effect bench (slowest, long-stable) stays last so a harness
    # timeout costs the least-new information.
    def drain():
        # drop the previous bench's device buffers/compiled executables and
        # host garbage BEFORE the next one: the native bucket packer's
        # latency-bound walk measured 6 s in a lean process but 19-60 s
        # with earlier benches' multi-GB residue still resident (page-table
        # pressure on the random row gather) — the cleanup keeps each
        # bench's number a property of the bench, not of suite order
        import gc

        import jax

        jax.clear_caches()
        gc.collect()

    # the summary is emitted from a finally so that even a partial run
    # (timeout kill arrives between benches, one bench raises) leaves a
    # terminal line with everything measured so far
    try:
        bench_end_to_end()
        drain()
        bench_glm()
        drain()
        bench_cd_sweep()
        drain()
        bench_refresh()
        drain()
        bench_freshness()
        drain()
        bench_ingest()
        drain()
        bench_serving_slo()
        drain()
        bench_serving_ranked()
        drain()
        bench_serving_fleet()
        drain()
        bench_re_sweep()
        drain()
        bench_random_effect()
    finally:
        _emit_summary()


if __name__ == "__main__":
    main()
