"""Per-function jit profiling: compile/execute accounting + program memory.

The span tree (tracing.py) answers "which STAGE took the wall-clock"; this
module answers the layer below it — for each hot jitted program, how much of
the wall went to *compilation* versus *execution*, how much memory the
compiled program needs, and whether the program keeps recompiling (the
training analog of serving's zero-recompile contract: the compile counter
must go FLAT after the first coordinate-descent sweep).

:func:`profile_jit` is the one wrapper. It replaces a ``jax.jit`` call site::

    train = profiling.profile_jit(train_fn, "game.fixed_effect")
    result = train(data, w0, lam)       # same call surface as jit

and drives the jit through JAX's AOT API instead of the opaque dispatch
cache: each distinct abstract signature (pytree structure + leaf
shape/dtype/sharding + static values) is lowered and compiled ONCE, timed
(inside a ``jit.compile`` span), and held in the wrapper's own executable
cache. Every later call with that signature dispatches the cached
executable directly. The accounting lands in the process-global metrics registry, so ``metrics.prom``
and ``GET /metrics`` expose it with zero extra plumbing:

- ``photon_compiles_total{fn}`` / ``photon_compile_seconds_total{fn}`` —
  lower+compile events and their wall seconds, per wrapped function. The
  seconds are those of the ``jit.compile{fn, lower_s, compile_s}`` span
  around the same region (one bracket, both read it): host work the chip
  waits for, so unlike a span around a dispatch its seconds are the real
  thing;
- ``photon_execute_latency_seconds{fn}`` — per-call latency histogram.
  NOTE async dispatch: jax returns before the device finishes, so by
  default this measures DISPATCH latency (the honest hot-path number —
  blocking here would serialize the coordinate-descent pipeline);
  ``block=True`` makes the timer wait for the result, for call sites that
  want device wall time;
- ``photon_peak_memory_bytes{fn}`` — ``Compiled.memory_analysis()``
  (arguments + outputs + temporaries) of the heaviest program compiled
  under the name.

Functions called UNDER A TRACE (a profiled function invoked inside another
jit, vmap or grad — e.g. the per-bucket solve inside the fused sweep
program) transparently fall back to the wrapped jit and inline: no separate
compile happens, so none is counted.

Two registry hooks complement the wrapper:

- :func:`record_compile` — for call sites that own their jit machinery
  (the serving engine counts traces from inside the traced body, where no
  wall-clock is measurable) but must share the ``photon_compiles_total``
  name family;
- :func:`install_xla_hooks` — a ``jax.monitoring`` listener folding EVERY
  XLA compile in the process (wrapped or not) into
  ``photon_xla_compiles_total{phase}`` /
  ``photon_xla_compile_seconds_total{phase}`` (phase: ``trace`` /
  ``lower`` / ``backend``), so the compile-vs-execute split in
  ``tools/perf_report.py`` never under-reports un-wrapped jits, and the
  persistent cache's answers into ``photon_xla_cache_events_total{result}``
  (``hit`` / ``miss``). Installed at this module's import. What fell before
  the process's first profiler session is kept apart besides
  (:func:`compiles_before_profile`): a traced job's set-up, read after its
  window.
"""

from __future__ import annotations

import inspect
import threading
import time
import weakref
from typing import Callable, Optional, Sequence

import jax

from photon_ml_tpu.telemetry import metrics as _metrics
from photon_ml_tpu.telemetry import tracing as _tracing
from photon_ml_tpu.telemetry.metrics import MetricsRegistry

__all__ = [
    "ProfiledFunction",
    "compiled_programs",
    "profile_jit",
    "record_compile",
    "total_compiles",
    "install_xla_hooks",
    "compiles_before_profile",
]

#: every live wrapper, so :func:`compiled_programs` can find by name the
#: executables a driver compiled somewhere below its entry point
_WRAPPERS: "weakref.WeakSet[ProfiledFunction]" = weakref.WeakSet()


def _families(registry: Optional[MetricsRegistry] = None):
    """The profiling metric families on ``registry`` (default registry when
    None) — get-or-create is idempotent, so every wrapper shares them."""
    reg = registry if registry is not None else _metrics.default_registry()
    return {
        "compiles": reg.counter(
            "photon_compiles_total",
            "XLA lower+compile events per profiled jit function (flat "
            "after warmup/first sweep = the zero-recompile contract)",
            labels=("fn",)),
        "compile_seconds": reg.counter(
            "photon_compile_seconds_total",
            "Wall seconds spent lowering+compiling, per profiled jit "
            "function", labels=("fn",)),
        "execute": reg.histogram(
            "photon_execute_latency_seconds",
            "Per-call latency of the compiled executable (dispatch-side "
            "unless the wrapper blocks; jax dispatch is async)",
            labels=("fn",)),
        "peak_memory": reg.gauge(
            "photon_peak_memory_bytes",
            "Peak program memory (arguments+outputs+temporaries) of the "
            "heaviest executable compiled under the fn label",
            labels=("fn",)),
    }


# --- global XLA compile accounting (jax.monitoring) ------------------------

#: jax.monitoring duration events → the phase label we expose
_XLA_EVENT_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}

#: jax.monitoring events of the persistent compile cache → the result label
#: and the key of :func:`compiles_before_profile` (a hit is a program loaded
#: from the cache, a miss one compiled and written there; both fall inside
#: the ``backend`` phase)
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": ("hit", "cache_hits"),
    "/jax/compilation_cache/cache_misses": ("miss", "cache_misses"),
}

_hooks_lock = threading.Lock()
_hooks_installed = False

#: what the listeners counted before the process's first profiler session
#: (:func:`compiles_before_profile`)
_before_profile = {"seconds": 0.0, "programs": 0, "cache_hits": 0,
                   "cache_misses": 0}
#: compile-pipeline regions open in the process, on any thread, and when
#: the first of them opened: a region inside another (an inner jit traced
#: while its caller is), or beside it on another thread, adds no seconds
_compiling = {"open": 0, "since": 0.0}


def install_xla_hooks() -> None:
    """Register the process-wide ``jax.monitoring`` listeners that fold
    every XLA compile (profiled or not) into
    ``photon_xla_compiles_total{phase}`` and
    ``photon_xla_compile_seconds_total{phase}``, and the persistent cache's
    answers into ``photon_xla_cache_events_total{result}``. Idempotent;
    called at this module's import, so no compile after it is missed."""
    global _hooks_installed
    with _hooks_lock:
        if _hooks_installed:
            return
        _hooks_installed = True
    compiles = _metrics.counter(
        "photon_xla_compiles_total",
        "XLA compile-pipeline events across the whole process (any jit, "
        "wrapped or not)", labels=("phase",))
    seconds = _metrics.counter(
        "photon_xla_compile_seconds_total",
        "Wall seconds in the XLA compile pipeline across the whole "
        "process (any jit, wrapped or not)", labels=("phase",))
    cache = _metrics.counter(
        "photon_xla_cache_events_total",
        "Programs the persistent compile cache served (hit) or that were "
        "compiled and written to it (miss)", labels=("result",))

    def _listener(event: str, duration: float, **_kw) -> None:
        phase = _XLA_EVENT_PHASES.get(event)
        if phase is None:
            return
        try:
            compiles.labels(phase=phase).inc()
            seconds.labels(phase=phase).inc(max(float(duration), 0.0))
        except Exception:
            pass  # a telemetry hook must never break a compile

    def _cache_listener(event: str, **_kw) -> None:
        if event not in _CACHE_EVENTS:
            return
        result, key = _CACHE_EVENTS[event]
        try:
            cache.labels(result=result).inc()
            if not _tracing.profiled():
                with _hooks_lock:
                    _before_profile[key] += 1
        except Exception:
            pass

    def _phase_entered(event: str, start: float, **_kw) -> None:
        if event not in _XLA_EVENT_PHASES:
            return
        try:
            with _hooks_lock:
                if _compiling["open"] == 0:
                    _compiling["since"] = float(start)
                _compiling["open"] += 1
        except Exception:
            pass

    def _phase_left(event: str, start: float, end: float, **_kw) -> None:
        phase = _XLA_EVENT_PHASES.get(event)
        if phase is None:
            return
        try:
            setup = not _tracing.profiled()
            with _hooks_lock:
                if _compiling["open"] > 0:
                    _compiling["open"] -= 1
                    start = _compiling["since"]
                if not setup:
                    return
                _before_profile["programs"] += phase == "backend"
                if _compiling["open"] == 0:
                    # wall clock, as JAX stamps a region's two ends
                    _before_profile["seconds"] += max(float(end - start), 0.0)
        except Exception:
            pass

    jax.monitoring.register_event_duration_secs_listener(_listener)
    jax.monitoring.register_event_listener(_cache_listener)
    jax.monitoring.register_scalar_listener(_phase_entered)
    jax.monitoring.register_event_time_span_listener(_phase_left)


def compiles_before_profile() -> dict:
    """What this process spent in XLA's compile pipeline before its first
    profiler session (``telemetry/tracing.py::profiled``), every program
    counted, wrapped or not: ``seconds`` (wall seconds in which a trace,
    lower or backend region was open on some thread: a jit traced inside
    another, or compiled beside it on another thread, adds none),
    ``programs`` (backend compiles, each a compile or a load from the
    persistent cache), ``cache_hits`` and ``cache_misses`` (the cache's own
    events). A process with no profiler session reads what it has spent so
    far. ``programs - cache_hits`` is what was compiled rather than loaded:
    the misses, and whatever the cache was not asked for."""
    with _hooks_lock:
        return dict(_before_profile)


install_xla_hooks()


def record_compile(name: str, seconds: float = 0.0,
                   registry: Optional[MetricsRegistry] = None) -> None:
    """Count one compile under ``fn=name`` for call sites that own their jit
    machinery (the serving engine increments from inside the traced body,
    where the compile wall-clock is not observable — ``seconds`` defaults
    to 0 there; the global :func:`install_xla_hooks` listener still
    captures the real backend seconds)."""
    fams = _families(registry)
    fams["compiles"].labels(fn=name).inc()
    if seconds > 0:
        fams["compile_seconds"].labels(fn=name).inc(seconds)


def total_compiles(registry: Optional[MetricsRegistry] = None) -> float:
    """Sum of ``photon_compiles_total`` across every ``fn`` label — the
    number coordinate descent stamps on each ``cd.sweep`` span so the
    flat-after-sweep-1 contract is visible in the trace."""
    reg = registry if registry is not None else _metrics.default_registry()
    fam = reg.get("photon_compiles_total")
    if fam is None:
        return 0.0
    return sum(child.value for _labels, child in fam.children())


# --- the wrapper -----------------------------------------------------------


def _leaf_key(leaf):
    """Hashable abstract key for one pytree leaf: arrays by
    (shape, dtype, sharding) — the same equivalence jit's dispatch cache
    uses — and Python scalars by type (they trace weakly typed, so the
    value does not change the program)."""
    shape = getattr(leaf, "shape", None)
    if shape is not None:
        sharding = getattr(leaf, "sharding", None)
        return (tuple(shape), str(getattr(leaf, "dtype", "?")), sharding)
    if isinstance(leaf, (bool, int, float, complex)):
        return type(leaf)
    return (type(leaf), repr(leaf))


class _Pending:
    """Placeholder cache entry while one thread compiles a signature —
    parallel warm-compiles of DIFFERENT signatures proceed concurrently,
    but two threads racing the SAME signature share one compile."""

    __slots__ = ("event", "result", "error")

    def __init__(self):
        self.event = threading.Event()
        self.result = None
        self.error = None


class ProfiledFunction:
    """A jitted function driven through the AOT API with per-signature
    compile/execute accounting (see the module docstring).

    Call surface matches the wrapped function. ``static_argnames`` mirrors
    ``jax.jit``'s (resolved positionally through the function signature,
    like jit does); static values key the executable cache by VALUE, traced
    leaves by abstract signature. Tracer arguments (calls inside another
    trace) fall back to the plain jit and inline.
    """

    def __init__(self, fn: Callable, name: str, *,
                 static_argnames: Sequence[str] = (),
                 block: bool = False,
                 registry: Optional[MetricsRegistry] = None):
        self.name = name
        self._static = tuple(static_argnames)
        self._block = block
        self._jitted = jax.jit(fn, static_argnames=self._static) \
            if self._static else jax.jit(fn)
        try:
            self._signature = inspect.signature(fn)
        except (TypeError, ValueError):
            if self._static:
                raise
            self._signature = None
        fams = _families(registry)
        self._compiles = fams["compiles"].labels(fn=name)
        self._compile_seconds = fams["compile_seconds"].labels(fn=name)
        self._execute = fams["execute"].labels(fn=name)
        self._peak_memory = fams["peak_memory"].labels(fn=name)
        self._lock = threading.Lock()
        self._cache: dict = {}
        _WRAPPERS.add(self)

    # --- introspection ----------------------------------------------------
    def executables(self) -> list:
        """The ``jax.stages.Compiled`` programs this wrapper holds — what
        actually runs, e.g. for reading ``as_text()`` to see whether a
        Pallas kernel or the XLA closed form was compiled in."""
        with self._lock:
            return [v for v in self._cache.values()
                    if not isinstance(v, _Pending)]

    @property
    def compiles(self) -> int:
        """Executables compiled by THIS wrapper so far."""
        with self._lock:
            return sum(1 for v in self._cache.values()
                       if not isinstance(v, _Pending))

    def cache_size(self) -> int:
        with self._lock:
            return len(self._cache)

    # --- internals --------------------------------------------------------
    def _split(self, args, kwargs):
        """Normalize a call to positional order and split static from
        dynamic arguments (jit's static_argnames semantics)."""
        if self._signature is None:
            return (), args, kwargs
        bound = self._signature.bind(*args, **kwargs)
        bound.apply_defaults()
        statics, dynamics = [], []
        for pname in self._signature.parameters:
            if pname not in bound.arguments:
                continue
            value = bound.arguments[pname]
            if pname in self._static:
                statics.append((pname, value))
            else:
                dynamics.append(value)
        return tuple(statics), tuple(dynamics), {}

    def _note_memory(self, compiled) -> None:
        """Raise ``photon_peak_memory_bytes`` to this program's arguments +
        outputs + temporaries, where the backend says."""
        try:
            ma = compiled.memory_analysis()
            peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes)
        except Exception:
            return
        if peak > self._peak_memory.value:
            self._peak_memory.set(peak)

    def _compile(self, key, lower_args, lower_kwargs):
        """Lower+compile ``key``'s executable, once per signature across
        threads (losers of the race wait on the winner's event)."""
        with self._lock:
            entry = self._cache.get(key)
            if entry is None:
                entry = self._cache[key] = _Pending()
                owner = True
            else:
                owner = False
        if not owner:
            if isinstance(entry, _Pending):
                entry.event.wait()
                if entry.error is not None:
                    raise entry.error
                return entry.result
            return entry
        pending = entry
        try:
            with _tracing.span("jit.compile", fn=self.name) as sp:
                t0 = time.perf_counter()
                lowered = self._jitted.lower(*lower_args, **lower_kwargs)
                t1 = time.perf_counter()
                compiled = lowered.compile()
                sp.set(lower_s=t1 - t0, compile_s=time.perf_counter() - t1)
            self._compile_seconds.inc(sp.seconds)
            self._compiles.inc()
            self._note_memory(compiled)
            with self._lock:
                self._cache[key] = compiled
            pending.result = compiled
            return compiled
        except BaseException as e:
            pending.error = e
            with self._lock:
                self._cache.pop(key, None)  # retryable: do not poison
            raise
        finally:
            pending.event.set()

    # --- the call ---------------------------------------------------------
    def __call__(self, *args, **kwargs):
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
            # called inside another trace (fused programs, vmap, grad):
            # inline through the plain jit — no separate compile exists
            return self._jitted(*args, **kwargs)
        statics, dyn_args, dyn_kwargs = self._split(args, kwargs)
        if self._signature is None:
            key = (treedef, tuple(_leaf_key(l) for l in leaves))
            lower_args, lower_kwargs = args, kwargs
        else:
            dyn_leaves, dyn_treedef = jax.tree_util.tree_flatten(
                (dyn_args, dyn_kwargs))
            key = (statics, dyn_treedef,
                   tuple(_leaf_key(l) for l in dyn_leaves))
            # jit resolves static_argnames positionally; pass the
            # normalized positional form so lowering sees what we keyed
            lower_args, lower_kwargs = self._ordered(statics, dyn_args), {}
        compiled = self._compile(key, lower_args, lower_kwargs)
        with self._execute.time():
            out = compiled(*dyn_args, **dyn_kwargs)
            if self._block:
                out = jax.block_until_ready(out)
        return out

    def _ordered(self, statics, dynamics):
        """Re-interleave statics and dynamics back into signature order for
        lowering (the compiled executable is then CALLED with the dynamics
        only — JAX's AOT contract)."""
        static_by_name = dict(statics)
        out = []
        dyn_iter = iter(dynamics)
        for pname in self._signature.parameters:
            if pname in static_by_name:
                out.append(static_by_name[pname])
            else:
                try:
                    out.append(next(dyn_iter))
                except StopIteration:
                    break
        return tuple(out)


def compiled_programs(name: str) -> list:
    """Every executable compiled so far under ``fn=name``, across wrappers
    (the per-(task, config) train functions share a name)."""
    return [c for w in list(_WRAPPERS) if w.name == name
            for c in w.executables()]


def profile_jit(fn: Callable, name: str, *,
                static_argnames: Sequence[str] = (),
                block: bool = False,
                registry: Optional[MetricsRegistry] = None,
                ) -> ProfiledFunction:
    """Wrap ``fn`` as a jitted function with compile/execute accounting
    under the ``fn=name`` label family — the drop-in replacement for
    ``jax.jit(fn)`` at the hot call sites (see the module docstring)."""
    return ProfiledFunction(fn, name, static_argnames=static_argnames,
                            block=block, registry=registry)
