"""Span tracing: nested ``span(name)`` contexts → ``trace.jsonl``.

``util/Timed.scala`` gave the reference *flat* stage timings in a log file;
a run that interleaves coordinate descent, retries, checkpointing and
validation needs the *tree*: which stage contained which step, and where
the wall-clock actually went. A span is one timed region with an id, its
enclosing span's id (tracked per-thread via ``contextvars``, so concurrent
serving requests each get their own stack), and arbitrary JSON attributes.

- unconfigured (the default), spans cost two contextvar operations, a
  ``perf_counter`` pair and one ``TraceAnnotation.is_enabled()`` — cheap
  enough to leave permanently in hot-ish paths like the coordinate-descent
  step loop;
- while a JAX profiler session runs (``jax.profiler.start_trace``; the
  benchmark's ``--trace 1``), every span also opens a
  ``jax.profiler.TraceAnnotation`` of its name, so it lies on ``/host:CPU``
  of the same ``.xplane.pb`` as the device's ``XLA Ops``, on the profiler's
  clock, and its record is kept;
- such a span's record also goes to a bounded in-memory ring
  (:func:`recorded` reads it): nothing is written at span exit for it, and
  nothing is kept there when no profiler runs;
- ``GLOBAL_TRACER.configure(path, bus=...)`` (done by the drivers'
  ``--telemetry-dir`` flag) appends one JSON line per completed span to
  ``<run_dir>/trace.jsonl`` and, when a bus is given, posts a
  ``span_finished`` event so the EventBus→metrics bridge folds span
  durations into the registry;
- ``timed()`` (:mod:`photon_ml_tpu.logging_util`) is now a thin wrapper
  over a span — stage sections appear in the trace tree for free.

Record layout (one JSON object per line)::

    {"name": ..., "span_id": 3, "parent_id": 2, "ts": <wall clock>,
     "t0": ..., "t1": ..., "seconds": ..., <attribute>: ...}

``t0``/``t1`` are ``perf_counter`` readings — monotonic and mutually
comparable within the process, so a child's interval provably nests inside
its parent's (the property the telemetry tests assert); ``ts`` is the wall
clock for humans correlating with ``photon.log``.

An attribute may be a device scalar (``sp.set(iterations=result.iterations)``).
It is held by reference: no span waits for the device. It becomes a Python
number when the record is read (:func:`recorded`) or written. A record that
holds one, and every record that completes behind it (so the file keeps its
order, a child before its parent), goes to the file and the taps as soon as
a later span completes and finds the value computed, at the latest at
:func:`flush` (which the drivers call where they already block) or
:func:`close`. At most ``PENDING_RECORDS`` wait so; one more, a value whose
program failed, or ``flush(wait=False)`` (the flight recorder's dump) writes
the record with the value ``None`` and its key under ``"unresolved"``. A span
around a dispatch times the HOST in any case (``seconds`` is how long the
call took to return), never the device: device time is the profiler's
``XLA Modules`` event of the program.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import json
import os
import sys
import threading
import time
from typing import Iterator, Optional

#: completed records the in-memory ring keeps (the oldest fall out)
RING_RECORDS = 4096

#: records that may wait for a device value before the file and the taps see
#: them (one more, and the oldest is written with its value unresolved)
PENDING_RECORDS = 256

#: the enclosing span's id on THIS thread/context (None = root)
_CURRENT: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "photon_current_span", default=None)

#: the full open-ancestor id stack on THIS thread/context — what lets a
#: span that outlives its lexical parent (async background work submitted
#: with a copied context) re-parent to the nearest ancestor still open
_STACK: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "photon_span_stack", default=())

#: the open Span OBJECTS on THIS thread/context, outermost first — what lets
#: a callee hand a value to the span of the caller that asked for the work
#: (:func:`set_on_enclosing`)
_SPANS: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "photon_open_spans", default=())

#: reserved record keys — span attributes may not shadow them
_RESERVED = frozenset(
    {"name", "span_id", "parent_id", "ts", "t0", "t1", "seconds",
     "unresolved"})


def _profiler_running() -> bool:
    """True exactly while a JAX profiler session runs. A process that has
    not imported jax (the fleet router) has none, and is not made to."""
    jax = sys.modules.get("jax")
    return jax is not None and jax.profiler.TraceAnnotation.is_enabled()


def _on_device(value) -> bool:
    return hasattr(value, "block_until_ready")


def _computed(record: dict) -> bool:
    """True when no device value of ``record`` would be waited for."""
    try:
        return all(v.is_ready() for v in record.values() if _on_device(v))
    except Exception:  # a failed program: nothing to wait for either
        return True


def _resolve(record: dict, wait: bool = True) -> dict:
    """``record`` with every device value as a Python number (or list).
    With ``wait``, waits for the device where a value is not computed yet.
    Such a value without ``wait``, and in any case one whose program failed,
    reads ``None`` and has its key listed under ``"unresolved"``."""
    out, unresolved = {}, []
    for key, value in record.items():
        if _on_device(value):
            try:
                value = (value.tolist() if wait or value.is_ready()
                         else None)
            except Exception:
                value = None
            if value is None:
                unresolved.append(key)
        out[key] = value
    if unresolved:
        out["unresolved"] = unresolved
    return out


class Span:
    """One live timed region; ``set(**attrs)`` attaches attributes any time
    before exit (e.g. a loss computed after the work the span times)."""

    __slots__ = ("name", "span_id", "parent_id", "attrs", "ts", "t0", "t1",
                 "seconds")

    def __init__(self, name: str, span_id: int, parent_id: Optional[int],
                 attrs: dict):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs = attrs
        self.seconds = 0.0

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def record(self) -> dict:
        bad = _RESERVED & self.attrs.keys()
        if bad:
            raise ValueError(f"span attributes shadow reserved keys {bad}")
        return {"name": self.name, "span_id": self.span_id,
                "parent_id": self.parent_id, "ts": self.ts,
                "t0": self.t0, "t1": self.t1,
                "seconds": self.seconds, **self.attrs}


class Tracer:
    """Span factory + in-memory ring + (optional) JSONL sink + (optional)
    EventBus bridge."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._fh = None
        self._path: Optional[str] = None
        self._bus = None
        #: ids of spans currently open anywhere in the process — consulted
        #: at span exit so an async span re-parents instead of recording an
        #: interval that leaks outside its (already closed) parent
        self._open: set[int] = set()
        #: completed-record taps (the flight recorder's span lane) —
        #: replaced wholesale on mutation so readers iterate an immutable
        #: snapshot without taking the lock on the span hot path
        self._taps: tuple = ()
        #: the last RING_RECORDS records of spans that a profiler saw,
        #: device values unresolved
        self._ring: collections.deque = collections.deque(maxlen=RING_RECORDS)
        #: records on their way to the file and the taps, oldest first: one
        #: that holds a device value not computed yet, and those behind it
        self._pending: collections.deque = collections.deque()

    @property
    def enabled(self) -> bool:
        """True when spans are being exported (a sink is configured)."""
        return self._fh is not None

    @property
    def path(self) -> Optional[str]:
        return self._path

    def configure(self, path: str, bus=None) -> "Tracer":
        """Start appending completed spans to ``path`` (parent dirs
        created). Reconfiguring closes the previous sink first."""
        self.flush()
        with self._lock:
            if self._fh is not None:
                self._fh.close()
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            self._fh = open(path, "a", encoding="utf-8")
            self._path = path
            self._bus = bus
        return self

    def close(self) -> None:
        """Write what :meth:`flush` would, then stop exporting; spans keep
        working (and keep their parentage) as no-ops."""
        self.flush()
        with self._lock:
            if self._fh is not None:
                self._fh.close()
            self._fh = None
            self._path = None
            self._bus = None

    def flush(self, wait: bool = True) -> None:
        """Hand the file and the taps every record that was held back for a
        device value. With ``wait`` each value is waited for: for call
        sites that already block on the work the spans were around. Without,
        a value not computed yet is written as unresolved: for the flight
        recorder's dump, which may be running because the device hangs."""
        self._drain(lambda record, held: True, wait)

    def _drain(self, due, wait: bool) -> None:
        """Write the pending records, oldest first, for as long as
        ``due(record, number pending)`` says so."""
        while True:
            with self._lock:
                if not self._pending or not due(self._pending[0],
                                                len(self._pending)):
                    return
                record = self._pending.popleft()
            self._write(_resolve(record, wait))

    def recorded(self) -> list[dict]:
        """A snapshot of the ring, oldest first: the last ``RING_RECORDS``
        records of spans that ran while a profiler did, device values as
        Python numbers (waits for those not computed yet)."""
        return [_resolve(r) for r in list(self._ring)]

    def add_tap(self, fn) -> "callable":
        """Call ``fn(record)`` for every completed span/annotation record
        — even when no file sink is configured (the flight recorder taps
        here so the black box fills on hosts that never write
        ``trace.jsonl``). Tap exceptions are swallowed; returns a
        removal callable."""
        with self._lock:
            self._taps = self._taps + (fn,)

        def _remove() -> None:
            with self._lock:
                self._taps = tuple(t for t in self._taps if t is not fn)
        return _remove

    @property
    def _sinking(self) -> bool:
        """True when a completed record goes to the file or a tap."""
        return self._fh is not None or bool(self._taps)

    def _keep(self, record: dict, in_ring: bool) -> None:
        """A completed record: into the ring if a profiler saw its span, and
        to the file and the taps where there are any. No wait: a record with
        a device value still being computed, and whatever completes behind
        it, is written once a later call here finds the value computed (or
        finds more than ``PENDING_RECORDS`` waiting)."""
        if in_ring:
            self._ring.append(record)
        if not self._sinking:
            return
        with self._lock:
            held = bool(self._pending) or not _computed(record)
            if held:
                self._pending.append(record)
        if held:
            self._drain(lambda head, n: n > PENDING_RECORDS
                        or _computed(head), wait=False)
        else:
            self._write(_resolve(record, wait=False))

    def _write(self, record: dict) -> None:
        for tap in self._taps:
            try:
                tap(record)
            except Exception:
                pass
        line = json.dumps(record) + "\n"
        with self._lock:
            if self._fh is not None:
                self._fh.write(line)
                self._fh.flush()

    @contextlib.contextmanager
    def _run(self, sp: Span, ancestors: tuple) -> Iterator[Span]:
        """The one body of :meth:`span` and :meth:`span_under`, which differ
        only in ``ancestors``: the ids, outermost first, among which a
        parent that closed before this span did is re-found."""
        token = _CURRENT.set(sp.span_id)
        stack_token = _STACK.set(ancestors + (sp.span_id,))
        spans_token = _SPANS.set(_SPANS.get() + (sp,))
        with self._lock:
            self._open.add(sp.span_id)
        # on the profiler's clock or not: decided once, at entry, so that a
        # span is never half in the profiler's trace
        annotation = None
        if _profiler_running():
            import jax

            # span_id marks the event as one of the program's spans (what
            # tools/perf_report.py --xplane tells them from JAX's own by)
            # and joins it to its record
            annotation = jax.profiler.TraceAnnotation(
                sp.name, span_id=sp.span_id,
                **{k: v for k, v in sp.attrs.items()
                   if isinstance(v, (int, float, str))})
            annotation.__enter__()
        sp.ts = time.time()
        sp.t0 = time.perf_counter()
        try:
            yield sp
        finally:
            # leave the open set BEFORE stamping t1: a concurrent child
            # that still observes this span open is then guaranteed to
            # stamp its own t1 first, so the enclosure check below can
            # never race a parent mid-close
            with self._lock:
                self._open.discard(sp.span_id)
            sp.t1 = time.perf_counter()
            sp.seconds = sp.t1 - sp.t0
            if annotation is not None:
                annotation.__exit__(None, None, None)
            _CURRENT.reset(token)
            _STACK.reset(stack_token)
            _SPANS.reset(spans_token)
            with self._lock:
                if (sp.parent_id is not None
                        and sp.parent_id not in self._open):
                    # the span outlived its parent (background writers
                    # inherit the submitting stage's context but may finish
                    # after the stage closes; a fan-out leg may outlive its
                    # request): re-parent to the nearest ancestor still
                    # open, or to root, so every recorded interval provably
                    # nests inside its parent's — the trace.jsonl enclosure
                    # contract
                    sp.parent_id = next(
                        (a for a in reversed(ancestors) if a in self._open),
                        None)
            if annotation is not None or self._sinking:
                self._keep(sp.record(), in_ring=annotation is not None)
            bus = self._bus
            if bus is not None:
                bus.post("span_finished", span=sp.name, span_id=sp.span_id,
                         parent_id=sp.parent_id, seconds=sp.seconds)

    def span(self, name: str, **attrs):
        return self._run(
            Span(name, next(self._ids), _CURRENT.get(), attrs), _STACK.get())

    def annotate(self, name: str, **payload) -> None:
        """Write a non-span record (e.g. an optimizer iteration table) into
        the trace file, tagged with the current span as its parent. No-op
        when unconfigured."""
        if not self._sinking:
            return
        self._write({"name": name, "span_id": None,
                     "parent_id": _CURRENT.get(), "ts": time.time(),
                     **payload})

    def span_under(self, parent_id: Optional[int], name: str, **attrs):
        """A span with an EXPLICIT parent — for work handed to a pool
        thread where the submitting request's contextvars do not follow
        (the fleet router's fan-out legs). Inside the context, nested
        ``span()`` calls parent to this span as usual; at exit, a parent
        that already closed re-parents this span to root rather than
        recording an interval that leaks outside it."""
        # the explicit parent is the only known-open ancestor here: the
        # submitting thread's deeper ancestry is not visible to this pool
        # thread, and claiming it would let re-parenting resurrect spans
        # this leg never nested inside
        return self._run(
            Span(name, next(self._ids), parent_id, attrs),
            () if parent_id is None else (parent_id,))

    def record_span(self, name: str, *, seconds: float,
                    parent_id: Optional[int] = None,
                    ts: Optional[float] = None, **attrs) -> int:
        """Materialize an EXTERNALLY timed region as a completed span —
        how the router turns a shard host's leg-summary stage seconds
        into children of its ``fleet.leg`` span. ``t0``/``t1`` are null
        (the remote perf_counter domain is not comparable to ours; the
        report tools only need ``seconds``/``parent_id``). Returns the
        new span id. No-op (id still minted) when unconfigured."""
        span_id = next(self._ids)
        if self._sinking:
            record = {"name": name, "span_id": span_id,
                      "parent_id": parent_id,
                      "ts": time.time() if ts is None else ts,
                      "t0": None, "t1": None,
                      "seconds": float(seconds), **attrs}
            bad = _RESERVED & attrs.keys()
            if bad:
                raise ValueError(
                    f"span attributes shadow reserved keys {bad}")
            self._write(record)
        return span_id

    def open_span_ids(self) -> tuple:
        """Ids of spans currently open anywhere in the process, sorted —
        what the flight recorder stamps into a dump header so a
        postmortem can name the work in flight at the moment of death."""
        with self._lock:
            return tuple(sorted(self._open))


#: process-global tracer the drivers configure; instrumented modules call
#: the module-level :func:`span` so embedders can swap sinks in one place
GLOBAL_TRACER = Tracer()


def span(name: str, **attrs):
    return GLOBAL_TRACER.span(name, **attrs)


def annotate(name: str, **payload) -> None:
    GLOBAL_TRACER.annotate(name, **payload)


def set_on_enclosing(name: str, **attrs) -> None:
    """Attach ``attrs`` to the nearest open span named ``name`` around the
    caller on this thread/context; nothing where there is none. How a solve
    hands its counts to the ``cd.step`` that asked for it without the step's
    callees returning them through every signature between. Values may be
    device scalars (held by reference, as :meth:`Span.set`'s are)."""
    for sp in reversed(_SPANS.get()):
        if sp.name == name:
            sp.set(**attrs)
            return


def current_span_id() -> Optional[int]:
    """The enclosing span's id on this thread/context (None = root) —
    capture it BEFORE handing work to a pool so :func:`span_under` can
    stitch the pool thread's spans back under the request."""
    return _CURRENT.get()


def span_under(parent_id: Optional[int], name: str, **attrs):
    return GLOBAL_TRACER.span_under(parent_id, name, **attrs)


def record_span(name: str, *, seconds: float,
                parent_id: Optional[int] = None,
                ts: Optional[float] = None, **attrs) -> int:
    return GLOBAL_TRACER.record_span(
        name, seconds=seconds, parent_id=parent_id, ts=ts, **attrs)


def enabled() -> bool:
    return GLOBAL_TRACER.enabled


def configure(path: str, bus=None) -> Tracer:
    return GLOBAL_TRACER.configure(path, bus=bus)


def close() -> None:
    GLOBAL_TRACER.close()


def flush(wait: bool = True) -> None:
    GLOBAL_TRACER.flush(wait)


def recorded() -> list[dict]:
    return GLOBAL_TRACER.recorded()
