"""From a profiler trace (``.xplane.pb``) to device busy time, per-module and
per-operation device time, collective time and the longest idle gaps.

Read with nothing but JAX (``jax.profiler.ProfileData``). A device plane is
named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per executed
operation (nested: a ``while`` spans its body's operations) and its
``XLA Modules`` line one event per executed program. The benchmark's own host
spans (``jax.profiler.TraceAnnotation``, names starting ``bench.``) are on the
host planes, on the same clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
UNIT_SPAN = "bench.unit"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
_SUFFIX = re.compile(r"[.(]\d+\)?$")  # fusion.12 -> fusion, jit_run(3) -> jit_run


def short(name: str) -> str:
    """An event's name without what changes from compile to compile: an
    operation's event is named by its whole HLO line (``%fusion.12 = f32[..]
    fusion(..)``), a program's by ``jit_run(<fingerprint>)``."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))

Interval = tuple[int, int, str]  # start_ns, end_ns, name


@dataclasses.dataclass
class Chip:
    index: int
    ops: list[Interval]
    modules: list[Interval]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> tuple[list[Chip], list[Interval]]:
    """The device planes' events and the benchmark's host spans."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    chips, spans = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        lines = {line.name: line for line in plane.lines}
        if m:
            def events(name):
                line = lines.get(name)
                return [] if line is None else [
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for e in line.events]

            chips.append(Chip(int(m.group(1)), events(OPS_LINE),
                              events(MODULES_LINE)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                           e.name) for e in line.events
                          if e.name.startswith("bench.")]
    chips.sort(key=lambda c: c.index)
    return chips, spans


def _clip(events: list[Interval], lo: int, hi: int) -> list[Interval]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def union_ns(events: list[Interval]) -> int:
    """Length of the union of the intervals."""
    total, end = 0, None
    for s, e, _ in sorted(events):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(events: list[Interval], lo: int, hi: int) -> list[tuple[int, int]]:
    """The intervals of ``[lo, hi]`` that no event covers."""
    out, end = [], lo
    for s, e, _ in sorted(events):
        if s > end:
            out.append((end, s))
        end = max(end, e)
    if hi > end:
        out.append((end, hi))
    return out


def self_times(events: list[Interval]) -> dict[str, int]:
    """Per name, the time of its events that no nested event covers."""
    out: dict[str, int] = {}
    stack: list[list] = []  # [end, name, self_ns]

    def close():
        end, name, own = stack.pop()
        out[name] = out.get(name, 0) + own

    for s, e, n in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            close()
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, short(n), e - s])
    while stack:
        close()
    return out


def by_name(events: list[Interval]) -> dict[str, int]:
    out: dict[str, int] = {}
    for s, e, n in events:
        n = short(n)
        out[n] = out.get(n, 0) + (e - s)
    return out


def window_of(spans: list[Interval], chips: list[Chip]) -> tuple[int, int]:
    """The traced window: the ``bench.window`` span, else the device events'
    extent."""
    for s, e, n in spans:
        if n == WINDOW_SPAN:
            return s, e
    starts = [s for c in chips for s, _, _ in c.ops]
    ends = [e for c in chips for _, e, _ in c.ops]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def reduce(path: str, n_chips: int) -> dict:
    """What the readers and the result line take from a trace."""
    chips, spans = load(path)
    if len(chips) < n_chips:
        raise ValueError(
            f"the trace holds {len(chips)} device planes, the cell uses "
            f"{n_chips}")
    chips = chips[:n_chips]
    lo, hi = window_of(spans, chips)
    units = _clip([s for s in spans if s[2] == UNIT_SPAN], lo, hi)
    per_chip = []
    for c in chips:
        ops = _clip(c.ops, lo, hi)
        own = self_times(ops)
        per_chip.append({
            "busy_s": union_ns(ops) / 1e9,
            "modules_s": {n: v / 1e9 for n, v in
                          by_name(_clip(c.modules, lo, hi)).items()},
            "ops_self_s": {n: v / 1e9 for n, v in own.items()},
            "collective_s": sum(
                v for n, v in own.items() if COLLECTIVE.match(n)) / 1e9,
        })
    # the longest gaps of the first chip, by what the host was doing and by
    # the program that ran last before the gap
    first = chips[0]
    mods = sorted(_clip(first.modules, lo, hi))
    named: dict[str, int] = {}
    for g0, g1 in gaps(_clip(first.ops, lo, hi), lo, hi):
        mid = (g0 + g1) // 2
        where = ("inside_unit" if any(s <= mid < e for s, e, _ in units)
                 else "between_units")
        before = [n for s, e, n in mods if e <= mid]
        after = short(before[-1]) if before else "window_start"
        key = f"{where}:after:{after}"
        named[key] = named.get(key, 0) + (g1 - g0)
    n = len(per_chip)
    ops_mean: dict[str, float] = {}
    for c in per_chip:
        for name, v in c["ops_self_s"].items():
            ops_mean[name] = ops_mean.get(name, 0.0) + v / n
    top = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(c["busy_s"] for c in per_chip) / n,
        "per_chip": per_chip,
        "device_ops": top(ops_mean),
        "idle_gaps": top({k: v / 1e9 for k, v in named.items()}),
    }
