#!/usr/bin/env python
"""Critical-path performance report for a ``--telemetry-dir`` run.

The run's artifacts already hold everything needed to answer "where did
the wall-clock go": ``trace.jsonl`` (the span tree — or
``trace.merged.jsonl`` for a multi-process run) and ``metrics.prom`` (the
registry snapshot — or ``metrics.aggregate.prom`` for the fleet fold).
This tool renders them into one deterministic text report:

- **critical path** — top-k span groups by EXCLUSIVE seconds (a span's
  own wall minus its direct children's), so a fat parent that merely
  contains the work doesn't mask the stage that performs it;
- **compile vs execute** — the profiled-jit accounting
  (``photon_compiles_total{fn}`` / ``photon_compile_seconds_total{fn}`` /
  ``photon_execute_latency_seconds{fn}`` — telemetry/profiling.py), per
  function and total, plus the process-wide XLA pipeline counters that
  catch un-wrapped jits. The execute seconds are DISPATCH seconds unless
  the wrapper blocks: they say where the host spent its time, and nothing
  about the device's throughput;
- **async I/O overlap** — how much of the ``io.save.*`` / ``io.read.*``
  span time (the background writer/prefetcher pipeline,
  ``io/pipeline.py``) lies hidden under training compute — the line that
  makes the save/ingest overlap provable from artifacts (section present
  only when the trace carries I/O spans);
- **per-coordinate table** — ``cd.step`` spans folded per coordinate with
  the optimizer-iteration counters;
- **serving request path** — the per-stage critical path of a serving
  snapshot (``photon_serving_stage_seconds{stage=...}``: parse →
  queue_wait → batch_assemble → execute → respond) with
  bucket-interpolated p50/p99 per stage plus the end-to-end
  ``photon_serving_request_latency_seconds`` summary and the request-log
  budget counters — the serving counterpart of the training critical
  path (section present only when the snapshot carries serving series);

Usage::

    python tools/perf_report.py DIR [--top K]

where DIR is the run's ``--telemetry-dir``. Merged/aggregate artifacts are
preferred automatically when present.

A second report reads a profiler trace, not a run directory::

    python tools/perf_report.py --xplane FILE.xplane.pb

- **device idle gaps by program span** — while a profiler runs, every span of
  ``telemetry/tracing.py`` is also an event on ``/host:CPU`` of the trace,
  on the device events' clock. For every gap between the first chip's
  ``XLA Ops`` inside the outermost program span, the innermost program span
  that covers the gap's midpoint, summed by span name: what the host was
  doing while the chip waited. A span's row is its own share, apart from
  its children's. ``benchmark.run --trace 1 --keep-trace FILE`` writes such
  a file; so does any run under ``--profile-dir``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Mapping, Optional, Sequence

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from photon_ml_tpu.telemetry import prometheus as tprom  # noqa: E402

#: span attributes that are record plumbing, not user attributes
_RESERVED = ("name", "span_id", "parent_id", "ts", "t0", "t1", "seconds",
             "process")


def load_spans(path: str) -> list[dict]:
    """Span records (``span_id`` non-null) from a trace file; annotations
    are dropped. Each record gets a ``process`` key (0 when absent)."""
    spans = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if rec.get("span_id") is None:
                continue
            rec.setdefault("process", 0)
            spans.append(rec)
    return spans


def _group_label(span: Mapping) -> str:
    """Aggregation key for the critical path: the span name, plus the
    coordinate attribute when present (cd.step{coordinate=global} is a
    different line of work than cd.step{coordinate=perUser})."""
    if "coordinate" in span:
        return f'{span["name"]}{{coordinate={span["coordinate"]}}}'
    return str(span["name"])


def exclusive_seconds(spans: Sequence[Mapping]) -> dict[tuple, dict]:
    """Per span-group: total, exclusive (total minus direct children) and
    call count. Spans key by (process, span_id) so merged multi-process
    traces fold correctly."""
    child_sum: dict[tuple, float] = {}
    for s in spans:
        if s.get("parent_id") is not None:
            pkey = (s["process"], s["parent_id"])
            child_sum[pkey] = child_sum.get(pkey, 0.0) + float(s["seconds"])
    groups: dict[tuple, dict] = {}
    for s in spans:
        key = (s["process"], _group_label(s))
        g = groups.setdefault(key, {"total": 0.0, "exclusive": 0.0,
                                    "calls": 0})
        own = float(s["seconds"])
        g["total"] += own
        g["exclusive"] += max(
            own - child_sum.get((s["process"], s["span_id"]), 0.0), 0.0)
        g["calls"] += 1
    return groups


def _merge_intervals(intervals: list[tuple[float, float]],
                     ) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def _overlap_seconds(lo: float, hi: float,
                     merged: list[tuple[float, float]]) -> float:
    return sum(max(0.0, min(hi, b) - max(lo, a)) for a, b in merged)


def io_overlap(spans: Sequence[Mapping]) -> Optional[dict]:
    """How much of the async I/O pipeline's wall was HIDDEN under
    training compute: per class (``save`` = ``io.save.*`` spans, ``read``
    = ``io.read.*`` spans), total span seconds and the fraction of them
    that lies inside the union of train intervals (``cd.sweep`` spans plus
    ``Train*`` stage spans), compared per process via the monotonic
    ``t0``/``t1`` readings. Nested I/O spans (``io.save.part`` under
    ``io.save.model``) count once — only spans whose direct parent is not
    itself an I/O span are summed. None when the trace has no I/O spans."""
    by_id = {(s["process"], s["span_id"]): s for s in spans}
    train: dict[int, list[tuple[float, float]]] = {}
    for s in spans:
        if (s["name"] == "cd.sweep"
                or (s.get("kind") == "stage"
                    and str(s["name"]).startswith("Train"))):
            train.setdefault(s["process"], []).append(
                (float(s["t0"]), float(s["t1"])))
    merged = {p: _merge_intervals(iv) for p, iv in train.items()}
    out = {}
    for cls in ("save", "read"):
        total = hidden = 0.0
        count = 0
        for s in spans:
            if not str(s["name"]).startswith(f"io.{cls}"):
                continue
            parent = by_id.get((s["process"], s.get("parent_id")))
            if parent is not None and str(parent["name"]).startswith("io."):
                continue  # nested I/O span: counted via its parent
            total += float(s["seconds"])
            hidden += _overlap_seconds(float(s["t0"]), float(s["t1"]),
                                       merged.get(s["process"], []))
            count += 1
        if count:
            out[cls] = {"seconds": total, "hidden_seconds": hidden,
                        "spans": count,
                        "hidden_pct": (100.0 * hidden / total
                                       if total > 0 else 0.0)}
    if not out:
        return None
    out["train_wall_s"] = sum(hi - lo for iv in merged.values()
                              for lo, hi in iv)
    return out


def _histogram_quantiles(parsed: Mapping, name: str,
                         match: Optional[Mapping[str, str]] = None,
                         ) -> Optional[dict]:
    """count/total_s/p50/p99 of one histogram series in a snapshot (the
    series whose labels contain ``match``); None when absent/empty."""
    import math

    from photon_ml_tpu.telemetry.metrics import quantile_from_buckets

    match = dict(match or {})
    pairs = []
    for labels, value in parsed.get(name + "_bucket", ()):
        if not all(labels.get(k) == v for k, v in match.items()):
            continue
        le = labels.get("le")
        pairs.append((math.inf if le == "+Inf" else float(le), int(value)))
    if not pairs:
        return None
    pairs.sort(key=lambda p: p[0])
    uppers = [u for u, _ in pairs][:-1]
    cum = [c for _, c in pairs]
    count = cum[-1]
    if count == 0:
        return None
    total = 0.0
    for labels, value in parsed.get(name + "_sum", ()):
        if all(labels.get(k) == v for k, v in match.items()):
            total = value
            break
    return {"count": int(count), "total_s": float(total),
            "p50_ms": quantile_from_buckets(uppers, cum, 0.50) * 1e3,
            "p99_ms": quantile_from_buckets(uppers, cum, 0.99) * 1e3}


def serving_request_path(parsed: Mapping) -> Optional[dict]:
    """The serving snapshot's per-stage critical path: stage histograms
    (``photon_serving_stage_seconds``), the end-to-end request histogram,
    and the request-log budget counters. None when the snapshot carries no
    serving stage series (a training-only run)."""
    stages = {}
    seen = {labels.get("stage")
            for labels, _ in parsed.get(
                "photon_serving_stage_seconds_bucket", ())}
    for stage in sorted(s for s in seen if s):
        q = _histogram_quantiles(parsed, "photon_serving_stage_seconds",
                                 {"stage": stage})
        if q is not None:
            stages[stage] = q
    if not stages:
        return None
    out = {
        "stages": stages,
        "request": _histogram_quantiles(
            parsed, "photon_serving_request_latency_seconds"),
        "reqlog": None,
    }
    reqlog = {}
    for key, series in (("records", "photon_reqlog_records_total"),
                        ("bytes", "photon_reqlog_bytes_total"),
                        ("dropped", "photon_reqlog_dropped_total")):
        samples = parsed.get(series, ())
        if samples:
            reqlog[key] = sum(v for _, v in samples)
    if reqlog:
        out["reqlog"] = {"records": reqlog.get("records", 0),
                         "bytes": reqlog.get("bytes", 0),
                         "dropped": reqlog.get("dropped", 0)}
    return out


def _labeled(parsed: Mapping, series: str, label: str) -> dict[str, float]:
    """{label value: sample value} over one series' samples."""
    out: dict[str, float] = {}
    for labels, value in parsed.get(series, ()):
        if label in labels:
            out[labels[label]] = out.get(labels[label], 0.0) + value
    return out


def _fmt_count(v: float) -> str:
    """Human scale for byte totals (deterministic, 3 significant-ish
    digits)."""
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("k", 1e3)):
        if abs(v) >= div:
            return f"{v / div:.2f}{unit}"
    return f"{v:.0f}"


def build_report(spans: Sequence[Mapping], prom_text: str,
                 top: int = 10) -> str:
    """The report text (the CLI prints it; tests golden-compare it)."""
    parsed = tprom.parse_text(prom_text)
    multi = len({s["process"] for s in spans}) > 1 if spans else False
    lines: list[str] = ["== photon performance report =="]

    roots = [s for s in spans if s.get("parent_id") is None]
    wall = sum(float(s["seconds"]) for s in roots)
    root_names = sorted({_group_label(s) for s in roots})
    lines.append(f"wall {wall:.3f} s across {len(roots)} root span(s)"
                 + (f" [{', '.join(root_names)}]" if root_names else ""))

    # --- critical path ----------------------------------------------------
    lines.append("")
    lines.append(f"-- critical path: top {top} span groups by exclusive "
                 f"seconds --")
    groups = exclusive_seconds(spans)
    header = f"{'exclusive_s':>12} {'total_s':>10} {'calls':>6}  span"
    lines.append(header)
    ranked = sorted(groups.items(),
                    key=lambda kv: (-kv[1]["exclusive"], kv[0]))
    for (process, label), g in ranked[:top]:
        tag = f" [proc {process}]" if multi else ""
        lines.append(f"{g['exclusive']:>12.3f} {g['total']:>10.3f} "
                     f"{g['calls']:>6d}  {label}{tag}")
    if not groups:
        lines.append("  (no spans)")

    # --- async I/O overlap -----------------------------------------------
    overlap = io_overlap(spans)
    if overlap is not None:
        lines.append("")
        lines.append("-- async I/O overlap (hidden under train) --")
        lines.append(f"train wall {overlap['train_wall_s']:.3f} s")
        for cls in ("save", "read"):
            if cls in overlap:
                o = overlap[cls]
                lines.append(
                    f"{cls}: {o['seconds']:.3f} s across {o['spans']} "
                    f"span(s), {o['hidden_pct']:.1f}% hidden")

    # --- compile vs execute ----------------------------------------------
    lines.append("")
    lines.append("-- compile vs execute (profiled jits) --")
    compiles = _labeled(parsed, "photon_compiles_total", "fn")
    compile_s = _labeled(parsed, "photon_compile_seconds_total", "fn")
    exec_s = _labeled(parsed, "photon_execute_latency_seconds_sum", "fn")
    exec_n = _labeled(parsed, "photon_execute_latency_seconds_count", "fn")
    fns = sorted(set(compiles) | set(exec_n))
    if fns:
        lines.append(f"{'fn':<28} {'compiles':>8} {'compile_s':>10} "
                     f"{'execs':>7} {'execute_s':>10}")
        for fn in fns:
            lines.append(
                f"{fn:<28} {int(compiles.get(fn, 0)):>8d} "
                f"{compile_s.get(fn, 0.0):>10.3f} "
                f"{int(exec_n.get(fn, 0)):>7d} {exec_s.get(fn, 0.0):>10.3f}")
        tot_c, tot_e = sum(compile_s.values()), sum(exec_s.values())
        lines.append(
            f"{'TOTAL':<28} {int(sum(compiles.values())):>8d} "
            f"{tot_c:>10.3f} {int(sum(exec_n.values())):>7d} "
            f"{tot_e:>10.3f}")
        if tot_c + tot_e > 0:
            share = 100.0 * tot_c / (tot_c + tot_e)
            lines.append(f"compile share of (compile+execute): {share:.1f}%")
    else:
        lines.append("  (no profiled-jit series in snapshot)")
    xla_n = _labeled(parsed, "photon_xla_compiles_total", "phase")
    xla_s = _labeled(parsed, "photon_xla_compile_seconds_total", "phase")
    if xla_s:
        parts = ", ".join(f"{ph} {xla_s.get(ph, 0.0):.3f}s"
                          f"/{int(xla_n.get(ph, 0))}"
                          for ph in ("trace", "lower", "backend")
                          if ph in xla_s or ph in xla_n)
        lines.append(f"process-wide XLA pipeline (any jit): {parts}")

    # --- serving request path --------------------------------------------
    serving = serving_request_path(parsed)
    if serving is not None:
        lines.append("")
        lines.append("-- serving request path (per-stage critical path) --")
        req = serving["request"]
        if req is not None:
            lines.append(
                f"requests {req['count']}: p50 {req['p50_ms']:.3f} ms, "
                f"p99 {req['p99_ms']:.3f} ms "
                f"(photon_serving_request_latency_seconds)")
        lines.append(f"{'stage':<16} {'count':>8} {'total_s':>10} "
                     f"{'p50_ms':>9} {'p99_ms':>9}")
        for stage in ("parse", "queue_wait", "batch_assemble", "execute",
                      "respond"):
            st = serving["stages"].get(stage)
            if st is None:
                continue
            lines.append(f"{stage:<16} {st['count']:>8d} "
                         f"{st['total_s']:>10.3f} {st['p50_ms']:>9.3f} "
                         f"{st['p99_ms']:>9.3f}")
        # stages not in the canonical order still render (forward compat)
        for stage in sorted(serving["stages"]):
            if stage in ("parse", "queue_wait", "batch_assemble",
                         "execute", "respond"):
                continue
            st = serving["stages"][stage]
            lines.append(f"{stage:<16} {st['count']:>8d} "
                         f"{st['total_s']:>10.3f} {st['p50_ms']:>9.3f} "
                         f"{st['p99_ms']:>9.3f}")
        if serving["reqlog"] is not None:
            r = serving["reqlog"]
            lines.append(
                f"request log: {int(r['records'])} records / "
                f"{_fmt_count(r['bytes'])}B written, "
                f"{int(r['dropped'])} dropped")

    # --- per-coordinate table --------------------------------------------
    steps = [s for s in spans if s["name"] == "cd.step"]
    if steps:
        lines.append("")
        lines.append("-- coordinate descent: per-coordinate --")
        iters = _labeled(parsed, "photon_optimizer_iterations_total",
                         "coordinate")
        by_cid: dict[str, list] = {}
        for s in steps:
            by_cid.setdefault(str(s.get("coordinate", "?")), []).append(
                float(s["seconds"]))
        lines.append(f"{'coordinate':<16} {'steps':>6} {'total_s':>10} "
                     f"{'mean_s':>9} {'opt_iters':>10}")
        for cid in sorted(by_cid):
            ss = by_cid[cid]
            lines.append(f"{cid:<16} {len(ss):>6d} {sum(ss):>10.3f} "
                         f"{sum(ss) / len(ss):>9.3f} "
                         f"{int(iters.get(cid, 0)):>10d}")
    return "\n".join(lines) + "\n"


def resolve_inputs(run_dir: str) -> tuple[str, str]:
    """(trace path, metrics path), preferring the merged/aggregate
    artifacts of a multi-process run when present."""
    trace = os.path.join(run_dir, "trace.merged.jsonl")
    if not os.path.exists(trace):
        trace = os.path.join(run_dir, "trace.jsonl")
    prom = os.path.join(run_dir, "metrics.aggregate.prom")
    if not os.path.exists(prom):
        prom = os.path.join(run_dir, "metrics.prom")
    return trace, prom


# --- device idle gaps by program span (a profiler trace) -------------------

#: gaps at most this long are summed in one row: between two operations of
#: one program the chip is idle for microseconds, and no host span is why
MIN_GAP_NS = 1_000_000
NO_SPAN = "(no program span)"


def load_program_spans(path: str) -> list[tuple[int, int, str]]:
    """``(start_ns, end_ns, name)`` of the program's spans on the host planes
    of a trace: the events that carry a ``span_id`` (``telemetry/tracing.py``
    gives every span's ``TraceAnnotation`` one) and the benchmark's own
    ``bench.*``. JAX's and the runtime's own host events are left out."""
    import jax

    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if (e.name.startswith("bench.")
                        or any(k == "span_id" for k, _ in e.stats)):
                    spans.append((int(e.start_ns),
                                  int(e.start_ns + e.duration_ns), e.name))
    return spans


def gaps_by_span(ops: Sequence[tuple], spans: Sequence[tuple],
                 min_gap_ns: int = MIN_GAP_NS) -> dict:
    """The idle gaps of ``ops`` (device operations' intervals) inside the
    extent of ``spans``, each put under the innermost (shortest) span that
    covers its midpoint. Returns ``{"window_ns", "idle_ns", "short_ns",
    "rows": {name: [gaps, ns]}}``; ``short_ns`` is the sum of the gaps of at
    most ``min_gap_ns``, which no row holds."""
    from benchmark import trace

    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    rows: dict[str, list] = {}
    idle = short = 0
    for g0, g1 in trace.gaps(trace._clip(list(ops), lo, hi), lo, hi):
        idle += g1 - g0
        if g1 - g0 <= min_gap_ns:
            short += g1 - g0
            continue
        mid = (g0 + g1) // 2
        covering = [(e - s, n) for s, e, n in spans if s <= mid < e]
        row = rows.setdefault(min(covering)[1] if covering else NO_SPAN,
                              [0, 0])
        row[0] += 1
        row[1] += g1 - g0
    return {"window_ns": hi - lo, "idle_ns": idle, "short_ns": short,
            "rows": rows}


def build_gap_report(path: str) -> str:
    """The ``--xplane`` report text."""
    from benchmark import trace

    chips, _ = trace.load(path)
    spans = load_program_spans(path)
    if not chips or not chips[0].ops:
        raise ValueError(f"{path} holds no device operation")
    if not spans:
        raise ValueError(f"{path} holds no program span: was the program "
                         f"running under the profiler?")
    table = gaps_by_span(chips[0].ops, spans)
    window, idle = table["window_ns"], table["idle_ns"]
    lines = ["== device idle gaps by program span ==",
             f"window {window / 1e9:.3f} s (the program spans' extent), "
             f"chip {chips[0].index} idle {idle / 1e9:.3f} s "
             f"({100.0 * idle / window:.2f}%)",
             f"{'idle_s':>10} {'% of idle':>10} {'gaps':>6}  innermost span "
             f"at the gap's midpoint"]
    ranked = sorted(table["rows"].items(), key=lambda kv: (-kv[1][1], kv[0]))
    for name, (n, ns) in ranked:
        lines.append(f"{ns / 1e9:>10.4f} {100.0 * ns / max(idle, 1):>10.1f} "
                     f"{n:>6d}  {name}")
    lines.append(f"{table['short_ns'] / 1e9:>10.4f} "
                 f"{100.0 * table['short_ns'] / max(idle, 1):>10.1f} "
                 f"{'':>6}  (gaps of at most {MIN_GAP_NS / 1e6:g} ms)")
    return "\n".join(lines) + "\n"


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        description="Render a critical-path report from a --telemetry-dir "
                    "run (trace.jsonl + metrics.prom), or the device's idle "
                    "gaps by program span from a profiler trace")
    p.add_argument("run_dir", nargs="?", help="the run's --telemetry-dir")
    p.add_argument("--top", type=int, default=10,
                   help="span groups to show in the critical path")
    p.add_argument("--xplane", metavar="FILE",
                   help="a profiler trace (.xplane.pb): report the device's "
                        "idle gaps by program span instead")
    args = p.parse_args(argv)
    if args.xplane:
        try:
            sys.stdout.write(build_gap_report(args.xplane))
        except (OSError, ValueError) as e:
            print(f"perf_report: {e}", file=sys.stderr)
            return 1
        return 0
    if not args.run_dir:
        p.error("give a run directory, or --xplane FILE")
    trace_path, prom_path = resolve_inputs(args.run_dir)
    if not os.path.exists(trace_path):
        print(f"no trace file under {args.run_dir} "
              f"(expected trace.jsonl — was the run started with "
              f"--telemetry-dir?)", file=sys.stderr)
        return 1
    spans = load_spans(trace_path)
    prom_text = ""
    if os.path.exists(prom_path):
        with open(prom_path, encoding="utf-8") as f:
            prom_text = f.read()
    sys.stdout.write(build_report(spans, prom_text, top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
