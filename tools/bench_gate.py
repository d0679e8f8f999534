#!/usr/bin/env python
"""Bench regression gate: compare a bench artifact against a baseline.

A bench artifact carries no machine-readable verdict of its own: a
reviewer must eyeball whether it is a genuine slowdown, ordinary noise, or
an environment outage (``rc=3`` and an ``error`` key, nothing measured).
This gate turns a (current, baseline) pair into ONE JSON line with a
verdict the trajectory can finally be read by:

- ``infra-failure`` — the current artifact measured nothing trustworthy:
  non-zero ``rc``, an ``error`` key in the suite summary (the shape
  ``bench.py`` emits for device-unreachable / mid-suite stalls), or an
  empty metric set. Exit code 2: the RUN failed, not the code — rerun,
  don't revert.
- ``missing-baseline`` — no baseline to compare against (absent file, or
  a baseline that itself infra-failed). Exit code 0: the current artifact
  simply becomes the next baseline.
- ``regression`` — at least one metric fell below
  ``baseline * (1 - threshold)``, or a metric in the baseline vanished
  from a clean current run (silent coverage loss reads as "fine" exactly
  when it is not). Exit code 1.
- ``ok`` — everything within the noise threshold. Exit code 0.

All bench metrics are rates (higher is better); the default threshold of
0.30 sits above the single-run wall swing documented in ``bench.py``
(host-bound stages swing 1.5-3x between runs; the e2e metric already
takes best-of-2 to shave that).

Artifact shapes accepted, for both sides: the harness wrapper
(``{"rc": N, "parsed": {..suite_summary..}}`` — the BENCH_rNN.json files)
and a bare ``suite_summary`` object (the last stdout line of ``bench.py``).

**Saturation/capacity families are non-gating against old baselines.**
The capacity plane (telemetry/saturation.py, PR 20) taught ``bench.py``
to emit ``duty_cycle`` / ``conn_peak`` readings; baselines recorded
before that plane existed simply lack them. The gate iterates BASELINE
metric names, so a metric present only in the current run never gates —
but that must be a contract, not an accident: ``SATURATION_FAMILIES``
names the families, and the verdict surfaces them under
``new_nongating`` so a reviewer sees they were measured and deliberately
not compared (they become comparable once they land in a baseline).
Capacity readings attached as per-line *extras* inside a metric payload
never reach ``artifact_metrics`` at all — only ``value`` is read.

Usage::

    python tools/bench_gate.py CURRENT.json [BASELINE.json]
        [--threshold 0.30] [--per-metric name=thr ...]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Mapping, Optional, Sequence

VERDICT_OK = "ok"
VERDICT_REGRESSION = "regression"
VERDICT_INFRA = "infra-failure"
VERDICT_MISSING_BASELINE = "missing-baseline"

EXIT_CODES = {VERDICT_OK: 0, VERDICT_MISSING_BASELINE: 0,
              VERDICT_REGRESSION: 1, VERDICT_INFRA: 2}

#: capacity-plane metric-name prefixes (see module docstring): absent
#: from pre-plane baselines by construction, so their appearance in a
#: current run is reported (``new_nongating``) but never compared
SATURATION_FAMILIES = ("duty_cycle", "conn_peak",
                       "photon_resource_", "photon_connection")


def is_saturation_family(name: str) -> bool:
    """True when ``name`` belongs to a capacity-plane family."""
    return any(name.startswith(prefix) for prefix in SATURATION_FAMILIES)


def normalize_artifact(doc: Mapping) -> dict:
    """Either artifact shape → ``{"rc": int, "summary": dict}``."""
    if "parsed" in doc:
        parsed = doc.get("parsed") or {}
        return {"rc": int(doc.get("rc", 0)), "summary": dict(parsed)}
    return {"rc": 0, "summary": dict(doc)}


def load_artifact(path: str) -> Optional[dict]:
    """Artifact from disk, or None when absent/unreadable (the caller
    decides whether that means missing-baseline or infra-failure)."""
    if not os.path.exists(path):
        return None
    try:
        with open(path, encoding="utf-8") as f:
            return normalize_artifact(json.load(f))
    except (json.JSONDecodeError, OSError, TypeError, ValueError):
        return None


def artifact_metrics(art: Mapping) -> dict[str, float]:
    """{metric name: value} of a normalized artifact's suite summary.
    Pre-suite-summary artifacts (BENCH_r01/r03: the parsed tail is one
    bare metric line) degrade to that single metric rather than reading as
    an infra failure."""
    summary = art["summary"]
    out = {}
    for name, payload in (summary.get("metrics") or {}).items():
        try:
            out[name] = float(payload["value"])
        except (KeyError, TypeError, ValueError):
            continue
    if not out and summary.get("metric") not in (None, "suite_summary") \
            and "value" in summary:
        try:
            out[str(summary["metric"])] = float(summary["value"])
        except (TypeError, ValueError):
            pass
    return out


def infra_failure(art: Optional[Mapping]) -> Optional[str]:
    """The infra-failure reason, or None when the artifact is sound."""
    if art is None:
        return "artifact missing or unparseable"
    if art["rc"] != 0:
        return f"bench exited rc={art['rc']}"
    if "error" in art["summary"]:
        return str(art["summary"]["error"])
    if not artifact_metrics(art):
        return "no metrics in suite summary"
    return None


def gate(current: Optional[Mapping], baseline: Optional[Mapping],
         threshold: float = 0.30,
         per_metric: Optional[Mapping[str, float]] = None) -> dict:
    """The verdict object (``main`` prints it as one JSON line)."""
    per_metric = dict(per_metric or {})
    reason = infra_failure(current)
    if reason is not None:
        return {"verdict": VERDICT_INFRA, "error": reason,
                "rc": None if current is None else current["rc"]}
    cur = artifact_metrics(current)
    if baseline is None or infra_failure(baseline) is not None:
        return {"verdict": VERDICT_MISSING_BASELINE,
                "n_metrics": len(cur),
                "note": "no sound baseline; current artifact becomes one"}
    base = artifact_metrics(baseline)
    regressions, compared = [], 0
    for name in sorted(base):
        thr = per_metric.get(name, threshold)
        if name not in cur:
            regressions.append({"metric": name, "value": None,
                                "baseline": base[name], "ratio": 0.0,
                                "why": "metric missing from current run"})
            continue
        compared += 1
        ratio = cur[name] / base[name] if base[name] else float("inf")
        if ratio < 1.0 - thr:
            regressions.append({
                "metric": name, "value": cur[name],
                "baseline": base[name], "ratio": round(ratio, 4),
                "threshold": thr})
    verdict = VERDICT_REGRESSION if regressions else VERDICT_OK
    out = {"verdict": verdict, "compared": compared,
           "threshold": threshold, "regressions": regressions}
    improved = {n: round(cur[n] / base[n], 3) for n in sorted(base)
                if n in cur and base[n] and cur[n] / base[n] > 1.0 + threshold}
    if improved:
        out["improved"] = improved
    # Saturation/capacity families measured now but absent from an older
    # baseline: surfaced, never gated (module docstring). Other
    # current-only metrics stay silent, as before.
    new_nongating = sorted(n for n in cur
                           if n not in base and is_saturation_family(n))
    if new_nongating:
        out["new_nongating"] = new_nongating
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        description="Single-line regression verdict for a bench artifact "
                    "pair (ok / regression / infra-failure / "
                    "missing-baseline)")
    p.add_argument("current", help="current bench artifact (BENCH_rNN.json "
                                   "wrapper or bare suite_summary)")
    p.add_argument("baseline", nargs="?", default=None,
                   help="baseline artifact (omit/absent → missing-baseline)")
    p.add_argument("--threshold", type=float, default=0.30,
                   help="relative drop tolerated before a metric counts as "
                        "a regression (default 0.30 — the documented "
                        "single-run wall swing)")
    p.add_argument("--per-metric", action="append", default=[],
                   metavar="NAME=THR",
                   help="per-metric threshold override (repeatable)")
    args = p.parse_args(argv)
    per_metric = {}
    for spec in args.per_metric:
        name, _, thr = spec.partition("=")
        per_metric[name] = float(thr)
    current = load_artifact(args.current)
    baseline = load_artifact(args.baseline) if args.baseline else None
    verdict = gate(current, baseline, threshold=args.threshold,
                   per_metric=per_metric)
    print(json.dumps(verdict))
    return EXIT_CODES[verdict["verdict"]]


if __name__ == "__main__":
    sys.exit(main())
