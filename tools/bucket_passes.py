#!/usr/bin/env python
"""Passes a bucket of the random-effect sweep programs, read from a kept
profiler trace::

    python3 -m benchmark.run --workload <cell> ... --trace 1 --keep-trace F
    python3 tools/bucket_passes.py F

A bucket's vmapped solve is one top-level ``while`` of a program named
``jit__sweep_fused_impl`` (or ``jit__solve_bucket_impl``). One pass is one
batched value-and-gradient evaluation of the bucket: the one at ``w0`` before
the loop, one for each trip of the loop's body, and (the nested form of
``optimize/lbfgs.py::minimize_lbfgs`` under ``vmap``) one for each trip of the
line search's ``while`` inside it. An instruction of a loop's body runs once
a trip, so a loop's trips are the count that most of its body's instructions
share. Printed for every top-level loop of every traced unit that runs an
evaluation (the sweep's other loops, a look-up's blocks among them, do not):
its device time, outer trips, the line search's trips and time, passes, the
entity kernel's events (one a pass inside the loop) and time, and the
operations that take most of its time.
"""

from __future__ import annotations

import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace  # noqa: E402

PROGRAMS = re.compile(r"^jit__(sweep_fused|solve_bucket)_impl")
KERNEL = "fused_entity_value_and_grad"
#: what an evaluation runs, by the trace's names: the entity kernel, or the
#: closed form's two contractions. A top-level loop without one is no solve
#: (the blocks of ``ops/design.py::lookup``, a scatter XLA wrote as a loop)
EVALUATION = (KERNEL, "multiply_reduce_fusion")


def _instruction(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def _trips(counts: collections.Counter) -> int:
    """The count most instructions of one loop body share."""
    if not counts:
        return 0
    return collections.Counter(counts.values()).most_common(1)[0][0]


def buckets(ops: list[trace.Interval]) -> list[dict]:
    """One entry per top-level ``while`` among ``ops`` (one program's). Of
    the loops inside its body the line search is the one that takes the most
    time (the others are scatters XLA wrote as loops)."""
    out: list[dict] = []
    stack: list[tuple[int, str | None]] = []  # (end, the loop's instruction)
    current = None
    for s, e, n in sorted(ops, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        loops = [name for _, name in stack if name is not None]
        name = _instruction(n)
        loop = trace.short(n) == "while"
        if not loops and loop:
            current = {"seconds": (e - s) / 1e9, "solve": False,
                       "body": collections.Counter(), "inner": {},
                       "kernel_events": 0, "kernel_ns": 0, "events": []}
            out.append(current)
        elif loops and current is not None:
            if len(loops) == 1 and loop:
                inner = current["inner"].setdefault(
                    name, {"ns": 0, "body": collections.Counter()})
                inner["ns"] += e - s
            elif len(loops) == 1:
                current["body"][name] += 1
            elif not loop:
                current["inner"][loops[1]]["body"][name] += 1
            current["events"].append((s, e, n))
            current["solve"] |= trace.short(n) in EVALUATION
            if trace.short(n) == KERNEL:
                current["kernel_events"] += 1
                current["kernel_ns"] += e - s
        stack.append((e, name if loop else None))
    for b in out:
        own = trace.self_times(b.pop("events"))
        b["outer_trips"] = _trips(b.pop("body"))
        inner = b.pop("inner")
        search = max(inner.values(), key=lambda i: i["ns"], default=None)
        b["search_trips"] = _trips(search["body"]) if search else 0
        b["search_s"] = search["ns"] / 1e9 if search else 0.0
        b["passes"] = 1 + b["outer_trips"] + b["search_trips"]
        b["kernel_s"] = b.pop("kernel_ns") / 1e9
        b["top_ops_s"] = [[k, round(v / 1e9, 4)] for k, v in sorted(
            own.items(), key=lambda kv: -kv[1])[:8]]
    return out


def main(argv) -> int:
    chips, spans = trace.load(argv[0])
    chip = chips[0]
    units = sorted(s for s in spans if s[2] == trace.UNIT_SPAN)
    for u, (lo, hi, _) in enumerate(units):
        programs = [m for m in sorted(chip.modules)
                    if lo <= m[0] < hi and PROGRAMS.match(trace.short(m[2]))]
        for p, (ps, pe, _) in enumerate(programs):
            inside = [o for o in chip.ops if o[0] >= ps and o[1] <= pe]
            solves = [b for b in buckets(inside) if b.pop("solve")]
            for k, b in enumerate(solves):
                print(json.dumps({"unit": u, "program": p,
                                  "program_s": (pe - ps) / 1e9,
                                  "bucket": k, **b}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
