"""The quickest proof that the system still starts on the chip.

One process, no arguments, no children: ``python3 chip_smoke.py``. It drives
the GAME trainer — the system's main path — through the function
``python -m photon_ml_tpu train_game`` calls, at the full width of
``bench.py``'s end-to-end cell, and checks what comes out by the repo's own
means. Exit code 0 only when every leg passed on a TPU; then the last two
stdout lines are ``report: {...}`` (per-leg verdicts, ``native``, the
Pallas/XLA path table, kernel errors) and the result line, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``. Any
failed check raises, so there is no result line and the exit code is not 0.
Without a TPU it exits 1 at once.

Legs, in order:

1. ``native``   the C++ ingest library builds and loads on this host.
2. ``train``    ``train_game.run`` on a generated Avro file: global fixed
   effect (32-feature bag + intercept) and per-user / per-song random effects
   on the 8-feature item bag, histogram buckets, bf16 designs, 40,000 users,
   15,000 songs, 200,000 rows (the size of the last recorded chip run, r04),
   20,000 validation rows, two coordinate-descent sweeps. Checks: the model is
   written, validation AUC is within 0.01 of the same command on CPU, every
   coefficient is finite, no profiled program compiles after sweep 0, and —
   read from the compiled program text, not from a flag — the fixed effect and
   at least one bucket of each random effect run a Mosaic kernel
   (``tpu_custom_call``).
3. ``kernels``  every Pallas entry point, compiled by Mosaic, against its XLA
   closed form at float32 matmul precision: the row-blocked GLM kernels at
   200,000 x 33 (the train leg's fixed effect) and 200,000 x 1,024 (the bench
   width), the entity kernel at every bucket shape the train leg produced;
   f32 and bf16 designs.
4. ``multichip`` with four or more chips: the train leg again under
   ``--mesh data=2,entity=2``; AUC within 2e-3 and scores within 2e-2 of the
   one-chip model (see ``MULTICHIP_SCORE_TOL``), kernels compiled under
   ``shard_map``, device memory in use on all four chips while it trains.
5. ``serve``    ``serve_game.build_server`` on the one-chip model: three
   ``/score`` POSTs of different sizes and one ``/rank`` over HTTP; scores
   within ``SERVE_TOL`` of ``score_game`` on the same records (f32
   accumulation — the bit-parity contract is a CPU-x64 contract), no compile
   after warm-up.

The wall seconds in the report line are set-up information for whoever
budgets chip time, not a metric.
"""

from __future__ import annotations

import functools
import json
import math
import os
import re
import sys
import tempfile
import threading
import time
import urllib.request

TRAIN_ROWS = 200_000
VALID_ROWS = 20_000
SERVE_ROWS = 64

#: validation AUC after sweep 1 of the train leg's exact command under
#: ``JAX_PLATFORMS=cpu`` in the sandbox (PR 21; XLA closed forms, bf16
#: designs). The chip run must reach it less ``AUC_SLACK``.
CPU_REFERENCE_AUC = 0.8111
AUC_SLACK = 0.01
#: one chip against four, validation AUC (``__graft_entry__._dryrun_impl``'s
#: tolerance; measured apart by 4e-7, PR 21)
MULTICHIP_AUC_TOL = 2e-3
#: ... and scores, absolute, on margins up to ~6. That tolerance is out of
#: reach for this cell's bf16 designs: the MXU kernel rounds its coefficient
#: operand to 8 mantissa bits, so the objective resolves a coefficient to
#: 2^-9 relative and two correct runs that sum in another order land that far
#: apart. Measured (PR 21): 5.0e-3 between one chip and four, 3.3e-3 between
#: one chip and the CPU's closed form, 3.9e-3 on CPU alone between the
#: interpreted kernels and the closed form.
MULTICHIP_SCORE_TOL = 2e-2
#: kernel against closed form, max abs error over max abs reference: f32
#: designs keep f32 operands end to end; bf16 designs round the coefficient
#: (and, on the MXU, the derivative) operand to 8 mantissa bits
KERNEL_TOL = {"float32": 1e-4, "bfloat16": 1e-2}
#: HTTP score against score_game's float64 host sum, absolute, on margins
#: of magnitude ~1: f32 products and f32 accumulation over <= 33 terms
SERVE_TOL = 1e-4

SHARDS = "global=g|intercept,item=it|noIntercept"


def check(ok: bool, what: str) -> None:
    """A failed check ends the run: non-zero exit, no result line."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def train_args(train: str, valid: str, out: str) -> list[str]:
    """bench.py's e2e cell (``bench_end_to_end``), two sweeps, validated."""
    return [
        "--training-data", train, "--validation-data", valid,
        "--feature-shards", SHARDS,
        "--coordinates",
        "global=fixed,shard=global,reg=L2,maxIter=25",
        ("perUser=random,entity=userId,shard=item,reg=L2,maxIter=25,"
         "buckets=histogram,maxSampleBuckets=4"),
        ("perSong=random,entity=songId,shard=item,reg=L2,maxIter=25,"
         "buckets=histogram,maxSampleBuckets=4"),
        "--update-sequence", "global,perUser,perSong",
        "--cd-iterations", "2",
        "--grid", "global=0.001", "perUser=1", "perSong=1",
        "--data-validation", "VALIDATE_DISABLED",
        "--design-dtype", "bfloat16",
        "--evaluators", "AUC",
        "--output-dir", out,
        "--telemetry-dir", os.path.join(out, "telemetry"),
    ]


# --- which program ran: read from the compiled text ------------------------

_MOSAIC_OPERAND = re.compile(
    r'custom_call_target="tpu_custom_call".*?'
    r"operand_layout_constraints=\{\w+\[([\d,]+)\]")


def mosaic_design_shapes(compiled) -> set[tuple[int, ...]]:
    """Shape of the design operand (the first) of every Mosaic kernel in a
    compiled program."""
    return {tuple(int(n) for n in m.group(1).split(","))
            for m in _MOSAIC_OPERAND.finditer(compiled.as_text())}


def path_table(out: str, seen: dict) -> tuple[dict, list]:
    """``{coordinate: "pallas"|"xla"}`` for the fixed effect and
    ``{coordinate: {"<dtype>[S,D]": ...}}`` per random-effect bucket, for the
    programs compiled since ``seen`` (name -> count) was last updated; and
    every bucket's ``(entities, samples, features)``."""
    from photon_ml_tpu.telemetry import profiling

    def new_programs(name):
        programs = profiling.compiled_programs(name)
        fresh = programs[seen.get(name, 0):]
        seen[name] = len(programs)
        return fresh

    fixed = new_programs("game.fixed_effect") \
        + new_programs("game.fixed_effect.dist")
    check(len(fixed) == 1, f"expected one fixed-effect program, got "
                           f"{len(fixed)}")
    table = {"global": "pallas" if any(
        len(s) == 2 for s in mosaic_design_shapes(fixed[0])) else "xla"}

    with open(os.path.join(out, "data-manifest.json")) as f:
        entities = {cid: len(c["entities"])
                    for cid, c in json.load(f)["coordinates"].items()}
    buckets = []
    for compiled in new_programs("game.re.sweep_fused"):
        # the sweep's third argument is one (x, labels, weights, gather,
        # scatter) tuple per bucket; its lanes add up to the coordinate's
        # entities (plus mesh padding), which names the coordinate
        (args, _kwargs) = compiled.args_info
        designs = [bucket[0] for bucket in args[2]]
        lanes = sum(x.shape[0] for x in designs)
        cid = min(entities, key=lambda c: abs(entities[c] - lanes))
        check(cid not in table, f"two sweep programs matched {cid}")
        # the entity kernel takes a bucket entities-last, (D, S', E'), S'
        # the rows padded to the design's sublane tile
        # (ops/pallas_re.py::entity_layout); a coordinate's buckets differ
        # in S by far more than a tile
        kernels = {s[:2] for s in mosaic_design_shapes(compiled)
                   if len(s) == 3}

        def laid(x):
            tile = 32 // x.dtype.itemsize
            return x.shape[2], -(-x.shape[1] // tile) * tile

        table[cid] = {
            f"{x.dtype.name}[{x.shape[1]},{x.shape[2]}]":
                "pallas" if laid(x) in kernels else "xla"
            for x in designs}
        buckets += [tuple(x.shape) for x in designs]
    return table, buckets


def check_paths(table: dict) -> None:
    check(table["global"] == "pallas",
          "the fixed effect compiled to the XLA closed form, not the "
          "Pallas kernel")
    for cid in ("perUser", "perSong"):
        check(cid in table, f"no sweep program found for {cid}")
        check("pallas" in table[cid].values(),
              f"every {cid} bucket compiled to the XLA closed form: "
              f"{table[cid]}")


# --- legs ------------------------------------------------------------------

def leg_train(files: dict, out: str, extra_args=()) -> dict:
    """§1: the trainer through its entry point; returns AUC per sweep and
    the profiled compiles each sweep triggered."""
    from photon_ml_tpu.cli import train_game
    from photon_ml_tpu.io.avro import iter_avro_file

    result = train_game.run(
        train_args(files["train"], files["valid"], out) + list(extra_args))
    best = os.path.join(out, "best")
    check(os.path.exists(os.path.join(best, "model-metadata.json")),
          "best/model-metadata.json was not written")
    auc = result["best_evaluation"]["AUC"]
    check(auc >= CPU_REFERENCE_AUC - AUC_SLACK,
          f"validation AUC {auc:.4f} < CPU reference {CPU_REFERENCE_AUC} "
          f"- {AUC_SLACK}")
    n_coefficients = 0
    for kind, cid in (("fixed-effect", "global"), ("random-effect", "perUser"),
                      ("random-effect", "perSong")):
        part_dir = os.path.join(best, kind, cid, "coefficients")
        for part in sorted(os.listdir(part_dir)):
            for record in iter_avro_file(os.path.join(part_dir, part)):
                for mean in record["means"]:
                    check(math.isfinite(mean["value"]),
                          f"{cid}/{record['modelId']}: non-finite "
                          f"coefficient {mean}")
                    n_coefficients += 1
    # the flat-recompile contract: photon_compiles_total{fn} does not move
    # after sweep 0 (each cd.sweep span carries the compiles it triggered)
    sweeps = {}
    with open(os.path.join(out, "telemetry", "trace.jsonl")) as f:
        for line in f:
            span = json.loads(line)
            if span.get("name") == "cd.sweep":
                sweeps[int(span["sweep"])] = int(span["compiles"])
    check(sorted(sweeps) == [0, 1], f"expected two cd.sweep spans: {sweeps}")
    check(sweeps[1] == 0, f"sweep 1 compiled {sweeps[1]} profiled "
                          f"program(s): the recompile contract is broken")
    return {"auc": auc, "coefficients": n_coefficients,
            "compiles_per_sweep": [sweeps[0], sweeps[1]]}


def _rel_err(got, ref) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(got - ref))
                 / (jnp.max(jnp.abs(ref)) + 1e-30))


def leg_kernels(re_shapes, n: int = TRAIN_ROWS, widths=(33, 1024),
                interpret: bool = False) -> dict:
    """§3: every Pallas entry point against its closed form. ``re_shapes``
    are the train leg's ``(entities, samples, features)`` buckets."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops import pallas_glm, pallas_re
    from photon_ml_tpu.ops.design import DenseDesign
    from photon_ml_tpu.ops.losses import LogisticLoss as loss
    from photon_ml_tpu.ops.objective import GLMData, GLMObjective

    objective = GLMObjective(loss=loss)
    errors: dict[str, float] = {}

    def compare(name, dtype, got, ref):
        for part, g, r in zip(("value", "grad"), got, ref):
            err = _rel_err(g, r)
            errors[f"{name}.{part}"] = err
            check(err <= KERNEL_TOL[dtype],
                  f"{name}.{part}: relative error {err:.3g} > "
                  f"{KERNEL_TOL[dtype]}")

    def vectors(key, shape):
        ky, ko, kw = jax.random.split(key, 3)
        labels = (jax.random.uniform(ky, shape) < 0.5).astype(jnp.float32)
        offsets = 0.3 * jax.random.normal(ko, shape, jnp.float32)
        # a fifth of the rows are weight-0 padding, as in a real bucket
        weights = (jax.random.uniform(kw, shape) > 0.2).astype(jnp.float32)
        return labels, offsets, weights

    def reference(fn, *args):
        # f32 matmuls on the TPU default to one bf16 pass: the closed form
        # is the reference only at full f32 precision
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    closed = jax.jit(
        lambda w, data: objective._closed_value_and_grad(w, data, 0.0))
    closed_lanes = jax.jit(jax.vmap(closed, in_axes=(0, None)))
    closed_hvp = jax.jit(lambda x, v, d2w: jnp.einsum(
        "nd,n->d", x, d2w * jnp.einsum(
            "nd,d->n", x, v, preferred_element_type=jnp.float32),
        preferred_element_type=jnp.float32))
    closed_entities = jax.jit(jax.vmap(
        functools.partial(pallas_re._closed_one, loss)))

    key = jax.random.key(21)
    for d in widths:
        for dtype in ("float32", "bfloat16"):
            key, kx, kw, kv, kvec = jax.random.split(key, 5)
            x = jax.random.normal(kx, (n, d), jnp.float32).astype(dtype)
            ws = jax.random.normal(kw, (5, d), jnp.float32) / math.sqrt(d)
            v = jax.random.normal(kv, (d,), jnp.float32)
            labels, offsets, weights = vectors(kvec, (n,))
            data = GLMData(DenseDesign(x), labels, offsets, weights)
            tag = f"{dtype}[{n},{d}]"
            compare(f"fused_value_and_grad {tag}", dtype,
                    pallas_glm.fused_value_and_grad(
                        loss, x, ws[0], labels, offsets, weights,
                        interpret=interpret),
                    reference(closed, ws[0], data))
            compare(f"fused_value_and_grad_multi {tag}", dtype,
                    pallas_glm.fused_value_and_grad_multi(
                        loss, x, ws, labels, offsets, weights,
                        interpret=interpret),
                    reference(closed_lanes, ws, data))
            d2w = reference(jax.jit(objective._d2_weights), ws[0], data)
            errors[f"fused_hvp {tag}"] = err = _rel_err(
                pallas_glm.fused_hvp(x, v, d2w, interpret=interpret),
                reference(closed_hvp, x, v, d2w))
            check(err <= KERNEL_TOL[dtype],
                  f"fused_hvp {tag}: relative error {err:.3g} > "
                  f"{KERNEL_TOL[dtype]}")
            del x, data
    for (e, s, d) in re_shapes:
        for dtype in ("float32", "bfloat16"):
            tag = f"fused_entity_value_and_grad {dtype}[{e},{s},{d}]"
            if pallas_re.entity_plan(e, s, d, dtype) is None:
                errors[tag] = "refused by entity_plan: XLA closed form"
                continue
            key, kx, kw, kvec = jax.random.split(key, 4)
            x = jax.random.normal(kx, (e, s, d), jnp.float32)
            ws = jax.random.normal(kw, (e, d), jnp.float32)
            labels, offsets, weights = vectors(kvec, (e, s))
            x = (x * weights[:, :, None]).astype(dtype)
            compare(tag, dtype,
                    pallas_re.fused_entity_value_and_grad(
                        loss, x, ws, labels, offsets, weights,
                        interpret=interpret),
                    reference(closed_entities, x, ws, labels, offsets,
                              weights))
    return errors


def _score_file(model_dir: str, data: str, out: str):
    """score_game on ``data`` -> the scores it wrote, in record order."""
    from photon_ml_tpu.cli import score_game
    from photon_ml_tpu.io.avro import iter_avro_file

    score_game.run(["--data", data, "--model-dir", model_dir,
                    "--output-dir", out, "--feature-shards", SHARDS])
    return [r["predictionScore"]
            for r in iter_avro_file(os.path.join(out, "scores.avro"))]


class _MemoryWatch:
    """Largest ``bytes_in_use`` each device reported while the block ran."""

    def __init__(self, period_s: float = 0.2):
        import jax

        self._devices = jax.local_devices()
        self._period_s = period_s
        self._stop = threading.Event()
        self.peak = [0] * len(self._devices)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="chip-smoke-memory-watch")

    def _sample(self):
        for i, d in enumerate(self._devices):
            self.peak[i] = max(self.peak[i],
                               int(d.memory_stats()["bytes_in_use"]))

    def _run(self):
        while not self._stop.wait(self._period_s):
            self._sample()

    def __enter__(self):
        self._sample()
        self.before = list(self.peak)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()


def leg_multichip(files: dict, work: str, one_chip: dict, seen: dict) -> dict:
    """§5: the train leg over a 2x2 mesh in this same process."""
    out = os.path.join(work, "mesh")
    with _MemoryWatch() as watch:
        trained = leg_train(files, out, ["--mesh", "data=2,entity=2"])
    table, _buckets = path_table(out, seen)
    mesh_scores = _score_file(out, files["serve"],
                              os.path.join(work, "mesh-scores"))
    result = {
        **trained, "paths": table,
        "score_gap": max(abs(a - b) for a, b in
                         zip(mesh_scores, one_chip["scores"])),
        "bytes_in_use_before": watch.before[:4],
        "bytes_in_use_peak": watch.peak[:4]}
    print(f"multichip: {json.dumps(result)}", flush=True)
    check_paths(table)
    check(abs(trained["auc"] - one_chip["auc"]) <= MULTICHIP_AUC_TOL,
          f"AUC on the mesh {trained['auc']:.5f} vs one chip "
          f"{one_chip['auc']:.5f}: apart by more than {MULTICHIP_AUC_TOL}")
    check(result["score_gap"] <= MULTICHIP_SCORE_TOL,
          f"scores on the mesh differ from one chip by "
          f"{result['score_gap']:.3g} > {MULTICHIP_SCORE_TOL}")
    # chip 0 also holds what the one-chip legs left behind; the other three
    # start empty, and each holds megabytes of designs if the layout is
    # spread — a chip that never held 1 MiB was left out of it
    check(min(watch.peak[1:4]) >= 1 << 20,
          f"peak bytes in use per chip {watch.peak[:4]}: the designs are "
          f"parked on chip 0, not spread over all four")
    return result


def _http(url: str, payload=None) -> dict:
    data = None if payload is None else json.dumps(payload).encode()
    request = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(request, timeout=120) as response:
        return json.loads(response.read())


def leg_serve(files: dict, model_dir: str, reference: list) -> dict:
    """The serving leg: a real server on the model just trained."""
    from photon_ml_tpu.cli import serve_game
    from photon_ml_tpu.io.avro import iter_avro_file

    records = [{"features": r["features"], "metadataMap": r["metadataMap"],
                "offset": r["offset"]}
               for r in iter_avro_file(files["serve"])]
    server = serve_game.build_server([
        "--model-dir", model_dir, "--feature-shards", SHARDS, "--port", "0",
        "--rank-item-coordinate", "perSong"]).start()
    try:
        warm = _http(server.url + "/healthz")
        scores, worst = [], 0.0
        for lo, hi in ((0, 1), (1, 8), (8, SERVE_ROWS)):
            reply = _http(server.url + "/score",
                          {"records": records[lo:hi]})
            check(len(reply["scores"]) == hi - lo,
                  f"/score returned {len(reply['scores'])} of {hi - lo}")
            scores += reply["scores"]
        for got, want in zip(scores, reference):
            check(math.isfinite(got), f"/score returned {got}")
            worst = max(worst, abs(got - want))
        check(worst <= SERVE_TOL,
              f"/score differs from score_game by {worst:.3g} > {SERVE_TOL}")
        ranked = _http(server.url + "/rank", {"record": records[0], "k": 5})
        check(len(ranked["ids"]) == 5 and all(
            math.isfinite(s) for s in ranked["scores"]),
            f"/rank returned {ranked}")
        check(ranked["scores"] == sorted(ranked["scores"], reverse=True),
              f"/rank scores are not descending: {ranked['scores']}")
        after = _http(server.url + "/healthz")
        check(after["compiles"] == warm["compiles"]
              and after["rank"]["compiles"] == warm["rank"]["compiles"],
              f"serving compiled after warm-up: {warm} -> {after}")
    finally:
        server.stop()
        server.history.close()
        server.telemetry.close()
    return {"max_abs_diff_vs_score_game": worst,
            "compiles": after["compiles"],
            "rank_compiles": after["rank"]["compiles"]}


def generate(work: str) -> dict:
    """The e2e cell's data, from its seed (bench._write_e2e_file)."""
    import bench

    files = {}
    for name, rows in (("train", TRAIN_ROWS), ("valid", VALID_ROWS),
                       ("serve", SERVE_ROWS)):
        files[name] = os.path.join(work, f"{name}.avro")
        bench._write_e2e_file(files[name], rows, bench.E2E_USERS,
                              bench.E2E_SONGS)
    return files


def result_line(device: dict) -> str:
    """The last stdout line of a run that passed. It holds these keys and no
    other — whoever runs the smoke reads it as the whole verdict; everything
    else is on the ``report:`` line before it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main() -> int:
    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"jax {jax.__version__} backend {backend} "
          f"device_kind {device['kind']!r} devices {device['count']}",
          flush=True)
    if backend != "tpu":
        print(f"chip_smoke: JAX's default backend is {backend!r}, not "
              f"'tpu'; nothing was run", file=sys.stderr)
        return 1

    from photon_ml_tpu import compile_cache, native

    compile_cache.configure()
    legs: dict[str, str] = {}
    seconds: dict[str, float] = {}
    started = time.perf_counter()

    def done(leg: str) -> None:
        # a leg that failed raised: whatever gets here passed
        nonlocal started
        now = time.perf_counter()
        legs[leg], seconds[leg] = "pass", round(now - started, 1)
        started = now
        print(f"leg {leg}: pass ({seconds[leg]} s)", flush=True)

    check(native.available(), "the native library did not build or load")
    done("native")
    seen: dict[str, int] = {}
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as work:
        files = generate(work)
        done("generate")
        out = os.path.join(work, "one-chip")
        one_chip = leg_train(files, out)
        table, buckets = path_table(out, seen)
        check_paths(table)
        paths = {"one_chip": table}
        done("train")
        kernel_errors = leg_kernels(buckets)
        done("kernels")
        one_chip["scores"] = _score_file(
            out, files["serve"], os.path.join(work, "one-chip-scores"))
        multichip = None
        if device["count"] >= 4:
            multichip = leg_multichip(files, work, one_chip, seen)
            paths["mesh_data2_entity2"] = multichip.pop("paths")
            done("multichip")
        else:
            legs["multichip"] = f"not run: {device['count']} device(s)"
        serving = leg_serve(files, out, one_chip.pop("scores"))
        done("serve")

    print("report: " + json.dumps({
        "legs": legs, "native": native.available(), "paths": paths,
        "train": one_chip, "multichip": multichip, "serve": serving,
        "kernel_rel_err": kernel_errors,
        "setup_wall_seconds_not_a_metric": seconds,
    }), flush=True)
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
