"""The wide sparse GLM refit: ``train_glm_sweep`` on a ``ChunkedSparseDesign``
that the program builds from the generator's entries.

Set-up draws the rows on the device (``gen/``: flat int32 bins and float32
values, a row's entries side by side), hands them to the program's one build
(``ChunkedSparseDesign.from_coo``: the dual layout, made on the device, inside
``setup_s``; its ``design.build`` record is kept from there) and lets them go:
the check draws them again from the seed, so the window holds what a
deployment holds, the design and the per-row vectors. A unit is one
``train_glm_sweep`` call with the configuration's weights from zero
coefficients, ending in a barrier.

What is held against the plain reference (``reference/glm_sparse.py``, over
the generator's entries, nothing the program built): the solve's own reports
(first gradient norm; loss and gradient norm after iterations 1 to ``STEPS``;
the loss and gradient norm where it stopped, against the reference's own at
the program's ``w``), the program's gradient vectors at zero and at its
answer, bin by bin (its own objective asked once more before its state is
dropped: a second compile of the same objective over the same design object,
not the timed ``while_loop`` program), and the answer against the reference's
own path. The reference solves ``reference_iterations`` iterations in the
check (as many as its time allows; the workload says how many); what a solve
of the configuration's full length has to reach is the workload's
``reference_full`` where it is given (the reference's own full-length solve of
the workload's one problem, read once on the chip: its iterations, its loss,
its distance from zero), else what the check's shorter path reaches: the
program stands at least as low and at least as far from zero, and unless it
reports that it converged it has made as many iterations (the ``final_*``
numbers, one-sided).
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.common import Comparison, rel_gap
from benchmark.reference import glm_sparse as reference
from benchmark.reference.lbfgs import options as lbfgs_options
from benchmark.work import glm_sparse as work

STEPS = 3  # L-BFGS iterations that are held one by one
FAULTS = ("half_batch", "stall_after_3", "duplicates_dropped",
          "hot_column_dropped")
#: what the readers take of the ``design.build`` record
BUILD_KEYS = ("seconds", "entries", "row_chunk", "col_chunk", "row_slots",
              "col_slots")
#: what a build with busy bins' planes records besides
BUILD_KEYS_HOT = ("hot_columns", "hot_entries")


def _program():
    """The program's names, imported late: a checkout without the program,
    or with one whose build records no ``design.build`` span, fails here,
    before any set-up."""
    from photon_ml_tpu.glm import training
    from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.ops.design import BUILD_SPAN, ChunkedSparseDesign
    from photon_ml_tpu.ops.objective import GLMData
    from photon_ml_tpu.ops.regularization import L2Regularization
    from photon_ml_tpu.optimize import OptimizerConfig
    from photon_ml_tpu.telemetry import tracing
    from photon_ml_tpu.types import OptimizerType, TaskType

    return locals()


def _weights(config: dict) -> list[float]:
    return sorted((float(v) for v in config["regularization_weights"]),
                  reverse=True)


def _generate(seed: int, config: dict, workload: dict) -> dict:
    gen = importlib.import_module(f"benchmark.gen.{workload['generator']}")
    return gen.generate(seed, workload, config)


class Cell:
    def __init__(self, seed: int, config: dict, workload: dict, devices):
        p = _program()
        self._train = p["training"]
        self.seed, self.config, self.workload = seed, config, workload
        if len(devices) != 1 or int(workload["chips"]) != 1:
            raise ValueError("the glm_sparse family drives one chip")
        opt = config["optimizer"]
        if (opt["type"], config["regularization"]) != ("LBFGS", "L2"):
            raise ValueError("the glm_sparse family drives L-BFGS with L2")
        if config["design_dtype"] != "float32":
            raise ValueError("the chunked layout keeps float32 values")
        self.dim = int(config["dim"])
        arrays = _generate(seed, config, workload)
        self.y = arrays["y"]
        self.rows, width = arrays["cols"].shape
        self.entries = self.rows * width
        # flat, and the generator's own arrays let go before the build
        cols, vals = (arrays.pop(k).reshape(-1) for k in ("cols", "vals"))
        row_ids = jnp.repeat(jnp.arange(self.rows, dtype=jnp.int32), width)
        records: list[dict] = []
        untap = p["tracing"].GLOBAL_TRACER.add_tap(records.append)
        try:
            design = p["ChunkedSparseDesign"].from_coo(
                row_ids, cols, vals, n_rows=self.rows, n_cols=self.dim)
            p["tracing"].flush()
        finally:
            untap()
        del arrays, cols, vals, row_ids  # check() draws them again
        record = next(r for r in records if r["name"] == p["BUILD_SPAN"])
        self.build = {k: record[k] for k in BUILD_KEYS}
        self.build.update({k: record[k] for k in BUILD_KEYS_HOT
                           if k in record})
        self.data = p["GLMData"](
            design=design, labels=self.y, offsets=jnp.zeros_like(self.y),
            weights=jnp.ones_like(self.y))
        self.task = p["TaskType"][config["task"]]
        self.opt_config = p["GLMOptimizationConfiguration"](
            optimizer=p["OptimizerType"].LBFGS,
            regularization=p["L2Regularization"],
            optimizer_config=p["OptimizerConfig"](**lbfgs_options(opt)))
        self.weights = _weights(config)
        if len(self.weights) != 1:
            raise ValueError("the refit solves one weight")
        self.rows_per_unit = self.rows
        self.iterations: list[list[int]] = []
        self.last = None
        self._outputs = None
        jax.block_until_ready(self.data)

    # --- the timed path ----------------------------------------------------
    def unit(self) -> None:
        trained = self._train.train_glm_sweep(
            self.task, self.data, self.weights, self.opt_config)
        self.last = [t.result for t in trained]
        jax.block_until_ready(self.last)
        self.iterations.append([int(r.iterations) for r in self.last])

    def reset_counts(self) -> None:
        self.iterations.clear()

    # --- what the readers and the work model see ---------------------------
    def counters(self) -> dict:
        flat = [i for unit in self.iterations for i in unit]
        return {"lbfgs_iterations": flat, "solves": len(flat),
                "design_build": self.build}

    def required_work(self) -> dict:
        flops, bytes_ = work.pass_work(self.entries, self.rows, self.dim)
        passes = sum(work.solve_passes(i) for u in self.iterations for i in u)
        return {"flops_per_chip": flops * passes,
                "bytes_per_chip": bytes_ * passes, "passes": passes}

    def describe(self) -> dict:
        """Which path the solve holds, read from its lowered text."""
        problem = self._train.build_problem(self.task, self.opt_config)
        text = jax.jit(problem.run).lower(
            self.data, jnp.zeros((self.dim,), jnp.float32),
            jnp.float32(1.0)).as_text(debug_info=True)
        return {"solve_program": "pallas" if "tpu_custom_call" in text
                else "xla", "rows": self.rows, "dim": self.dim,
                "entries": self.entries,
                "scopes": [s for s in ("design.matvec", "design.rmatvec")
                           if s in text],
                "design": type(self.data.design).__name__, **self.build}

    # --- after the window ---------------------------------------------------
    def release(self) -> None:
        """The last unit's outputs to the host, the program's own gradient at
        zero and at its answer with them (its objective asked once more);
        then the program's state is dropped."""
        if self.data is None:
            return
        objective = self._train.build_problem(
            self.task, self.opt_config).objective
        evaluate = jax.jit(objective.value_and_grad)
        lam = jnp.float32(self.weights[0])
        out = outputs_of(self.last)
        for o, r in zip(out, self.last):
            o["cap"] = int(self.config["optimizer"]["max_iterations"])
            o["grad0"] = np.asarray(evaluate(
                jnp.zeros_like(r.w), self.data, lam)[1], np.float64)
            o["grad"] = np.asarray(evaluate(r.w, self.data, lam)[1],
                                   np.float64)
        self._outputs = out
        self.data = self.last = None

    def outputs(self) -> list[dict]:
        self.release()
        return self._outputs

    def entries_again(self):
        """The generator's entries, drawn from the seed once more."""
        a = _generate(self.seed, self.config, self.workload)
        return a["cols"], a["vals"], a["y"]

    def check(self) -> list[Comparison]:
        return compare_outputs(self, self.outputs())


def outputs_of(results) -> list[dict]:
    out = []
    for r in results:
        k = int(r.iterations) + 1
        out.append({"w": np.asarray(r.w, np.float64),
                    "iterations": int(r.iterations),
                    "converged": bool(r.converged),
                    "value": float(r.value), "grad_norm": float(r.grad_norm),
                    "values": np.asarray(r.values[:k], np.float64),
                    "grad_norms": np.asarray(r.grad_norms[:k], np.float64)})
    return out


def solve_path(entries, config: dict, workload: dict, *, iterations: int,
               round_to=None, skip_bin: int = -1) -> list[dict]:
    """The reference's own path in the shape of a cell's outputs: the weight
    solved from zero with the configuration's L-BFGS, ``iterations`` at the
    most (the outputs' ``cap``), with the gradient vectors at zero and at the
    answer."""
    cols, vals, y = entries
    lam = _weights(config)[0]
    fun = reference.objective(cols, vals, y, lam,
                              block=int(workload["row_block"]),
                              round_to=round_to, skip_bin=skip_bin)
    opts = {**lbfgs_options(config["optimizer"]),
            "max_iterations": int(iterations)}
    zero = np.zeros(int(config["dim"]))
    r = reference.lbfgs(fun, zero, **opts)
    grad_at = lambda w: np.asarray(fun(jnp.asarray(w, jnp.float32))[1],
                                   np.float64)
    tol = opts["tolerance"] * max(r["grad_norms"][0], 1.0)
    return [{"w": r["w"], "cap": int(iterations),
             "iterations": len(r["values"]) - 1,
             "converged": bool(r["grad_norms"][-1] <= tol),
             "value": r["values"][-1], "grad_norm": r["grad_norms"][-1],
             "values": np.asarray(r["values"]),
             "grad_norms": np.asarray(r["grad_norms"]),
             "grad0": grad_at(zero), "grad": grad_at(r["w"])}]


def _at(history, k: int) -> float:
    return float(history[k]) if k < len(history) else np.inf


def _worst_bin(got, want) -> float:
    """Largest gap of a gradient's entries, bin by bin, each against the
    larger of 1 and the reference's entry."""
    gap = np.abs(got - want) / np.maximum(1.0, np.abs(want))
    return float(np.max(gap)) if np.all(np.isfinite(gap)) else np.inf


def compare(outputs: list[dict], entries, config: dict, workload: dict,
            ref: list[dict] | None = None) -> list[Comparison]:
    cols, vals, y = entries
    lam = _weights(config)[0]
    if ref is None:
        ref = solve_path(entries, config, workload,
                         iterations=int(workload["reference_iterations"]))
    out, rf = outputs[0], ref[0]
    g0 = rf["grad_norms"][0]
    steps = range(1, STEPS + 1)
    f, g = reference.value_and_grad(
        cols, vals, y, jnp.asarray(out["w"], jnp.float32), jnp.float32(lam),
        block=int(workload["row_block"]))
    g = np.asarray(g, np.float64)
    norm = lambda v: float(np.linalg.norm(v))
    # what a full-length solve reaches, read once for the workload's problem;
    # outputs made under a shorter cap (the check's own path, a stand-in as
    # long) are held against the check's path
    full = workload.get("reference_full")
    if not full or out["cap"] < int(config["optimizer"]["max_iterations"]):
        full = {"iterations": rf["iterations"], "loss": rf["value"],
                "w_norm": norm(rf["w"])}
    numbers = {
        "grad0_gap": rel_gap(out["grad_norms"][0], g0),
        "grad0_bin_gap": _worst_bin(out["grad0"], rf["grad0"]),
        "step_loss_gap": max(
            rel_gap(_at(out["values"], k), _at(rf["values"], k))
            for k in steps),
        "step_gnorm_gap": max(
            rel_gap(_at(out["grad_norms"], k), _at(rf["grad_norms"], k))
            for k in steps),
        "report_loss_gap": rel_gap(out["value"], float(f)),
        "kkt_gap": rel_gap(out["grad_norm"], norm(g), scale=g0),
        "kkt_bin_gap": _worst_bin(out["grad"], g),
        # one-sided: the program's solve stands no higher and no nearer to
        # zero than the reference's, and stopped no sooner unless it converged
        "final_loss_gap": (max(0.0, float(f) - full["loss"])
                           / abs(full["loss"]) if np.isfinite(float(f))
                           else np.inf),
        "final_move_gap": max(0.0, full["w_norm"] - norm(out["w"]))
        / full["w_norm"],
        "final_count_gap": 0.0 if out["converged"] else max(
            0.0, full["iterations"] - out["iterations"])
        / max(full["iterations"], 1),
    }
    limits = workload["limits"]
    return [Comparison(n, v, float(limits[n])) for n, v in numbers.items()]


def setup(seed: int, config: dict, workload: dict, devices) -> Cell:
    return Cell(seed, config, workload, devices)


# --- what the selfcheck and the readings ask besides (families/common.py) ---
def reference_outputs(cell: Cell) -> list[dict]:
    return solve_path(cell.entries_again(), cell.config, cell.workload,
                      iterations=int(cell.workload["reference_iterations"]))


def compare_outputs(cell: Cell, outputs: list[dict],
                    ref: list[dict] | None = None) -> list[Comparison]:
    return compare(outputs, cell.entries_again(), cell.config, cell.workload,
                   ref)


def fault_outputs(kind: str, entries, config: dict, workload: dict
                  ) -> list[dict]:
    """A fault planted in the reference put in the program's place, every
    report consistent with where it stopped, each solved as long as the
    reference's own path (``reference_iterations``: the readings are taken on
    the chip, where a reference evaluation takes seconds): ``half_batch`` trains on the first half of the rows;
    ``stall_after_3`` leaves the state unchanged after the third iteration;
    ``duplicates_dropped`` counts a row's colliding entries once;
    ``hot_column_dropped`` leaves the busiest bin's entries out of the
    transpose, so that its coefficient never moves."""
    cols, vals, y = entries
    length = int(workload["reference_iterations"])
    block = int(workload["row_block"])
    if kind == "half_batch":
        half = max(y.shape[0] // 2 // block, 1) * block
        return solve_path((cols[:half], vals[:half], y[:half]), config,
                          workload, iterations=length)
    if kind == "stall_after_3":
        return solve_path(entries, config, workload, iterations=STEPS)
    if kind == "duplicates_dropped":
        once = reference.first_of_duplicates(cols, vals, block=block)
        return solve_path((cols, once, y), config, workload, iterations=length)
    if kind == "hot_column_dropped":
        hot = int(reference.busiest_bin(cols, dim=int(config["dim"])))
        return solve_path(entries, config, workload, iterations=length,
                          skip_bin=hot)
    raise ValueError(f"unknown fault {kind!r}")


def stand_ins(cell: Cell, faults, ref: list[dict]):
    """``(name, outputs)`` of the lower-precision control (the reference with
    the gathered coefficients and the per-row residuals rounded to bfloat16,
    in the program's place) and of each of ``faults``, each as long as the
    reference's own path."""
    entries = cell.entries_again()
    yield "control_bfloat16", solve_path(
        entries, cell.config, cell.workload,
        iterations=int(cell.workload["reference_iterations"]),
        round_to="bfloat16")
    for kind in faults:
        yield f"fault_{kind}", fault_outputs(kind, entries, cell.config,
                                             cell.workload)
