"""The dense GLM lambda path: ``train_glm_sweep`` on data from the generator.

A unit is one call of ``train_glm_sweep`` with the configuration's weights:
the program dispatches the path's solves back to back without a barrier of its
own, so the call's end (a barrier on every result) is the only unit boundary
the host can see. Set-up builds the data and the optimisation configuration
once; the warm unit and every unit of the window go through :meth:`Cell.unit`.

On one chip the design is ``(rows, dim)`` and the call is the plain one. On
several the design is the stacked layout ``(chips, rows_per_chip, dim)``, block
``i`` on chip ``i`` of a mesh with the one axis ``data``, and the call passes
``mesh=`` and ``dim=``: the program's ``shard_map``/``psum`` objective. The
plain reference follows the layout (``reference/glm.py``,
``reference/glm_stacked.py``); everything below is the same for both.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.common import Comparison, rel_gap
from benchmark.reference import glm as reference
from benchmark.reference import glm_stacked as reference_stacked
from benchmark.reference.lbfgs import options as lbfgs_options
from benchmark.work import glm as work

STEPS = 3  # L-BFGS iterations of the first solve that are held one by one


def _weights(config: dict) -> list[float]:
    """The path's weights as the program solves them: strongest first."""
    return sorted((float(v) for v in config["regularization_weights"]),
                  reverse=True)


def _program():
    """The program's names, imported late: a checkout without the program
    fails here, before any result."""
    from photon_ml_tpu.glm import training
    from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.ops.design import DenseDesign
    from photon_ml_tpu.ops.objective import GLMData
    from photon_ml_tpu.ops.regularization import L2Regularization
    from photon_ml_tpu.optimize import OptimizerConfig
    from photon_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh
    from photon_ml_tpu.types import OptimizerType, TaskType

    return locals()


def _reference_for(x):
    """The plain reference that reads ``x``'s layout."""
    return reference_stacked if x.ndim == 3 else reference


class Cell:
    def __init__(self, seed: int, config: dict, workload: dict, devices):
        p = _program()
        self._train = p["training"]
        self.config, self.workload = config, workload
        if len(devices) != int(workload["chips"]):
            raise ValueError(f"the cell drives {workload['chips']} chips, "
                             f"not {len(devices)}")
        gen = importlib.import_module(f"benchmark.gen.{workload['generator']}")
        self.dim = int(config["dim"])
        self.chips = len(devices)
        if self.chips == 1:
            self.mesh, self._mesh_args = None, {}
            arrays = gen.generate(seed, workload, config)
        else:
            self.mesh = p["make_mesh"]({p["DATA_AXIS"]: self.chips},
                                       devices=devices)
            self._mesh_args = {"mesh": self.mesh, "dim": self.dim}
            arrays = gen.generate(seed, workload, config, self.mesh)
        self.x, self.y = arrays["x"], arrays["y"]
        if config["design_dtype"] != "float32":
            self.x = self.x.astype(config["design_dtype"])
        self.rows = int(self.y.size)
        # like the labels: on the mesh, block by block on the labels' chips
        self.data = p["GLMData"](
            design=p["DenseDesign"](x=self.x), labels=self.y,
            offsets=jnp.zeros_like(self.y), weights=jnp.ones_like(self.y))
        opt = config["optimizer"]
        if (opt["type"], config["regularization"]) != ("LBFGS", "L2"):
            raise ValueError("the glm family drives L-BFGS with L2 only")
        self.task = p["TaskType"][config["task"]]
        self.opt_config = p["GLMOptimizationConfiguration"](
            optimizer=p["OptimizerType"].LBFGS,
            regularization=p["L2Regularization"],
            optimizer_config=p["OptimizerConfig"](**lbfgs_options(opt)))
        self.weights = _weights(config)
        self.rows_per_unit = self.rows * len(self.weights)
        self.iterations: list[list[int]] = []  # per unit, per solve
        self.last = None  # the last unit's results, on the device
        jax.block_until_ready(self.data)

    # --- the timed path ----------------------------------------------------
    def unit(self) -> None:
        trained = self._train.train_glm_sweep(
            self.task, self.data, self.weights, self.opt_config,
            **self._mesh_args)
        self.last = [t.result for t in trained]
        jax.block_until_ready(self.last)
        self.iterations.append([int(r.iterations) for r in self.last])

    def reset_counts(self) -> None:
        self.iterations.clear()

    # --- what the readers and the work model see ---------------------------
    def counters(self) -> dict:
        flat = [i for unit in self.iterations for i in unit]
        return {"lbfgs_iterations": flat, "solves": len(flat)}

    def required_work(self) -> dict:
        """Least device seconds for the window's solves, by pass counts."""
        flops, bytes_ = work.pass_work(
            self.rows // self.chips, self.dim,
            jnp.dtype(self.x.dtype).itemsize)
        passes = sum(work.solve_passes(i) for u in self.iterations for i in u)
        return {"flops_per_chip": flops * passes,
                "bytes_per_chip": bytes_ * passes, "passes": passes}

    def describe(self) -> dict:
        """Which path the compiled solve holds, read from its text."""
        problem = self._train.build_problem(self.task, self.opt_config,
                                            mesh=self.mesh)
        w = jnp.zeros((self.dim,), jnp.float32)
        text = jax.jit(problem.run).lower(
            self.data, w, jnp.float32(1.0)).compile().as_text()
        return {"solve_program": "pallas" if "tpu_custom_call" in text
                else "xla", "rows": self.rows, "dim": self.dim}

    # --- after the window ---------------------------------------------------
    def release(self) -> None:
        """Drop the program's state; the design and labels stay for the
        reference (they are the benchmark's, made from the seed)."""
        self.data = None

    def outputs(self) -> list[dict]:
        return outputs_of(self.last)

    def check(self) -> list[Comparison]:
        return compare(self.outputs(), self.x, self.y, self.config,
                       self.workload)


def outputs_of(results) -> list[dict]:
    """Host copies of what the reference is held against, per solve: the
    answer, what the solve reported where it stopped, and the loss and
    gradient norm after every iteration it took (index 0: its start)."""
    out = []
    for r in results:
        k = int(r.iterations) + 1
        out.append({"w": np.asarray(r.w, np.float64),
                    "value": float(r.value), "grad_norm": float(r.grad_norm),
                    "values": np.asarray(r.values[:k], np.float64),
                    "grad_norms": np.asarray(r.grad_norms[:k], np.float64)})
    return out


def solve_path(x, y, config: dict, workload: dict, *, round_to=None,
               iterations=None) -> list[dict]:
    """The reference's own path, in the shape of :func:`outputs_of`: every
    weight solved with the configuration's L-BFGS, each from the answer before
    it. ``round_to`` rounds the design (the lower-precision control);
    ``iterations`` (one cap per solve) plants a solve that stops early."""
    chunk = int(workload["row_chunk"])
    opts = lbfgs_options(config["optimizer"])
    w = np.zeros(int(config["dim"]))
    outputs = []
    for k, lam in enumerate(_weights(config)):
        if iterations is not None:
            opts = {**opts, "max_iterations": int(iterations[k])}
        r = reference.lbfgs(
            _reference_for(x).objective(x, y, lam, chunk=chunk,
                                        round_to=round_to),
            w, **opts)
        w = r["w"]
        outputs.append({"w": w, "value": r["values"][-1],
                        "grad_norm": r["grad_norms"][-1],
                        "values": np.asarray(r["values"]),
                        "grad_norms": np.asarray(r["grad_norms"])})
    return outputs


def _at(history, k: int) -> float:
    """Iteration ``k`` of a history; a step never taken reads infinity."""
    return float(history[k]) if k < len(history) else np.inf


def _moves(outputs: list[dict]) -> list[float]:
    """Per solve, how far the answer lies from the solve's start (the answer
    before it; zero for the first)."""
    start = np.zeros_like(outputs[0]["w"])
    out = []
    for o in outputs:
        out.append(float(np.linalg.norm(o["w"] - start)))
        start = o["w"]
    return out


def compare(outputs: list[dict], x, y, config: dict, workload: dict,
            ref: list[dict] | None = None) -> list[Comparison]:
    """The last unit's solves against the plain reference.

    The reference solves the whole path itself (:func:`solve_path`: every
    weight to the configuration's iteration cap, warm-started from its own
    answers). Held against it: the first gradient as the optimizer got it;
    the loss and the gradient norm after each of the first solve's first
    ``STEPS`` iterations; every solve's final loss; and how far every solve
    moved from its start (the norm of the parameters' change). The first
    solve, which both sides start from zero, is held apart from the later
    ones, whose starts already differ by where the cap stopped the solve
    before (PERF.md, section 4). Besides, the reference evaluates its own
    objective and gradient at every answer of the program: the loss and the
    gradient norm that the program reported there.
    """
    chunk = int(workload["row_chunk"])
    weights = _weights(config)
    if ref is None:
        ref = solve_path(x, y, config, workload)
    first, ref_first = outputs[0], ref[0]
    g0 = ref_first["grad_norms"][0]
    steps = range(1, STEPS + 1)
    loss = [rel_gap(o["value"], r["value"]) for o, r in zip(outputs, ref)]
    move = [rel_gap(a, b) for a, b in zip(_moves(outputs), _moves(ref))]
    numbers = {
        "grad0_gap": rel_gap(first["grad_norms"][0], g0),
        "step_loss_gap": max(
            rel_gap(_at(first["values"], k), _at(ref_first["values"], k))
            for k in steps),
        "step_gnorm_gap": max(
            rel_gap(_at(first["grad_norms"], k),
                    _at(ref_first["grad_norms"], k)) for k in steps),
        "solve1_loss_gap": loss[0],
        "solve1_move_gap": move[0],
    }
    if len(weights) > 1:
        numbers["later_loss_gap"] = max(loss[1:])
        numbers["later_move_gap"] = max(move[1:])
    loss_gaps, kkt_gaps = [], []
    for out, lam in zip(outputs, weights):
        f, g = _reference_for(x).value_and_grad(
            x, y, jnp.asarray(out["w"], jnp.float32), jnp.float32(lam),
            chunk=chunk)
        loss_gaps.append(rel_gap(out["value"], float(f)))
        kkt_gaps.append(rel_gap(
            out["grad_norm"], float(jnp.linalg.norm(g)), scale=g0))
    numbers["report_loss_gap"] = max(loss_gaps)
    numbers["kkt_gap"] = max(kkt_gaps)
    limits = workload["limits"]
    return [Comparison(n, v, float(limits[n])) for n, v in numbers.items()]


def setup(seed: int, config: dict, workload: dict, devices) -> Cell:
    return Cell(seed, config, workload, devices)


# --- what the selfcheck and the readings ask besides (families/common.py) ---
def reference_outputs(cell: Cell) -> list[dict]:
    return solve_path(cell.x, cell.y, cell.config, cell.workload)


def compare_outputs(cell: Cell, outputs: list[dict],
                    ref: list[dict] | None = None) -> list[Comparison]:
    return compare(outputs, cell.x, cell.y, cell.config, cell.workload, ref)


FAULTS = ("half_batch", "stall_after_3", "warm_start_returned", "no_exchange")


def fault_outputs(kind: str, x, y, config: dict, workload: dict,
                  ref: list[dict]) -> list[dict]:
    """A fault planted in the reference put in the program's place, at full
    precision, every report consistent with where it stopped: ``half_batch``
    trains on the first half of every chip's rows; ``stall_after_3`` leaves
    every solve's state unchanged after its third iteration;
    ``warm_start_returned`` solves the first weight soundly (``ref``'s own
    answer) and hands the later solves' warm start back unmoved;
    ``no_exchange`` leaves the chips' losses and gradients un-summed: the
    whole path solved and reported on one chip's rows as if they were all."""
    n = len(_weights(config))
    if kind == "half_batch":
        chunk = int(workload["row_chunk"])
        half = max(y.shape[-1] // 2 // chunk, 1) * chunk
        return solve_path(x[..., :half, :], y[..., :half], config, workload)
    if kind == "stall_after_3":
        return solve_path(x, y, config, workload, iterations=[STEPS] * n)
    if kind == "warm_start_returned":
        chunk = int(workload["row_chunk"])
        w = ref[0]["w"]
        out = [ref[0]]
        for lam in _weights(config)[1:]:
            f, g = _reference_for(x).value_and_grad(
                x, y, jnp.asarray(w, jnp.float32), jnp.float32(lam),
                chunk=chunk)
            gn = float(jnp.linalg.norm(g))
            out.append({"w": w, "value": float(f), "grad_norm": gn,
                        "values": np.asarray([float(f)]),
                        "grad_norms": np.asarray([gn])})
        return out
    if kind == "no_exchange":
        return solve_path(*reference_stacked.chip_blocks(x, y)[0], config,
                          workload)
    raise ValueError(f"unknown fault {kind!r}")


def stand_ins(cell: Cell, faults, ref: list[dict]):
    """``(name, outputs)`` of the lower-precision control (the reference, its
    design rounded to bfloat16, in the program's place) and of each of
    ``faults`` that the cell can have, for the readings that the limits are
    set from: ``no_exchange`` only where chips exchange."""
    yield "control_bfloat16", solve_path(
        cell.x, cell.y, cell.config, cell.workload, round_to="bfloat16")
    for kind in faults:
        if kind != "no_exchange" or cell.chips > 1:
            yield f"fault_{kind}", fault_outputs(
                kind, cell.x, cell.y, cell.config, cell.workload, ref)
