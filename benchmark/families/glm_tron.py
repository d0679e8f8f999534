"""The dense GLM lambda path fitted by trust-region Newton: ``train_glm_sweep``
under ``OptimizerType.TRON`` on data from the generator.

A unit is one call of ``train_glm_sweep`` with the configuration's weights and
the configuration's TRON settings passed through ``OptimizerConfig``, as a
caller of the library passes them: no entry point, option or flag of its own.
The program dispatches the path's solves back to back, so the call's end (a
barrier on every result) is the only unit boundary the host can see.

What the family needs of the program, and refuses to run without: the count
of Hessian-vector products in a solve's result (``OptimizerResult.hvps``:
asked for in :func:`_program`, before any data is drawn), and on a TPU both
Pallas kernels in the compiled solve (``fused_value_and_grad`` and
``fused_hvp``, by their names in the compiled text: :meth:`Cell.describe`).

The plain reference is ``reference/tron.py``; everything is on one chip.
"""

from __future__ import annotations

import dataclasses
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.common import Comparison, rel_gap
from benchmark.families.glm import _at, _moves, _weights
from benchmark.families.glm import outputs_of as _glm_outputs_of
from benchmark.reference import tron as reference
from benchmark.work import glm_tron as work

STEPS = 3  # outer iterations of the first solve that are held one by one
KERNELS = ("fused_value_and_grad", "fused_hvp")


def _program():
    """The program's names, imported late: a checkout without the program,
    or whose solves do not count their Hessian-vector products, fails here,
    before any data is drawn and any result is printed."""
    from photon_ml_tpu.glm import training
    from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.ops.design import DenseDesign
    from photon_ml_tpu.ops.objective import GLMData
    from photon_ml_tpu.ops.regularization import L2Regularization
    from photon_ml_tpu.optimize import OptimizerConfig, OptimizerResult
    from photon_ml_tpu.types import OptimizerType, TaskType

    if "hvps" not in {f.name for f in dataclasses.fields(OptimizerResult)}:
        raise RuntimeError(
            "this checkout's OptimizerResult has no 'hvps': its TRON solves "
            "do not count their Hessian-vector products, and the cell's "
            "required work is made of that count")
    return locals()


def kernels_in(text: str) -> list[str]:
    """Which of :data:`KERNELS` the compiled text calls as TPU custom calls
    (a ``pallas_call``'s ``name=`` is the instruction's name there)."""
    return [k for k in KERNELS if re.search(
        rf"%{k}[\w.]* = [^\n]*custom_call_target=\"tpu_custom_call\"", text)]


class Cell:
    def __init__(self, seed: int, config: dict, workload: dict, devices):
        p = _program()
        self._train = p["training"]
        self.config, self.workload = config, workload
        if len(devices) != 1 or int(workload["chips"]) != 1:
            raise ValueError("the glm_tron family drives one chip")
        opt = config["optimizer"]
        if (opt["type"], config["regularization"]) != ("TRON", "L2"):
            raise ValueError("the glm_tron family drives TRON with L2 only")
        gen = importlib.import_module(f"benchmark.gen.{workload['generator']}")
        self.dim = int(config["dim"])
        arrays = gen.generate(seed, workload, config)
        self.x, self.y = arrays["x"], arrays["y"]
        if config["design_dtype"] != "float32":
            self.x = self.x.astype(config["design_dtype"])
        self.rows = int(self.y.size)
        self.data = p["GLMData"](
            design=p["DenseDesign"](x=self.x), labels=self.y,
            offsets=jnp.zeros_like(self.y), weights=jnp.ones_like(self.y))
        self.task = p["TaskType"][config["task"]]
        self.opt_config = p["GLMOptimizationConfiguration"](
            optimizer=p["OptimizerType"].TRON,
            regularization=p["L2Regularization"],
            optimizer_config=p["OptimizerConfig"](
                max_iterations=int(opt["max_iterations"]),
                tolerance=float(opt["tolerance"]),
                cg_max_iterations=int(opt["cg_max_iterations"])))
        self.weights = _weights(config)
        self.rows_per_unit = self.rows * len(self.weights)
        self.counts: list[list[tuple[int, int]]] = []  # per unit, per solve
        self.last = None  # the last unit's results, on the device
        self._outputs = None
        jax.block_until_ready(self.data)
        self._paths = None
        if devices[0].platform == "tpu":
            missing = set(KERNELS) - set(self.describe()["kernels"])
            if missing:
                raise RuntimeError(
                    f"the compiled solve holds no {sorted(missing)}: the "
                    f"cell measures the Pallas path and reports no other")

    # --- the timed path ----------------------------------------------------
    def unit(self) -> None:
        trained = self._train.train_glm_sweep(
            self.task, self.data, self.weights, self.opt_config)
        self.last = [t.result for t in trained]
        jax.block_until_ready(self.last)
        self.counts.append([(int(r.iterations), int(r.hvps))
                            for r in self.last])

    def reset_counts(self) -> None:
        self.counts.clear()

    # --- what the readers and the work model see ---------------------------
    def counters(self) -> dict:
        flat = [c for unit in self.counts for c in unit]
        return {"tron_iterations": [i for i, _ in flat],
                "tron_hvps": [h for _, h in flat], "solves": len(flat)}

    def required_work(self) -> dict:
        """Least device seconds for the window's solves, by pass counts."""
        return work.solves_work(
            self.rows, self.dim, jnp.dtype(self.x.dtype).itemsize,
            [c for unit in self.counts for c in unit])

    def _problem(self):
        return self._train.build_problem(self.task, self.opt_config)

    def describe(self) -> dict:
        """Which kernels the compiled solve holds, read from its text (once:
        set-up asks on a TPU, and refuses a solve that lacks one)."""
        if self._paths is None:
            text = jax.jit(self._problem().run).lower(
                self.data, jnp.zeros((self.dim,), jnp.float32),
                jnp.float32(1.0)).compile().as_text()
            kernels = kernels_in(text)
            self._paths = {
                "solve_program": "pallas" if set(kernels) == set(KERNELS)
                else "xla", "kernels": kernels, "optimizer": "TRON",
                "rows": self.rows, "dim": self.dim}
        return self._paths

    # --- after the window ---------------------------------------------------
    def release(self) -> None:
        """The last unit's outputs to the host, and with them the program's
        first gradient and first Hessian-vector product ``H(0) g0`` at the
        first weight: the objective and the design object of the timed path
        asked once more, in a compile of their own (the timed ``while_loop``
        program is held by its reports and its answers). Then the program's
        state is dropped; the design and labels stay for the reference."""
        if self.data is None:
            return
        objective = self._problem().objective

        def probe(data, w, lam):
            _, g = objective.value_and_grad(w, data, lam)
            return g, objective.hvp_operator(w, data, lam)(g)

        out = outputs_of(self.last)
        g0, hvp0 = jax.jit(probe)(
            self.data, jnp.zeros((self.dim,), jnp.float32),
            jnp.float32(self.weights[0]))
        out[0]["g0"] = np.asarray(g0, np.float64)
        out[0]["hvp0"] = np.asarray(hvp0, np.float64)
        self._outputs = out
        self.data = self.last = None

    def outputs(self) -> list[dict]:
        self.release()
        return self._outputs

    def check(self) -> list[Comparison]:
        return compare(self.outputs(), self.x, self.y, self.config,
                       self.workload)


def outputs_of(results) -> list[dict]:
    """Host copies of what the reference is held against, per solve: what
    the L-BFGS family keeps (the answer, what the solve reported where it
    stopped, the loss and gradient norm after every outer iteration: index
    0 its start, a rejected step repeating the entry before it) and the
    solve's counts."""
    out = _glm_outputs_of(results)
    for o, r in zip(out, results):
        o.update(iterations=int(r.iterations), hvps=int(r.hvps),
                 converged=bool(r.converged))
    return out


def solve_path(x, y, config: dict, workload: dict, *, round_to=None,
               iterations=None, cg_cap=None, flat_curvature=False
               ) -> list[dict]:
    """The reference's own path, in the shape of :func:`outputs_of` (the
    first solve's entry with ``g0`` and ``hvp0`` as :meth:`Cell.release`
    adds them): every weight solved by ``reference/tron.py`` with the
    configuration's settings, each from the answer before it. ``round_to``
    rounds the design (the lower-precision control). Planted faults:
    ``iterations`` (one cap per solve) stops a solve early; ``cg_cap`` caps
    the conjugate gradients; ``flat_curvature`` holds ``d2`` at 1/4, the
    Hessian of ``w = 0``, at every iterate."""
    chunk = int(workload["row_chunk"])
    opts = reference.options(config["optimizer"])
    if cg_cap is not None:
        opts["cg_max_iterations"] = int(cg_cap)
    d2_at = None
    if flat_curvature:
        d2_at = lambda w: jnp.full((x.shape[0],), 0.25, x.dtype)
    w = np.zeros(int(config["dim"]), np.float32)
    outputs = []
    for k, lam in enumerate(_weights(config)):
        if iterations is not None:
            opts["max_iterations"] = int(iterations[k])
        problem = reference.Problem(x, y, lam, chunk=chunk, round_to=round_to,
                                    d2_at=d2_at)
        first = {}
        if k == 0:
            g0 = problem.fun(w)[1]
            first = {"g0": np.asarray(g0, np.float64),
                     "hvp0": np.asarray(problem.hessian_at(w)(g0),
                                        np.float64)}
        r = reference.tron(problem.fun, problem.hessian_at, w, **opts)
        w = r["w"]
        outputs.append({"w": np.asarray(w, np.float64),
                        "value": r["values"][-1],
                        "grad_norm": r["grad_norms"][-1],
                        "iterations": r["iterations"], "hvps": r["hvps"],
                        "converged": r["converged"],
                        "values": np.asarray(r["values"]),
                        "grad_norms": np.asarray(r["grad_norms"]), **first})
    return outputs


def _worse_by(program: float, reference_: float, *, higher: bool) -> float:
    """The gap between the two readings where the program's is the worse one
    (the ``higher`` one, or the lower), zero where it is level or better."""
    gap = rel_gap(program, reference_)
    if not np.isfinite(gap):
        return gap
    worse = program > reference_ if higher else program < reference_
    return gap if worse else 0.0


def compare(outputs: list[dict], x, y, config: dict, workload: dict,
            ref: list[dict] | None = None) -> list[Comparison]:
    """The last unit's solves against the plain reference.

    The reference solves the whole path itself (:func:`solve_path`). Held
    against it, two-sided: the first gradient as the optimizer got it; the
    first Hessian-vector product ``H(0) g0``, the reference's product of the
    same vector beside the program's, entry by entry (the norm of the
    difference over the norm); the loss and the gradient norm after each of
    the first solve's first ``STEPS`` outer iterations; the first solve's
    counts of outer iterations and of products. Conjugate gradients multiply
    a rounding in float32 from one inner step to the next, so from a few
    outer iterations on the two paths are two paths: the final answers are
    held one-sided. No solve may end at a higher loss than the reference's
    path, and none may have moved less far from its start (the norm of the
    parameters' change). Besides, the reference evaluates its own objective
    and gradient at every answer of the program: the loss and the gradient
    norm that the program reported there.
    """
    chunk = int(workload["row_chunk"])
    weights = _weights(config)
    if ref is None:
        ref = solve_path(x, y, config, workload)
    first, ref_first = outputs[0], ref[0]
    g0 = ref_first["grad_norms"][0]
    steps = range(1, STEPS + 1)
    l2 = jnp.float32(weights[0])
    zero = jnp.zeros((x.shape[1],), jnp.float32)
    ref_hvp0 = np.asarray(reference.hessian_vector(
        x, reference.curvature(x, zero, chunk=chunk),
        jnp.asarray(first["g0"], jnp.float32), l2, chunk=chunk), np.float64)
    hvp0_gap = rel_gap(np.linalg.norm(first["hvp0"] - ref_hvp0), 0.0,
                       scale=np.linalg.norm(ref_hvp0))
    loss = [_worse_by(o["value"], r["value"], higher=True)
            for o, r in zip(outputs, ref)]
    move = [_worse_by(a, b, higher=False)
            for a, b in zip(_moves(outputs), _moves(ref))]
    numbers = {
        "grad0_gap": rel_gap(first["grad_norms"][0], g0),
        "hvp0_gap": hvp0_gap,
        "step_loss_gap": max(
            rel_gap(_at(first["values"], k), _at(ref_first["values"], k))
            for k in steps),
        "step_gnorm_gap": max(
            rel_gap(_at(first["grad_norms"], k),
                    _at(ref_first["grad_norms"], k)) for k in steps),
        "solve1_iterations_gap": rel_gap(first["iterations"],
                                         ref_first["iterations"]),
        "solve1_hvps_gap": rel_gap(first["hvps"], ref_first["hvps"]),
        "solve1_loss_gap": loss[0],
        "solve1_move_gap": move[0],
    }
    if len(weights) > 1:
        numbers["later_loss_gap"] = max(loss[1:])
        numbers["later_move_gap"] = max(move[1:])
    loss_gaps, kkt_gaps = [], []
    for out, lam in zip(outputs, weights):
        f, g = reference.value_and_grad(
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


FAULTS = ("half_batch", "stall_after_3", "warm_start_returned",
          "curvature_at_zero", "cg_one_step")


def fault_outputs(kind: str, x, y, config: dict, workload: dict,
                  ref: list[dict]) -> list[dict]:
    """A fault planted in the reference put in the program's place, at full
    precision, every report consistent with where it stopped: ``half_batch``
    trains on the first half of the rows; ``stall_after_3`` leaves every
    solve's state unchanged after its third outer iteration;
    ``warm_start_returned`` solves the first weight soundly (``ref``'s own
    answer) and hands the later solves' warm start back unmoved; and two of
    this method's own: ``curvature_at_zero`` takes every Hessian-vector
    product with the curvature of ``w = 0`` (``d2`` = 1/4: a Newton method
    that never looks at its iterate again), ``cg_one_step`` stops the
    conjugate gradients after one product (steepest descent inside the
    trust region)."""
    n = len(_weights(config))
    chunk = int(workload["row_chunk"])
    if kind == "half_batch":
        half = max(y.shape[0] // 2 // chunk, 1) * chunk
        return solve_path(x[:half], y[:half], config, workload)
    if kind == "stall_after_3":
        return solve_path(x, y, config, workload, iterations=[STEPS] * n)
    if kind == "curvature_at_zero":
        return solve_path(x, y, config, workload, flat_curvature=True)
    if kind == "cg_one_step":
        return solve_path(x, y, config, workload, cg_cap=1)
    if kind == "warm_start_returned":
        w = ref[0]["w"]
        out = [ref[0]]
        for lam in _weights(config)[1:]:
            f, g = reference.value_and_grad(
                x, y, jnp.asarray(w, jnp.float32), jnp.float32(lam),
                chunk=chunk)
            gn = float(jnp.linalg.norm(g))
            out.append({"w": w, "value": float(f), "grad_norm": gn,
                        "iterations": 0, "hvps": 0, "converged": True,
                        "values": np.asarray([float(f)]),
                        "grad_norms": np.asarray([gn])})
        return out
    raise ValueError(f"unknown fault {kind!r}")


def stand_ins(cell: Cell, faults, ref: list[dict]):
    """``(name, outputs)`` of the lower-precision control (the reference, its
    design rounded to bfloat16, in the program's place) and of each of
    ``faults``, for the readings that the limits are set from."""
    yield "control_bfloat16", solve_path(
        cell.x, cell.y, cell.config, cell.workload, round_to="bfloat16")
    for kind in faults:
        yield f"fault_{kind}", fault_outputs(
            kind, cell.x, cell.y, cell.config, cell.workload, ref)
