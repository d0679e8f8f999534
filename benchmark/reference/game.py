"""Plain reference for one coordinate-descent sweep of a GLMix model (Zhang
et al., KDD 2016): a fixed effect and per-entity random effects, logistic
loss, L2, as published. It imports nothing of the program and takes nothing
the program made.

One sweep: the fixed effect solved on offset 0; then every random effect in
the update sequence, each entity's rows solved alone with the margins of all
the coordinates before it as offsets; every solve L-BFGS from zero
coefficients with the configuration's cap and tolerance (``reference/
lbfgs.py`` for the fixed effect, its lane-by-lane twin ``lbfgs_lanes.py`` for
the entities). Float32 ``jax.numpy``: elementwise products and sums, so no
matrix unit and no lower-precision pass.

Entities are solved one group of equal padded length at a time (lengths are
padded to the next power of two; a padded slot has weight 0) so that a group
is one dense ``(entities, length, dim)`` block that fits the chip. No kernel,
no fused sweep, none of the program's bucket strategy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import glm
from benchmark.reference.lbfgs import lbfgs, options as lbfgs_options
from benchmark.reference.lbfgs_lanes import lbfgs_lanes


def groups_of(ids: np.ndarray, n_entities: int) -> list[dict]:
    """The rows of every entity that has any, grouped by padded length:
    ``[{"entities": (E,), "rows": (E,) counts, "index": (E, S) row numbers,
    -1 in padded slots}]``, shortest first."""
    ids = np.asarray(ids, np.int64)
    order = np.argsort(ids, kind="stable")
    counts = np.bincount(ids, minlength=n_entities)
    starts = np.cumsum(counts) - counts
    present = np.flatnonzero(counts)
    length = 1 << np.ceil(np.log2(counts[present])).astype(np.int64)
    out = []
    for s in np.unique(length):
        ents = present[length == s]
        slot = np.arange(s)[None, :]
        held = slot < counts[ents][:, None]
        at = np.minimum(starts[ents][:, None] + slot, len(order) - 1)
        out.append({"entities": ents, "rows": counts[ents],
                    "index": np.where(held, order[at], -1).astype(np.int32)})
    return out


@functools.partial(jax.jit, static_argnames=("round_to", "pad_weight"))
def _group_block(x, y, offsets, index, *, round_to=None, pad_weight=0.0):
    """A group's dense block: design, labels, offsets, weights. A padded
    slot gathers row 0 and weighs ``pad_weight`` (0, but for the fault that
    counts padded rows)."""
    held = index >= 0
    at = jnp.maximum(index, 0)
    xb = x[at]
    if round_to is not None:
        xb = xb.astype(round_to).astype(jnp.float32)
    return xb, y[at], offsets[at], jnp.where(held, 1.0, pad_weight)


@jax.jit
def _lanes_value_and_grad(xb, yb, ob, wb, w, l2):
    """Every lane's objective ``sum_s weight (softplus(m) - y m) + 0.5 l2
    |w|^2`` with ``m = x.w + offset``, and its gradient."""
    m = jnp.sum(xb * w[:, None, :], axis=-1) + ob
    f = jnp.sum(wb * (jnp.logaddexp(0.0, m) - yb * m), axis=-1)
    r = wb * (jax.nn.sigmoid(m) - yb)
    g = jnp.sum(r[:, :, None] * xb, axis=1)
    return f + 0.5 * l2 * jnp.sum(w * w, axis=-1), g + l2 * w


@jax.jit
def _row_margins(x, w_rows):
    return jnp.sum(x * w_rows, axis=-1)


def margins_of(x, table: np.ndarray, ids: np.ndarray | None = None):
    """Every row's margin ``x_i . w`` (fixed effect) or ``x_i . w[id_i]``."""
    w = jnp.asarray(table, jnp.float32)
    return _row_margins(x, w if ids is None else w[jnp.asarray(ids)])


def total_loss(margins, y) -> float:
    """The sweep's data loss (no regularizer) at the given total margins;
    the sum on the host in float64."""
    per_row = jnp.logaddexp(0.0, margins) - y * margins
    return float(np.sum(np.asarray(per_row, np.float64)))


def evaluate_entities(x, y, offsets, groups, table: np.ndarray, l2: float,
                      **block) -> tuple[np.ndarray, np.ndarray]:
    """``(values, gradient norms)`` of every entity's objective at its row of
    ``table``, indexed by entity (0 for one without rows)."""
    n = table.shape[0]
    values, norms = np.zeros(n), np.zeros(n)
    for grp in groups:
        f, g = _lanes_value_and_grad(
            *_group_block(x, y, offsets, jnp.asarray(grp["index"]), **block),
            jnp.asarray(table[grp["entities"]], jnp.float32), jnp.float32(l2))
        values[grp["entities"]] = np.asarray(f, np.float64)
        norms[grp["entities"]] = np.linalg.norm(
            np.asarray(g, np.float64), axis=1)
    return values, norms


def solve_entities(x, y, offsets, groups, n_entities: int, l2: float,
                   opts: dict, *, skip_odd_lanes: bool = False,
                   **block) -> dict:
    """Every entity solved from zero, a group at a time. Per entity: ``w``,
    ``value``, ``grad_norm``, ``grad0_norm``, ``iterations``, ``converged``,
    and ``has`` (it has rows, so it has coefficients).
    ``skip_odd_lanes`` plants a fault: every second lane of every group is
    left at zero."""
    dim = x.shape[1]
    out = {"w": np.zeros((n_entities, dim)), "value": np.zeros(n_entities),
           "grad_norm": np.zeros(n_entities),
           "grad0_norm": np.zeros(n_entities),
           "iterations": np.zeros(n_entities, np.int64),
           "converged": np.zeros(n_entities, bool),
           "has": np.zeros(n_entities, bool)}
    for grp in groups:
        ents = grp["entities"]
        blk = _group_block(x, y, offsets, jnp.asarray(grp["index"]), **block)
        solved = lbfgs_lanes(
            lambda w, blk=blk: _lanes_value_and_grad(
                *blk, jnp.asarray(w), jnp.float32(l2)),
            np.zeros((len(ents), dim)), **opts)
        if skip_odd_lanes:
            odd = np.arange(len(ents)) % 2 == 1
            f0, _ = _lanes_value_and_grad(
                *blk, jnp.zeros((len(ents), dim), jnp.float32),
                jnp.float32(l2))
            solved["w"][odd] = 0.0
            solved["value"][odd] = np.asarray(f0, np.float64)[odd]
            solved["grad_norm"][odd] = solved["grad0_norm"][odd]
            solved["iterations"][odd] = 0
            solved["converged"][odd] = False
        for key, value in solved.items():
            out[key][ents] = value
        out["has"][ents] = True
    return out


def sweep(data: dict, config: dict, workload: dict, *, round_to=None,
          fault: str | None = None) -> list[dict]:
    """One coordinate-descent sweep from zero: ``[fixed, <random effect>...,
    totals]``, one dict a coordinate in the update sequence and the sweep's
    final margins and loss. ``data``: every feature shard (by its name) and
    ``y`` on the device, the id columns (by their names) on the host.
    ``round_to`` rounds both designs (the lower-precision control); ``fault``
    plants one of ``FAULTS``: ``stale_residual`` (a random effect is trained
    without the scores of the random effect before it), ``half_entities``
    (every second lane of every group left at zero), ``pad_rows_counted`` (a
    padded slot, which gathers row 0, at weight 1), ``stall_after_3`` (every
    solve stops after its third iteration)."""
    opts = lbfgs_options(config["optimizer"])
    if fault == "stall_after_3":
        opts = {**opts, "max_iterations": 3}
    weights = config["regularization_weights"]
    y = data["y"]
    coordinates = config["coordinates"]
    block = {"round_to": round_to,
             "pad_weight": 1.0 if fault == "pad_rows_counted" else 0.0}
    out = []
    fixed_id, *random_ids = config["update_sequence"]
    xf = data[coordinates[fixed_id]["feature_shard"]]
    r = lbfgs(glm.objective(xf, y, weights[fixed_id],
                            chunk=int(workload["row_chunk"]),
                            round_to=round_to),
              np.zeros(xf.shape[1]), **opts)
    out.append({"coordinate": fixed_id, "w": r["w"],
                "value": r["values"][-1], "grad_norm": r["grad_norms"][-1],
                "grad0_norm": r["grad_norms"][0],
                "iterations": len(r["values"]) - 1})
    total = margins_of(xf, r["w"])
    before = total  # the margins before the newest random effect
    for cid in random_ids:
        coordinate = coordinates[cid]
        xi, ids = data[coordinate["feature_shard"]], data[coordinate["entity"]]
        n_entities = int(workload[coordinate["count"]])
        offsets = before if fault == "stale_residual" else total
        solved = solve_entities(
            xi, y, offsets, groups_of(ids, n_entities), n_entities,
            weights[cid], opts, skip_odd_lanes=fault == "half_entities",
            **block)
        out.append({"coordinate": cid, **solved})
        before = total
        total = total + margins_of(xi, solved["w"], ids)
    out.append({"coordinate": "totals",
                "margins": np.asarray(total, np.float32),
                "loss": total_loss(total, y)})
    return out


FAULTS = ("stale_residual", "half_entities", "pad_rows_counted",
          "stall_after_3")
