"""Pallas TPU kernel: entity-batched GLM value + gradient in ONE pass over X.

A random-effect bucket is a batch of independent small solves, one lane an
entity, and every L-BFGS evaluation wants each lane's value and gradient
(game/random_effect.py -> glm/problem.py::run_lanes). XLA computes them as
two passes over the bucket's design; this kernel reads it once.

The layout is the whole game: every operand carries the ENTITY axis last, in
the chip's 128-lane dimension: design ``(D, S, E)``, labels / offsets /
weights ``(S, E)``, coefficients and gradient ``(D, E)``, values ``(1, E)``.
A random effect's D is small (8 in the benchmark's cell); with D last, as
the bucket's statics hold it, a tile is 8 lanes of numbers and 120 of
padding, in HBM and in VMEM alike (16 times the bytes: PERF.md, PR 31). With
the entities last every tile is full, a lane's arithmetic is elementwise
across lanes (no reduction over lanes, no relayout, no lane sees another),
and the flat L-BFGS loop, which holds its iterate as ``(d, E)`` for the same
reason (optimize/lbfgs.py), hands it over as it is. S is the sublane axis of
the design, not D: a bfloat16 design's 16-row tile then holds 16 rows of one
column, upcast after the half-width load; all arithmetic is float32 on the
VPU (a lane's contraction is a matvec of its own: no MXU shape).

    per block of BE lanes, per 128 of them, per tile of rows r:
        m[r]     = off[r] + sum_d x[d, r] * w[d]
        val     += wt[r] * loss(m[r], y[r])         (weight-0 rows masked)
        grad[d] += wt[r] * loss'(m[r], y[r]) * x[d, r]
    then one sum over the tile's sublanes for the value and each column.

The layout is made once a solve, outside the optimizer's loop
(:func:`entity_layout`); the solver pads the bucket to the block plan once a
sweep (:func:`entity_pad`), with weight-0 lanes that converge at once.
``entity_plan`` picks the largest multiple-of-128 block whose operands fit
the scoped-VMEM budget; :func:`lane_fits_vmem` is the only thing that decides
whether a shape reaches Mosaic (``GLMObjective._entity_kernel_serves``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.pallas_glm import _out_struct

#: what one grid step may hold in VMEM by :func:`_entity_bytes`' and
#: :func:`_body_bytes`' count: Mosaic's scoped limit for a kernel on the v5e
#: (16 MiB: the kernel passes no ``vmem_limit_bytes``) less a quarter for
#: what the count cannot see.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

#: entity blocks are multiples of this: the entities ride the lane dimension
ENTITY_TILE = 128


def _round_up(n: int, k: int) -> int:
    return -(-n // k) * k


def _row_tile(dtype) -> int:
    """Rows of one sublane tile of a stored design: 8 float32, 16 bfloat16.
    The kernel walks a lane's rows a tile at a time."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _entity_bytes(s: int, d: int, dtype) -> int:
    """VMEM bytes one entity lane costs a grid step: the pipelined operands,
    two buffers each, by Mosaic's tiles with the lanes full. In: the stored
    ``(D, S)`` slab (S padded to the dtype's row tile), the three float32
    ``(S,)`` vectors, the ``(D,)`` coefficients (D padded to 8 sublanes).
    Out: the gradient likewise, and the value's one row in a tile of 8."""
    itemsize = jnp.dtype(dtype).itemsize
    rows = _round_up(max(s, 1), _row_tile(dtype))
    column = 4 * _round_up(max(d, 1), 8)
    return 2 * (rows * d * itemsize + 3 * rows * 4 + 2 * column + 4 * 8)


def _body_bytes(d: int, dtype) -> int:
    """What the body keeps live whatever the block: float32 tiles of one
    row tile by 128 lanes; the ``D`` upcast columns, the ``D`` broadcast
    coefficients and the ``D + 1`` sums, and a dozen for the row's margins,
    loss and masks. In registers where they fit, spilled to VMEM else."""
    return (3 * d + 13) * _row_tile(dtype) * ENTITY_TILE * 4


def entity_plan(e: int, s: int, d: int, dtype) -> "tuple[int, int] | None":
    """``(block_entities, padded_e)`` for an ``(e, s, d)`` bucket, or
    ``None`` when even a minimum (128-entity) block would blow the VMEM
    budget: callers then keep the XLA closed form. Idempotent on its own
    padded size (``entity_plan(padded_e, ...)[1] == padded_e``), which is
    what lets the solver pre-pad once and the kernel re-derive the same
    plan with no further copy."""
    room = VMEM_BUDGET_BYTES - _body_bytes(d, dtype)
    cap = (room // _entity_bytes(s, d, dtype)) // ENTITY_TILE * ENTITY_TILE
    if cap < ENTITY_TILE:
        return None
    be = min(cap, _round_up(max(e, 1), ENTITY_TILE))
    return be, _round_up(max(e, 1), be)


def lane_fits_vmem(s: int, d: int, dtype) -> bool:
    """The E-independent eligibility half of :func:`entity_plan`: the
    per-lane gate ``GLMObjective._entity_kernel_serves`` checks."""
    return entity_plan(ENTITY_TILE, s, d, dtype) is not None


def entity_pad(e: int, s: int, d: int, dtype) -> int:
    """Extra weight-0 entity lanes the SOLVER appends before the batched
    solve so the kernel's block plan divides the batch and the optimizer's
    arrays are as wide as the kernel's."""
    plan = entity_plan(e, s, d, dtype)
    return 0 if plan is None else plan[1] - e


def entity_layout(x, labels, offsets, weights):
    """The kernel's operands ``(x (D, S', E'), labels, offsets, weights
    (S', E'))`` from a bucket's ``(E, S, D)`` design and ``(E, S)`` vectors:
    entities last, E padded to the block plan and S to the design's row tile
    with zeros (weight 0: such rows and lanes count for nothing). One read
    of the design; made once a solve, never inside an optimizer's loop."""
    e, s, d = x.shape
    plan = entity_plan(e, s, d, x.dtype)
    if plan is None:
        raise ValueError(
            f"entity slab ({s}, {d}, {jnp.dtype(x.dtype).name}) exceeds the "
            f"VMEM block budget: the eligibility gate (lane_fits_vmem) "
            f"should have kept the XLA closed form")
    pad_e = plan[1] - e
    pad_s = _round_up(s, _row_tile(x.dtype)) - s
    last = lambda a: jnp.pad(a.astype(jnp.float32),
                             ((0, pad_e), (0, pad_s))).T
    return (jnp.transpose(jnp.pad(x, ((0, pad_e), (0, pad_s), (0, 0))),
                          (2, 1, 0)),
            last(labels), last(offsets), last(weights))


def _kernel(loss: PointwiseLoss, x_ref, y_ref, off_ref, wt_ref, w_ref,
            val_ref, grad_ref):
    d, s, be = x_ref.shape
    rows = _row_tile(x_ref.dtype)
    f32 = jnp.float32
    zero = jnp.zeros((rows, ENTITY_TILE), f32)

    def lanes_128(c, carry):
        lanes = pl.ds(pl.multiple_of(c * ENTITY_TILE, ENTITY_TILE),
                      ENTITY_TILE)
        w = w_ref[:, lanes]  # (D, 128)
        ws = [jnp.broadcast_to(w[k:k + 1], zero.shape) for k in range(d)]

        def row_tile(t, sums):
            val, grad = sums
            r = pl.ds(pl.multiple_of(t * rows, rows), rows)
            # read once, used by both contractions; a bfloat16 design is
            # upcast after the half-width load, all arithmetic is float32
            xs = [x_ref[k, r, lanes].astype(f32) for k in range(d)]
            m = off_ref[r, lanes]
            for k in range(d):
                m = m + xs[k] * ws[k]
            y, wt = y_ref[r, lanes], wt_ref[r, lanes]
            # padded rows carry weight 0: evaluate them at margin 0 (finite)
            # AND zero-weight the output, the double-where guard of
            # GLMObjective.value
            live = wt > 0
            m_safe = jnp.where(live, m, 0.0)
            dvec = jnp.where(live, loss.d1(m_safe, y) * wt, 0.0)
            val = val + jnp.where(live, wt * loss.loss(m_safe, y), 0.0)
            return val, tuple(g + dvec * x for g, x in zip(grad, xs))

        val, grad = lax.fori_loop(0, s // rows, row_tile, (zero, (zero,) * d))
        val_ref[:, lanes] = jnp.sum(val, axis=0, keepdims=True)
        for k in range(d):
            grad_ref[k:k + 1, lanes] = jnp.sum(grad[k], axis=0, keepdims=True)
        return carry

    lax.fori_loop(0, be // ENTITY_TILE, lanes_128, 0)


@functools.partial(jax.jit, static_argnames=("loss", "interpret"))
def entity_value_and_grad_lanes(loss: PointwiseLoss, x, labels, offsets,
                                weights, w, *, interpret: bool = False):
    """``(values (E,), grads (D, E))`` of the per-entity GLM objectives
    ``sum_s weights[s,e] * loss(x[:,s,e] . w[:,e] + offsets[s,e], y[s,e])``
    in ONE pass over the design, on :func:`entity_layout`'s operands and
    coefficients ``w (D, E)`` (no L2: a coefficient-space term, the caller
    adds it). ``x`` is float32 or bfloat16; everything else float32. Where
    ``w`` holds fewer lanes than the laid-out bucket, the bucket's first (a
    caller that did not pad its batch to the block plan), it is padded and
    the results cut at every call; the design is never copied here.
    """
    d, s, e = x.shape
    plan = entity_plan(e, s, d, x.dtype)
    if plan is None or plan[1] != e or s % _row_tile(x.dtype):
        raise ValueError(
            f"({d}, {s}, {e}) {jnp.dtype(x.dtype).name} operands are not "
            f"entity_layout's: block plan {plan}")
    be = plan[0]
    e_w = w.shape[1]
    w = jnp.pad(w, ((0, 0), (0, e - e_w)))
    f32 = jnp.float32
    itemsize = jnp.dtype(x.dtype).itemsize
    vector = pl.BlockSpec((s, be), lambda i: (0, i), memory_space=pltpu.VMEM)
    column = pl.BlockSpec((d, be), lambda i: (0, i), memory_space=pltpu.VMEM)
    values, grads = pl.pallas_call(
        functools.partial(_kernel, loss),
        # the operation's name in a profiler trace, held here so that a
        # refactoring cannot rename what a reader of traces matches
        name="fused_entity_value_and_grad",
        grid=(e // be,),
        in_specs=[
            pl.BlockSpec((d, s, be), lambda i: (0, 0, i),
                         memory_space=pltpu.VMEM),
            vector, vector, vector, column,
        ],
        out_specs=[
            pl.BlockSpec((1, be), lambda i: (0, i), memory_space=pltpu.VMEM),
            column,
        ],
        out_shape=[
            _out_struct(x, (1, e), f32),
            _out_struct(x, (d, e), f32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * e * s * d,
            transcendentals=2 * e * s,
            bytes_accessed=e * (s * (d * itemsize + 3 * 4) + (2 * d + 1) * 4),
        ),
        interpret=interpret,
    )(x, labels, offsets, weights, w.astype(f32))
    return values[0, :e_w], grads[:, :e_w]


def fused_entity_value_and_grad(loss: PointwiseLoss, x, ws, labels, offsets,
                                weights, *, interpret: bool = False):
    """``(values (E,), grads (E, D))`` for a bucket as its statics hold it,
    ``x (E, S, D)``, vectors ``(E, S)``, ``ws (E, D)``: the one kernel, its
    operands laid out at the call. A caller inside an optimizer's loop pays
    that copy of the design at every evaluation: the L-BFGS buckets do not
    come this way (``GLMObjective.entity_kernel_evaluation``)."""
    values, grads = entity_value_and_grad_lanes(
        loss, *entity_layout(x, labels, offsets, weights), ws.T,
        interpret=interpret)
    return values, grads.T


def _closed_one(loss: PointwiseLoss, x, w, labels, offsets, weights):
    """Single-entity closed form — the custom_vmap primal (and its
    sequential fallback body). Mirrors GLMObjective._closed_value_and_grad
    at identity normalization (the eligibility gate guarantees it), so an
    unbatched call through the wrapper is numerically the path the gate
    would otherwise have taken."""
    live = weights > 0
    m = jnp.dot(x, w.astype(x.dtype),
                preferred_element_type=jnp.float32) + offsets
    m_safe = jnp.where(live, m, 0.0)
    lvec = loss.loss(m_safe, labels)
    value = jnp.sum(jnp.where(live, weights * lvec, 0.0))
    dvec = jnp.where(live, weights * loss.d1(m_safe, labels), 0.0)
    grad = jnp.dot(dvec.astype(x.dtype), x,
                   preferred_element_type=jnp.float32)
    return value, grad.astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def vmappable_entity_value_and_grad(loss: PointwiseLoss,
                                    interpret: bool = False):
    """The entity-batched (value, grad) with a custom vmap rule: a vmap
    carrying the batch axis on EVERY operand — an OWL-QN or TRON bucket's
    ``vmap(run)`` — dispatches to the single-pass entity kernel
    (:func:`fused_entity_value_and_grad`); any other combination falls back
    to a sequential lane map of the closed form (no production path hits
    it; the rule must merely stay total)."""

    @jax.custom_batching.custom_vmap
    def vag(x, w, labels, offsets, weights):
        return _closed_one(loss, x, w, labels, offsets, weights)

    @vag.def_vmap
    def _rule(axis_size, in_batched, x, w, labels, offsets, weights):
        xb, wb, lb, ob, wtb = in_batched
        if xb and wb and lb and ob and wtb:
            values, grads = fused_entity_value_and_grad(
                loss, x, w, labels, offsets, weights, interpret=interpret)
            return (values, grads), (True, True)

        def body(i):
            return _closed_one(
                loss, x[i] if xb else x, w[i] if wb else w,
                labels[i] if lb else labels, offsets[i] if ob else offsets,
                weights[i] if wtb else weights)

        values, grads = jax.lax.map(body, jnp.arange(axis_size))
        return (values, grads), (True, True)

    return vag
