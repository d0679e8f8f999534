"""Pallas TPU kernel: entity-batched GLM value + gradient in ONE pass over X.

The random-effect bucket solve is ``vmap(solve_one)`` over entity lanes of
an ``(E, S, D)`` design block (game/random_effect.py). Under vmap, XLA
computes each L-BFGS evaluation's value and gradient as two passes over the
block — batched margins (``einsum esd,ed->es``) then the transposed batched
gradient (``einsum es,esd->ed``) — so the HBM-dominant payload is read
twice per optimizer evaluation, exactly the double-read
:mod:`photon_ml_tpu.ops.pallas_glm` eliminated for the fixed effect (1.36x
f32, ~1.95x bf16 on TPU v5e). This kernel is the vmapped-entity
generalization of that module's ``fused_value_and_grad_multi`` shape:
stream a block of whole entity slabs through VMEM once and compute margins,
weighted loss, AND per-entity gradients while the slab is resident:

    per entity block i (BE entities):
        m[e, s]  = Σ_d x[e, s, d]·w[e, d] + off[e, s]   (VPU lane reduce)
        val[e]   = Σ_s wt[e, s]·loss(m, y)[e, s]        (VPU)
        grad[e,d]= Σ_s dvec[e, s]·x[e, s, d]            (VPU sublane reduce)

Formulation notes (why no MXU): each entity's contraction is an
independent (S, D)·(D,) matvec — a block-diagonal batched matmul the MXU
has no single-program shape for. The M=1 matvec form already leaves
127/128 MXU rows idle in the fixed-effect kernel (its measured issue
wall), and random-effect dims are small (D is the per-entity local dim,
typically 4–64, padded to one 128-lane tile), so the rank-3
multiply-and-reduce on the VPU meets the HBM stream at full rate while
the slab is read exactly once. Blocks keep the arrays' own trailing dims —
x ``(BE, S, D)``, vectors ``(BE, S)``, coefficients ``(BE, D)`` — so no
operand is copied to be blocked; inside the body the two contractions do
move S between sublanes (in x) and lanes (in the vectors), relayouts
Mosaic (libtpu 0.0.34) compiles at every shape tried and whose cost is not
measured. f32 math runs
on the VPU at full f32 precision — no MXU bf16-pass caveat, no
``Precision.HIGHEST`` needed; bf16 designs are upcast register-side after
the half-width DMA (the whole point of storing the design bf16).

Per-entity outputs land in their own block rows (no cross-step
accumulation), so grid steps are independent and Pallas double-buffers the
slab DMAs across steps.

Block selection: ``entity_plan`` picks the largest multiple-of-8 entity
block whose operands and temporaries (``_entity_bytes``) fit the
scoped-VMEM budget. Entity counts rarely
divide it, and padding the batch INSIDE the traced objective would copy
the full (E, S, D) design on every L-BFGS evaluation (the measured
regression that shaped pallas_glm's auto mode) — so the SOLVER pre-pads
the bucket once per solve with weight-0 lanes (``entity_pad``), the
kernel's own pad path exists only as a correctness backstop, and padded
lanes converge immediately (zero data ⇒ gradient = L2 at w0=0 = 0).

Engagement: ``GLMObjective(fused_entity=True)`` (set by
``RandomEffectSolver(fused=True)``, the default) dispatches here through a
``custom_vmap`` rule when EVERY operand carries the entity batch axis —
the bucket-solve shape. Any other batching combination, projected or
streaming datasets, and non-TPU backends (without the test-only
interpreter flag) fall back to the XLA closed form transparently.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.pallas_glm import _out_struct

#: what one grid step may hold in VMEM by :func:`_entity_bytes`' count:
#: Mosaic's scoped limit for a kernel on the v5e (16 MiB — the kernel passes
#: no ``vmem_limit_bytes``) less a quarter for what the count cannot see
#: (relayout buffers, spills). The count is an upper bound — Mosaic streams
#: most elementwise temporaries through registers: cross-compiled for a v5e
#: at 16 lane shapes x f32/bf16 (PR 21), every planned block compiled, and
#: so did twice the planned block.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

#: entity blocks are multiples of this: the f32 vector/output blocks
#: ``(BE, S)`` / ``(BE, D)`` carry BE in the sublane dim, whose Mosaic
#: tile is 8 for f32 (the x slab's BE rides an untiled leading dim)
ENTITY_TILE = 8


def _round_up(n: int, k: int) -> int:
    return -(-n // k) * k


def _entity_bytes(s: int, d: int, dtype) -> int:
    """VMEM bytes one entity lane costs a grid step, tile padding included
    — everything :func:`_kernel` holds for it, so the plan and the compiler
    agree:

    - the pipelined operands, two buffers each: the stored ``(S, D)`` slab
      (S padded to the dtype's sublane tile, D to 128-wide lane tiles), the
      three f32 ``(S,)`` label/offset/weight vectors and the coefficient
      row in, the value and gradient rows out;
    - the body's slab-sized f32 temporaries: the two rank-3 products
      (``xf * w[:, None, :]``, ``dvec[:, :, None] * xf``) and, for a bf16
      design, the upcast ``xf`` itself (an f32 design is used as loaded);
    - the body's vector-sized f32 temporaries: margins, their masked copy,
      loss, derivative, weighted loss and the live mask.
    """
    itemsize = jnp.dtype(dtype).itemsize
    d_pad = _round_up(max(d, 1), 128)
    slab_stored = _round_up(max(s, 1), 8 * 4 // itemsize) * d_pad * itemsize
    slab_f32 = _round_up(max(s, 1), 8) * d_pad * 4
    vector = 4 * _round_up(max(s, 1), 128)
    row = 4 * d_pad
    operands = slab_stored + 3 * vector + 2 * row + 4 * 128  # + value row
    n_slab_temps = 2 if itemsize == 4 else 3
    return 2 * operands + n_slab_temps * slab_f32 + 6 * vector


def entity_plan(e: int, s: int, d: int, dtype) -> "tuple[int, int] | None":
    """``(block_entities, padded_e)`` for an ``(e, s, d)`` bucket, or
    ``None`` when even a minimum (8-entity) block would blow the VMEM
    budget — callers then keep the XLA closed form (nothing else decides
    whether a shape reaches Mosaic). Idempotent on its own
    padded size (``entity_plan(padded_e, ...)[1] == padded_e``), which is
    what lets the solver pre-pad once and the kernel re-derive the same
    plan with zero further copies."""
    per = _entity_bytes(s, d, dtype)
    cap = (VMEM_BUDGET_BYTES // per) // ENTITY_TILE * ENTITY_TILE
    if cap < ENTITY_TILE:
        return None
    be = min(cap, _round_up(max(e, 1), ENTITY_TILE))
    return be, _round_up(max(e, 1), be)


def lane_fits_vmem(s: int, d: int, dtype) -> bool:
    """The E-independent eligibility half of :func:`entity_plan` — the
    per-lane gate ``GLMObjective._entity_kernel_serves`` checks (under
    vmap the objective sees one (S, D) lane, never the batch size)."""
    return entity_plan(ENTITY_TILE, s, d, dtype) is not None


def entity_pad(e: int, s: int, d: int, dtype) -> int:
    """Extra weight-0 entity lanes the SOLVER should append before the
    batched solve so the kernel's block plan divides the batch — padding
    inside the traced objective instead would copy the full design every
    L-BFGS evaluation (see module docstring)."""
    plan = entity_plan(e, s, d, dtype)
    return 0 if plan is None else plan[1] - e


def _kernel(loss: PointwiseLoss, x_ref, y_ref, off_ref, wt_ref, w_ref,
            val_ref, grad_ref):
    x = x_ref[:]  # (BE, S, D) — read once, used by both contractions
    w = w_ref[:]  # (BE, D) f32
    y = y_ref[:]  # (BE, S) f32
    off = off_ref[:]
    wt = wt_ref[:]
    # bf16 designs upcast register-side after the half-width DMA; all math
    # is f32 on the VPU (exact — no MXU single-bf16-pass precision caveat)
    xf = x.astype(jnp.float32)
    m = jnp.sum(xf * w[:, None, :], axis=2) + off  # (BE, S)
    # padded rows carry weight 0: evaluate them at margin 0 (finite) AND
    # zero-weight the output — the double-where guard of GLMObjective.value
    live = wt > 0
    m_safe = jnp.where(live, m, 0.0)
    lvec = loss.loss(m_safe, y)
    dvec = jnp.where(live, loss.d1(m_safe, y) * wt, 0.0)
    val_ref[:] = jnp.sum(jnp.where(live, wt * lvec, 0.0),
                         axis=1).reshape(-1, 1)  # (BE, 1)
    grad_ref[:] = jnp.sum(dvec[:, :, None] * xf, axis=1)  # (BE, D)


@functools.partial(jax.jit,
                   static_argnames=("loss", "block_entities", "interpret"))
def fused_entity_value_and_grad(loss: PointwiseLoss, x, ws, labels, offsets,
                                weights, *, block_entities: int | None = None,
                                interpret: bool = False):
    """``(values (E,), grads (E, D))`` of the per-entity GLM objectives
    ``Σ_s weights[e,s]·loss(x[e,s]·w[e] + offsets[e,s], y[e,s])`` in ONE
    pass over the ``(E, S, D)`` design (no L2 — coefficient-space term,
    the caller adds it). ``x`` is f32 or bf16; everything else f32.
    """
    e, s, d = x.shape
    if block_entities is None:
        plan = entity_plan(e, s, d, x.dtype)
        if plan is None:
            raise ValueError(
                f"entity slab ({s}, {d}, {jnp.dtype(x.dtype).name}) exceeds "
                f"the VMEM block budget — the eligibility gate "
                f"(lane_fits_vmem) should have kept the XLA closed form")
        be, e_pad = plan
    else:
        be = _round_up(block_entities, ENTITY_TILE)
        e_pad = _round_up(max(e, 1), be)
    if e_pad != e:
        # correctness backstop only — the solver pre-pads (entity_pad) so
        # this copy never runs inside a production optimizer loop
        pad = e_pad - e
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
        labels = jnp.pad(labels, ((0, pad), (0, 0)))
        offsets = jnp.pad(offsets, ((0, pad), (0, 0)))
        weights = jnp.pad(weights, ((0, pad), (0, 0)))
        ws = jnp.pad(ws, ((0, pad), (0, 0)))

    f32 = jnp.float32
    itemsize = jnp.dtype(x.dtype).itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, loss),
        # the operation's name in a profiler trace, held here so that a
        # refactoring cannot rename what a reader of traces matches
        name="fused_entity_value_and_grad",
        grid=(e_pad // be,),
        in_specs=[
            pl.BlockSpec((be, s, d), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((be, s), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((be, s), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((be, s), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((be, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((be, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((be, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _out_struct(x, (e_pad, 1), f32),
            _out_struct(x, (e_pad, d), f32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * e_pad * s * d,
            transcendentals=2 * e_pad * s,
            bytes_accessed=e_pad * s * d * itemsize,
        ),
        interpret=interpret,
    )(
        x,
        labels.astype(f32),
        offsets.astype(f32),
        weights.astype(f32),
        ws.astype(f32),
    )
    values, grads = out
    return values[:e, 0], grads[:e]


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
    carrying the batch axis on EVERY operand — the random-effect bucket
    solve's ``vmap(solve_one)`` shape — dispatches to the single-pass
    entity kernel; any other combination falls back to a sequential lane
    map of the closed form (no production path hits it; the rule must
    merely stay total)."""

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
