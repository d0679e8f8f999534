"""GLM objective: value / gradient / Hessian-vector product by autodiff.

TPU-first replacement for the reference's objective-function hierarchy
(``photon-api/.../function/ObjectiveFunction.scala``, ``DiffFunction.scala``,
``TwiceDiffFunction.scala``, ``function/glm/DistributedGLMLossFunction.scala``,
``function/glm/SingleNodeGLMLossFunction.scala`` and the four aggregator
classes ``ValueAndGradientAggregator`` / ``HessianVectorAggregator`` /
``HessianDiagonalAggregator`` / ``HessianMatrixAggregator``).

Design stance (SURVEY.md §7): define only the per-sample pointwise loss and the
(linear) margin model; derive everything else:

- value: ``sum_i weight_i * l(margin_i, label_i) + 0.5 * l2 * ||w_reg||^2``
- gradient: ``jax.grad`` of that pure function,
- Hessian-vector product: ``jax.jvp`` of the gradient — exact for GLMs
  (the margin is linear in ``w``, so forward-over-reverse equals
  ``X^T diag(d2) X v + l2 v``, the quantity TRON needs),
- Hessian diagonal / full matrix (for variance computation): closed-form
  contractions using the loss's ``d2``.

Everything here is a pure function of ``(w, data, l2)`` and safe under
``jit`` / ``vmap`` / ``shard_map``; the distributed ("DistributedGLMLossFunction")
variant is these same functions wrapped in a ``psum`` by
:mod:`photon_ml_tpu.parallel.distributed` — one code path from a single chip
to a pod, replacing the RDD ``treeAggregate`` tree.

Normalization is applied as a coefficient-space reparameterization
(:mod:`photon_ml_tpu.ops.normalization`) — transformed-space margins are
computed on raw features on the fly, never materializing scaled data, matching
the reference's normalization-aware aggregators.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.ops.design import (
    ChunkedSparseDesign,
    CsrDesign,
    DenseDesign,
    Design,
)
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.normalization import NormalizationContext, NoNormalization

Array = jax.Array

logger = logging.getLogger(__name__)


@functools.lru_cache(maxsize=1024)
def _log_declined(kernel: str, reason: str) -> None:
    """A Pallas kernel that was asked for gives way to the XLA closed form:
    say which predicate declined — once per distinct (kernel, reason), so
    once per shape for the shape predicates; the gates run at trace time,
    several times per trace."""
    logger.info("%s declined: %s — XLA closed form", kernel, reason)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GLMData:
    """One batch/shard of labeled GLM data.

    Counterpart of the reference's ``data/LabeledPoint.scala`` collection:
    ``labels`` ``(n,)``, per-sample additive ``offsets`` ``(n,)`` (the residual
    scores that make GAME coordinate descent work), non-negative ``weights``
    ``(n,)``. ``weights`` may also encode padding: a padded row has weight 0
    and contributes exactly nothing to value/grad/Hvp, which is what makes
    fixed-shape bucketing of ragged entity data correct.
    """

    design: Design
    labels: Array
    offsets: Array
    weights: Array

    @property
    def n_samples(self) -> int:
        return self.design.n_samples

    @property
    def dim(self) -> int:
        return self.design.dim

    def with_offsets(self, offsets: Array) -> "GLMData":
        return dataclasses.replace(self, offsets=offsets)


@dataclasses.dataclass(frozen=True)
class GLMObjective:
    """Pure-functional twice-differentiable GLM objective.

    Static configuration only (the pointwise loss, the normalization context,
    and an optional L2 mask); all numeric state flows through arguments so a
    single compilation serves every lambda in a regularization sweep (the
    reference's warm-start sweep in ``ModelTraining.scala``).

    ``reg_mask`` is an optional ``(d,)`` 0/1 vector selecting which
    coefficients the L2 term touches (e.g. to exempt the intercept).
    """

    loss: PointwiseLoss
    normalization: NormalizationContext = NoNormalization
    reg_mask: Optional[Array] = None
    #: use the Pallas fused one-pass value+grad kernel (TPU only; dense
    #: designs with identity normalization — other cases fall back to
    #: autodiff transparently). See photon_ml_tpu/ops/pallas_glm.py.
    fused: bool = False
    #: entity-batched variant of ``fused`` (the random-effect bucket solve):
    #: a batch of lanes runs the single-pass entities-last Pallas kernel
    #: (ops/pallas_re.py), through :meth:`entity_kernel_evaluation` (an
    #: L-BFGS bucket) or a vmap carrying the batch axis on every operand. A
    #: separate switch because eligibility differs — per-entity designs are
    #: small, so the gate is the ENTITY block plan (lane_fits_vmem), not
    #: auto_block_rows over the sample dim. Set by RandomEffectSolver; the
    #: two flags are not meant to be combined.
    fused_entity: bool = False
    #: testing only: run the fused kernel through the Pallas interpreter on
    #: non-TPU backends instead of falling back to the closed form. The
    #: interpreter is orders of magnitude slower than XLA — never in prod.
    fused_interpret: bool = False

    def __post_init__(self):
        # The closed-form paths (reg_curvature, _closed_value_and_grad) and
        # the autodiff of value() agree only for a 0/1 mask: the L2 term is
        # 0.5*l2*||w*mask||², whose true curvature is l2*mask² — equal to
        # the l2*mask the closed forms use iff mask ∈ {0, 1}.
        if self.reg_mask is not None and not isinstance(
                self.reg_mask, jax.core.Tracer):
            import numpy as np

            vals = np.asarray(self.reg_mask)
            if not np.all((vals == 0) | (vals == 1)):
                raise ValueError(
                    "reg_mask must be a 0/1 selector vector; got values "
                    f"outside {{0, 1}}: {vals[(vals != 0) & (vals != 1)][:5]}")

    # --- margins ----------------------------------------------------------
    def margins(self, w: Array, data: GLMData) -> Array:
        w_eff, margin_shift = self.normalization.transform_coefficients(w)
        return data.design.matvec(w_eff) + margin_shift + data.offsets

    # --- objective value --------------------------------------------------
    def _reg_w(self, w: Array) -> Array:
        """Coefficients as seen by the L2 term (reg_mask selects, e.g. to
        exempt the intercept) — single home of the mask semantics."""
        return w if self.reg_mask is None else w * self.reg_mask

    def _l2_term(self, w: Array, l2) -> Array:
        wr = self._reg_w(w)
        return 0.5 * l2 * jnp.vdot(wr, wr)

    def reg_curvature(self, l2):
        """The L2 term's Hessian diagonal — single home of the 0/1-mask
        curvature convention (d²/dw² of 0.5·l2·||w·mask||² = l2·mask for a
        0/1 mask; shared by the distributed wrappers)."""
        return l2 if self.reg_mask is None else l2 * self.reg_mask

    def value(self, w: Array, data: GLMData, l2=0.0) -> Array:
        live = data.weights > 0
        m = self.margins(w, data)
        # Double-where masking: weight-0 padding rows are evaluated at margin
        # 0 (finite) AND zero-weighted. Masking only the output would leave
        # 0 * inf = NaN in the value and — because backprop differentiates the
        # overflowing primal — NaN in the gradient; this is the invariant that
        # makes fixed-shape bucketing of ragged entity data safe.
        m_safe = jnp.where(live, m, 0.0)
        per_sample = self.loss.loss(m_safe, data.labels)
        contrib = jnp.where(live, data.weights * per_sample, 0.0)
        return jnp.sum(contrib) + self._l2_term(w, l2)

    # --- derivatives ------------------------------------------------------
    def _kernel_decline(self, design: Design) -> Optional[str]:
        """What both Pallas kernels need whatever the shape, as the reason
        they cannot serve ``design`` (None when they can): Mosaic lowering
        needs a TPU (tests opt into the interpreter via fused_interpret), a
        dense design, identity normalization."""
        backend = jax.default_backend()
        if backend != "tpu" and not self.fused_interpret:
            return f"backend is {backend!r}, not 'tpu'"
        if not isinstance(design, DenseDesign):
            return f"design is {type(design).__name__}, not DenseDesign"
        if not self.normalization.is_identity:
            return "normalization is not the identity"
        return None

    def _fused_eligible(self, data: GLMData) -> bool:
        """Single home of the fused-kernel gate (shared by value_and_grad,
        hvp_prefers_operator, hvp_operator — they must not drift):
        :meth:`_kernel_decline` plus a no-copy auto block (shapes with no
        tile-aligned dividing block would force the kernel to re-pad the
        full design per evaluation — a net loss vs the closed form). A
        requested kernel that gives way says so (:func:`_log_declined`)."""
        if not self.fused:
            return False
        from photon_ml_tpu.ops.pallas_glm import auto_block_rows

        reason = self._kernel_decline(data.design)
        if reason is None and auto_block_rows(
                data.n_samples, data.design.x.dtype) is None:
            reason = (f"no tile-aligned block divides the "
                      f"{data.n_samples} rows of the ({data.n_samples}, "
                      f"{data.dim}) design (the kernel would re-pad it per "
                      f"evaluation)")
        if reason is not None:
            _log_declined("pallas_glm", reason)
        return reason is None

    def _entity_kernel_serves(self, design: Design, s: int, d: int) -> bool:
        """Gate for the entity-batched kernel (``fused_entity``) on lanes
        of ``s`` samples by ``d`` features — :meth:`_kernel_decline` like
        :meth:`_fused_eligible`, but the shape test is the per-entity VMEM
        plan: the kernel blocks over entities, so ``auto_block_rows`` over
        samples is the wrong question."""
        if not self.fused_entity:
            return False
        from photon_ml_tpu.ops.pallas_re import lane_fits_vmem

        reason = self._kernel_decline(design)
        if reason is None and not lane_fits_vmem(s, d, design.x.dtype):
            reason = (f"a 128-entity block of "
                      f"{jnp.dtype(design.x.dtype).name} ({s}, {d}) lanes "
                      f"exceeds the kernel's VMEM budget")
        if reason is not None:
            _log_declined("pallas_re", reason)
        return reason is None

    def entity_pad(self, x: Array) -> int:
        """Weight-0 lanes the bucket solver appends to an ``(E, S, D)``
        bucket so the entity kernel's block plan divides it — 0 when the
        kernel will not serve the bucket. Asks the gate its lanes will meet
        under the vmap, so the pre-pad and the dispatch cannot disagree."""
        e, s, d = x.shape
        if not self._entity_kernel_serves(DenseDesign(x=x), s, d):
            return 0
        from photon_ml_tpu.ops.pallas_re import entity_pad

        return entity_pad(e, s, d, x.dtype)

    def entity_kernel_evaluation(self, data: GLMData, l2=0.0):
        """The value and gradient of every lane of a batch in one call,
        ``w (d, E) -> (values (E,), grads (d, E))``, lanes last as the flat
        L-BFGS loop holds them (optimize/lbfgs.py), through the entity
        kernel; ``None`` where the gate keeps the closed form for such
        lanes. ``data``'s leaves lead with the lane axis. The kernel's
        operands are laid out here, once: the returned function closes over
        them, so a loop that calls it reads the design once an evaluation
        and copies nothing. A batch the solver has not padded to the block
        plan (:meth:`entity_pad`) is served too, at a pad of ``w`` a call.
        """
        design = data.design
        if not self._entity_kernel_serves(design, data.labels.shape[-1],
                                          design.dim):
            return None
        from photon_ml_tpu.ops.pallas_re import (
            entity_layout,
            entity_value_and_grad_lanes,
        )

        laid = entity_layout(design.x, data.labels, data.offsets,
                             data.weights)
        interpret = jax.default_backend() != "tpu"
        mask = None if self.reg_mask is None else self.reg_mask[:, None]

        def evaluate(w: Array) -> tuple[Array, Array]:
            values, grads = entity_value_and_grad_lanes(
                self.loss, *laid, w, interpret=interpret)
            wr = w if mask is None else w * mask
            reg = jnp.asarray(l2, values.dtype)
            return (values + 0.5 * reg * jnp.sum(wr * wr, axis=0),
                    grads + reg * wr)

        return evaluate

    def value_and_grad(self, w: Array, data: GLMData, l2=0.0) -> tuple[Array, Array]:
        if self._entity_kernel_serves(data.design, data.n_samples,
                                      data.dim):
            from photon_ml_tpu.ops.pallas_re import (
                vmappable_entity_value_and_grad,
            )

            # custom-vmap wrapper: an all-operands vmap (an OWL-QN or TRON
            # bucket's) dispatches the single-pass entity kernel; called
            # unbatched it is the closed form (identical math, one lane)
            vag = vmappable_entity_value_and_grad(
                self.loss, jax.default_backend() != "tpu")
            value, grad = vag(data.design.x, w, data.labels, data.offsets,
                              data.weights)
            l2 = jnp.asarray(l2, value.dtype)
            return (value + self._l2_term(w, l2),
                    grad + l2 * self._reg_w(w))
        if self._fused_eligible(data):
            from photon_ml_tpu.ops.pallas_glm import vmappable_value_and_grad

            # custom-vmap wrapper: a vmap over w alone (the batched lambda
            # sweep) runs the multi-row kernel — one pass over X for all
            # lanes; unbatched calls behave exactly like the plain kernel
            vag = vmappable_value_and_grad(
                self.loss, jax.default_backend() != "tpu")
            value, grad = vag(data.design.x, w, data.labels, data.offsets,
                              data.weights)
            l2 = jnp.asarray(l2, value.dtype)
            return (value + self._l2_term(w, l2),
                    grad + l2 * self._reg_w(w))
        return self._closed_value_and_grad(w, data, l2)

    def _closed_value_and_grad(self, w, data, l2) -> tuple[Array, Array]:
        """Closed-form (value, grad): margins computed ONCE, two passes over
        the design total. ``jax.value_and_grad`` rematerializes the margins
        in the backward pass — a third full pass over X — which costs ~1.5x
        wall-clock in the HBM-bound regime (measured on TPU v5e); GLM
        gradients are simple enough (``g = X'(weight·dl)``) that autodiff
        buys nothing here. Same double-where padding guards as :meth:`value`.

        Normalization enters by chain rule: the transformed column is
        ``f_j·(x_ij − s_j)``, so ``g = f ∘ (Xᵀdl − s·Σdl)`` — no scaled
        design is ever materialized (reference: normalization-aware
        ``ValueAndGradientAggregator.scala``).
        """
        live = data.weights > 0
        m = self.margins(w, data)
        m_safe = jnp.where(live, m, 0.0)
        lvec = self.loss.loss(m_safe, data.labels)
        value = (jnp.sum(jnp.where(live, data.weights * lvec, 0.0))
                 + self._l2_term(w, l2))
        dl = jnp.where(live, data.weights * self.loss.d1(m_safe, data.labels),
                       0.0)
        g = data.design.rmatvec(dl)
        norm = self.normalization
        if norm.shifts is not None:
            g = g - norm.shifts * jnp.sum(dl)
        if norm.factors is not None:
            g = g * norm.factors
        g = g.astype(w.dtype)
        return value, g + jnp.asarray(l2, w.dtype) * self._reg_w(w)

    def grad(self, w: Array, data: GLMData, l2=0.0) -> Array:
        return jax.grad(self.value)(w, data, l2)

    def hvp(self, w: Array, v: Array, data: GLMData, l2=0.0) -> Array:
        """Exact Hessian-vector product. Replaces
        ``HessianVectorAggregator.scala``; feeds TRON's inner CG.
        One-shot form of :meth:`hvp_operator`.
        """
        return self.hvp_operator(w, data, l2)(v)

    def hvp_prefers_operator(self, data: GLMData) -> bool:
        """True when :meth:`hvp_operator` actually buys wall-clock — i.e.
        the fused one-pass Hvp kernel will engage. Forcing the hoisted
        operator form onto the plain closed form measured SLOWER than
        letting XLA's loop-invariant code motion handle the d2 pass
        (1280 ms vs 987 ms on the TRON bench shape), so TRON only asks for
        the operator when the kernel is available."""
        return self._fused_eligible(data)

    def hvp_operator(self, w: Array, data: GLMData, l2=0.0):
        """``v ↦ Hv`` at fixed ``w`` — the shape TRON's inner CG wants.

        The margin-dependent ``d2`` weights are computed ONCE here (one
        pass over the design); each returned product is then a single
        further design traversal: the fused Pallas one-pass kernel on TPU
        for dense identity-normalization objectives, else the closed form
        ``X'ᵀ(d2·(X'v)) + l2·v`` with the normalized column
        ``x'_ij = f_j·(x_ij − s_j)`` expanded by chain rule (autodiff would
        differentiate through ``matvec``, and the backward of a sparse
        gather is the giant scatter the chunked design exists to avoid).
        """
        norm = self.normalization
        d2w = self._d2_weights(w, data)
        reg = jnp.asarray(self.reg_curvature(l2), w.dtype)

        if self._fused_eligible(data):
            from photon_ml_tpu.ops.pallas_glm import fused_hvp

            x = data.design.x
            interpret = jax.default_backend() != "tpu"

            def apply_fused(v: Array) -> Array:
                hv = fused_hvp(x, v, d2w, interpret=interpret)
                return hv.astype(w.dtype) + reg * v

            return apply_fused

        def apply(v: Array) -> Array:
            u = v if norm.factors is None else v * norm.factors
            t = data.design.matvec(u)
            if norm.shifts is not None:
                t = t - jnp.vdot(u, norm.shifts)
            d2t = d2w * t
            hv = data.design.rmatvec(d2t)
            if norm.shifts is not None:
                hv = hv - norm.shifts * jnp.sum(d2t)
            if norm.factors is not None:
                hv = hv * norm.factors
            return hv.astype(w.dtype) + reg * v

        return apply

    # --- closed-form second-order contractions (for variance) -------------
    def _d2_weights(self, w: Array, data: GLMData) -> Array:
        live = data.weights > 0
        m = jnp.where(live, self.margins(w, data), 0.0)
        d2 = self.loss.d2(m, data.labels)
        return jnp.where(live, data.weights * d2, 0.0)

    def hessian_diagonal(self, w: Array, data: GLMData, l2=0.0) -> Array:
        """Diagonal of the Hessian in *transformed* feature space.

        Replaces ``HessianDiagonalAggregator.scala`` (VarianceComputationType
        SIMPLE). Computed as ``sum_i d2_i * x'_ij^2`` via one Hvp-free pass:
        for the dense design it is an einsum; for sparse, a scatter-add of
        squared values.
        """
        d2 = self._d2_weights(w, data)
        design = data.design
        factors = self.normalization.factors

        if isinstance(design, DenseDesign):
            x = design.x
            if self.normalization.shifts is not None:
                x = x - self.normalization.shifts
            if factors is not None:
                x = x * factors
            diag = jnp.einsum("nd,n->d", jnp.square(x), d2,
                              preferred_element_type=jnp.promote_types(x.dtype, jnp.float32))
        elif isinstance(design, (ChunkedSparseDesign, CsrDesign)):
            # Σ_i d2_i (x_ij − s_j)² expands analytically over the sparse
            # pattern: Σ d2 x² − 2 s_j Σ d2 x + s_j² Σ d2, where the first
            # two column sums draw only on stored entries and the last term
            # covers the implicit zeros ((0 − s_j)² = s_j²) for free.
            if isinstance(design, ChunkedSparseDesign):
                sq = design.rmatvec_squared(d2)
            else:
                contrib = jnp.square(design.values) * jnp.take(d2, design.rows)
                sq = jnp.zeros((design.dim,), contrib.dtype).at[design.cols].add(contrib)
            shifts = self.normalization.shifts
            if shifts is None:
                diag = sq
            else:
                lin = design.rmatvec(d2)
                diag = sq - 2.0 * shifts * lin + jnp.square(shifts) * jnp.sum(d2)
            if factors is not None:
                # transformed column is f_j·(x_ij − s_j): factor² scales out
                diag = diag * jnp.square(factors)
        else:
            raise TypeError(type(design))
        return diag + self.reg_curvature(l2)

    def hessian_matrix(self, w: Array, data: GLMData, l2=0.0) -> Array:
        """Full ``(d, d)`` Hessian (VarianceComputationType FULL; replaces
        ``HessianMatrixAggregator.scala``). Only for small ``d`` — the
        reference has the same restriction."""
        if not isinstance(data.design, DenseDesign):
            # Materialize through Hvp columns for sparse designs; the
            # operator form computes the d2 weights once for all columns.
            eye = jnp.eye(data.dim, dtype=w.dtype)
            return jax.vmap(self.hvp_operator(w, data, l2))(eye).T
        d2 = self._d2_weights(w, data)
        x = data.design.x
        if self.normalization.shifts is not None:
            x = x - self.normalization.shifts
        if self.normalization.factors is not None:
            x = x * self.normalization.factors
        h = jnp.einsum("nd,n,ne->de", x, d2, x,
                       preferred_element_type=jnp.promote_types(x.dtype, jnp.float32))
        return h + jnp.diag(jnp.broadcast_to(self.reg_curvature(l2),
                                             (data.dim,)))
