"""Distributed GLM objective: ``shard_map`` + ``psum`` over the data axis.

TPU-native replacement for the reference's
``photon-api/.../function/glm/DistributedGLMLossFunction.scala``: where the
reference broadcasts the coefficient vector to executors and reduces
per-partition aggregator arrays through ``RDD.treeAggregate`` (depth 1–2 tree
over netty RPC), here every chip computes its shard's (value, gradient) with
the SAME pure math as the single-chip path and one ``lax.psum`` over ICI
produces the global result — inside the compiled optimizer loop, so a whole
L-BFGS/TRON run is ONE device program with no host round-trips per iteration
(the reference pays a broadcast + treeAggregate per iteration).

Data layout: :func:`shard_glm_data` splits samples into per-device blocks on
host (padding the tail block with weight-0 rows, which contribute exactly
zero), stacks them on a leading mesh-axis dimension, and the objective's
``shard_map`` consumes one block per device. The L2 term is added OUTSIDE the
psum so it is counted once globally, not once per shard.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.ops.design import ChunkedSparseDesign, CsrDesign, DenseDesign
from photon_ml_tpu.ops.objective import GLMData, GLMObjective
from photon_ml_tpu.parallel.mesh import DATA_AXIS, FEATURE_AXIS

Array = jax.Array


def _unstack(tree):
    """Drop the per-device leading axis inside a shard_map body."""
    return jax.tree.map(lambda x: x[0], tree)


def _l2_value_and_grad(objective: GLMObjective, w: Array, l2):
    wr = w if objective.reg_mask is None else w * objective.reg_mask
    l2 = jnp.asarray(l2, w.dtype)
    return 0.5 * l2 * jnp.vdot(wr, wr), l2 * wr


@dataclasses.dataclass(frozen=True)
class ShardBudget:
    """Shared shape budget for building agreeing shard layouts on
    independent hosts (SPMD demands identical leaf shapes on every process;
    a host with more rows or denser data would otherwise stack taller or
    wider blocks). Sparse-only fields are 0 for dense designs ("local
    choice"). Computed per-host via :func:`shard_budget`, max-reduced by
    :func:`photon_ml_tpu.parallel.multihost.allreduce_shard_budget`."""

    rows_per_shard: int
    row_chunk: int = 0
    col_chunk: int = 0
    row_chunks: int = 0  # padded per-block row-major chunk count (mr)
    col_chunks: int = 0  # padded per-block col-major chunk count (mc)

    def to_array(self) -> np.ndarray:
        return np.array([self.rows_per_shard, self.row_chunk, self.col_chunk,
                         self.row_chunks, self.col_chunks], np.int64)

    @staticmethod
    def from_array(a) -> "ShardBudget":
        a = np.asarray(a, np.int64)
        return ShardBudget(*(int(v) for v in a))


def shard_budget(sharded: GLMData) -> ShardBudget:
    """Read back the shape budget a stacked layout was built with, so hosts
    can compare (and max-reduce) theirs before a multi-host feed."""
    per = int(sharded.labels.shape[1])
    design = sharded.design
    if isinstance(design, ChunkedSparseDesign):
        return ShardBudget(
            rows_per_shard=per,
            row_chunk=int(design.rvals.shape[2]),
            col_chunk=int(design.cvals.shape[2]),
            row_chunks=int(design.rvals.shape[1]),
            col_chunks=int(design.cvals.shape[1]))
    return ShardBudget(rows_per_shard=per)


def shard_glm_data(data: GLMData, n_shards: int, *, device_put_mesh: Optional[Mesh] = None,
                   axis: str = DATA_AXIS,
                   budget: Optional[ShardBudget] = None,
                   host_stage: bool = False) -> GLMData:
    """Split a host-resident :class:`GLMData` into ``n_shards`` equal blocks.

    Returns a GLMData whose leaves have a leading ``n_shards`` dimension
    (block i = device i's shard). Sample counts are padded up to a multiple of
    ``n_shards`` with zero-weight rows; a sparse design's nnz budget is padded
    to the max per-block nnz. If ``device_put_mesh`` is given, leaves are
    placed with the leading dim sharded over ``axis`` so each block lives on
    its device (the host→device feed the reference does via Spark partition
    locality). ``host_stage=True`` keeps the leaves as numpy arrays — for
    feeds that do their own host→device transfer (the multihost path), so
    the full local dataset never detours through one device's HBM.
    """
    _j = np.ascontiguousarray if host_stage else jnp.asarray
    n = data.n_samples
    per = math.ceil(n / n_shards)
    if budget is not None:
        if budget.rows_per_shard < per:
            raise ValueError(
                f"budget.rows_per_shard={budget.rows_per_shard} cannot hold "
                f"{n} rows over {n_shards} shards (need ≥ {per})")
        per = budget.rows_per_shard
    n_pad = per * n_shards

    labels = np.zeros((n_pad,), np.asarray(data.labels).dtype)
    labels[:n] = np.asarray(data.labels)
    offsets = np.zeros((n_pad,), np.asarray(data.offsets).dtype)
    offsets[:n] = np.asarray(data.offsets)
    weights = np.zeros((n_pad,), np.asarray(data.weights).dtype)
    weights[:n] = np.asarray(data.weights)

    design = data.design
    from photon_ml_tpu.game.factored import FactoredDesign

    if isinstance(design, DenseDesign):
        x = np.asarray(design.x)
        xp = np.zeros((n_pad, x.shape[1]), x.dtype)
        xp[:n] = x
        sharded_design = DenseDesign(x=_j(xp.reshape(n_shards, per, x.shape[1])))
    elif isinstance(design, FactoredDesign):
        # the factored projection solve's implicit Khatri-Rao design: both
        # row arrays (raw features x, per-sample latents v) stack like a
        # dense design; matvec/rmatvec work per block unchanged
        x = np.asarray(design.x)
        v = np.asarray(design.v)
        xp = np.zeros((n_pad, x.shape[1]), x.dtype)
        xp[:n] = x
        vp = np.zeros((n_pad, v.shape[1]), v.dtype)
        vp[:n] = v
        sharded_design = FactoredDesign(
            x=_j(xp.reshape(n_shards, per, x.shape[1])),
            v=_j(vp.reshape(n_shards, per, v.shape[1])),
            latent_dim=design.latent_dim)
    elif isinstance(design, (CsrDesign, ChunkedSparseDesign)):
        if isinstance(design, ChunkedSparseDesign):
            raise TypeError(
                "shard_glm_data splits by row from COO; pass the host "
                "CsrDesign and the sharded layout is built chunked per block")
        rows = np.asarray(design.rows)
        cols = np.asarray(design.cols)
        vals = np.asarray(design.values)
        block_of = rows // per
        local_row = rows % per
        # per-block chunked layouts (ChunkedSparseDesign: the dual
        # gather+partial-sum form that replaces the big scatters), with
        # common chunk widths and chunk counts padded to the block max so
        # the blocks stack into one leading-device-dim pytree
        live = vals != 0
        # per-BLOCK key counts pick the width: blocks partition rows, so
        # global per-row counts equal per-block ones; columns appear in
        # every block, so count (block, col) pairs — merging across blocks
        # would inflate the medians (and the padding) ~n_shards x
        if budget is not None and budget.row_chunk and budget.col_chunk:
            row_chunk, col_chunk = budget.row_chunk, budget.col_chunk
        else:
            row_chunk = ChunkedSparseDesign.default_chunk(
                np.bincount(rows[live], minlength=n))
            # unique, not bincount: a dense (n_shards * n_cols) count array
            # would be tens of GB in the wide-sparse regime this path
            # serves; default_chunk only looks at nonzero counts anyway
            _, blockcol_counts = np.unique(
                block_of[live] * np.int64(design.n_cols) + cols[live],
                return_counts=True)
            col_chunk = ChunkedSparseDesign.default_chunk(blockcol_counts)
        lays = []
        for b in range(n_shards):
            sel = block_of == b
            lays.append(ChunkedSparseDesign.layout_numpy(
                local_row[sel], cols[sel], vals[sel], per, design.n_cols,
                row_chunk=row_chunk, col_chunk=col_chunk))
        mr = max(lay["rrow"].shape[0] for lay in lays)
        mc = max(lay["ccol"].shape[0] for lay in lays)
        if budget is not None and budget.row_chunks and budget.col_chunks:
            if budget.row_chunks < mr or budget.col_chunks < mc:
                raise ValueError(
                    f"budget chunk counts (mr={budget.row_chunks}, "
                    f"mc={budget.col_chunks}) below this host's layout "
                    f"(mr={mr}, mc={mc}) — compute the budget from the "
                    f"same data")
            mr, mc = budget.row_chunks, budget.col_chunks

        def pad_stack(key, m, fill):
            outs = []
            for lay in lays:
                a = lay[key]
                pad_n = m - a.shape[0]
                if pad_n:
                    pad_block = np.full((pad_n,) + a.shape[1:], fill, a.dtype)
                    a = np.concatenate([a, pad_block])
                outs.append(a)
            return _j(np.stack(outs))

        sharded_design = ChunkedSparseDesign(
            rvals=pad_stack("rvals", mr, 0.0),
            rcols=pad_stack("rcols", mr, 0),
            # pad segment ids with the LAST id so sortedness holds; padded
            # chunks carry value 0 and contribute nothing
            rrow=pad_stack("rrow", mr, max(per - 1, 0)),
            cvals=pad_stack("cvals", mc, 0.0),
            crows=pad_stack("crows", mc, 0),
            ccol=pad_stack("ccol", mc, max(design.n_cols - 1, 0)),
            n_rows=per, n_cols=design.n_cols)
    else:
        raise TypeError(type(design))

    out = GLMData(
        design=sharded_design,
        labels=_j(labels.reshape(n_shards, per)),
        offsets=_j(offsets.reshape(n_shards, per)),
        weights=_j(weights.reshape(n_shards, per)),
    )
    if device_put_mesh is not None:
        sharding = NamedSharding(device_put_mesh, P(axis))
        out = jax.tree.map(lambda x: jax.device_put(x, sharding), out)
    return out


@dataclasses.dataclass(frozen=True)
class DistributedGLMObjective:
    """The fixed-effect objective over a sharded dataset.

    Drop-in for :class:`GLMObjective` (same value / value_and_grad / hvp
    signatures) but ``data`` must be the stacked per-device layout from
    :func:`shard_glm_data`. Feed its closures straight into
    ``minimize_lbfgs/owlqn/tron`` — the optimizers don't know they're driving
    a pod (the reference needed a separate Distributed vs SingleNode class
    hierarchy for this).
    """

    objective: GLMObjective
    mesh: Mesh
    axis: str = DATA_AXIS

    def _global_value_fn(self, blk, l2):
        """Inside a shard_map body: the GLOBAL objective as a function of w.

        The ``psum`` sits INSIDE the differentiated function, so shard_map's
        varying-axis-aware autodiff derives the correct global gradient and
        Hvp (an explicit psum on an inner-autodiff gradient would double-count
        — the cotangent of the replicated ``w`` is already all-reduced). The
        L2 term is added after the psum so it counts once, not per shard.
        """
        data = _unstack(blk)

        def global_value(wv):
            local = self.objective.value(wv, data, 0.0)
            return jax.lax.psum(local, self.axis) + self.objective._l2_term(wv, l2)

        return global_value

    def value_and_grad(self, w: Array, sharded: GLMData, l2=0.0):
        def body(wv, blk):
            # loss-only per shard (closed-form fast path inside), explicit
            # psums: the global gradient is the sum of shard gradients; L2
            # added after so it counts once
            val, g = self.objective.value_and_grad(wv, _unstack(blk), 0.0)
            val = jax.lax.psum(val, self.axis)
            g = jax.lax.psum(g, self.axis)
            l2_val, l2_grad = _l2_value_and_grad(self.objective, wv, l2)
            return val + l2_val, g + l2_grad

        return shard_map(body, mesh=self.mesh,
                         in_specs=(P(), P(self.axis)), out_specs=(P(), P()))(w, sharded)

    def value(self, w: Array, sharded: GLMData, l2=0.0):
        def body(wv, blk):
            return self._global_value_fn(blk, l2)(wv)

        return shard_map(body, mesh=self.mesh,
                         in_specs=(P(), P(self.axis)), out_specs=P())(w, sharded)

    def grad(self, w: Array, sharded: GLMData, l2=0.0):
        return self.value_and_grad(w, sharded, l2)[1]

    def hvp(self, w: Array, v: Array, sharded: GLMData, l2=0.0):
        # closed form per shard for every normalization (GLMObjective.hvp
        # expands the affine transform by chain rule; autodiff's gather
        # backward would re-create the per-nnz scatter the chunked sparse
        # layout exists to avoid), psum'd; L2 curvature added once outside
        def body(wv, tangent, blk):
            local = self.objective.hvp(wv, tangent, _unstack(blk), 0.0)
            return jax.lax.psum(local, self.axis)

        hv = shard_map(body, mesh=self.mesh,
                       in_specs=(P(), P(), P(self.axis)),
                       out_specs=P())(w, v, sharded)
        return hv + jnp.asarray(self.objective.reg_curvature(l2),
                                w.dtype) * v

    # NOTE no hvp_operator here, deliberately: single-chip measurement
    # showed force-hoisting the plain closed form out of TRON's CG loop is
    # SLOWER than XLA's own loop-invariant code motion (1280 ms vs 987 ms
    # on the bench shape), so distributed TRON stays on the per-call hvp
    # above. That per-call hvp still gets the fused one-pass Pallas Hvp
    # kernel INSIDE the shard_map body when the wrapped objective is
    # fused-eligible — validated on-chip through a mesh: dp TRON 1295 ms →
    # 675 ms (1.9x), identical objective value (XLA hoists the d2 pass out
    # of the CG loop; the kernel halves each product's design traffic).

    def margins(self, w: Array, sharded: GLMData) -> Array:
        """Per-sample margins in the stacked (n_shards, per) layout."""
        def local(wv, blk):
            return self.objective.margins(wv, _unstack(blk))[None, :]

        return shard_map(local, mesh=self.mesh,
                         in_specs=(P(), P(self.axis)), out_specs=P(self.axis))(w, sharded)

    # --- second-order contractions (variance computation) ------------------
    def _psum_of_local(self, fn_name: str, w: Array, sharded: GLMData):
        """psum of a per-shard l2-free contraction; L2 added once outside."""
        def body(wv, blk):
            local = getattr(self.objective, fn_name)(wv, _unstack(blk), 0.0)
            return jax.lax.psum(local, self.axis)

        return shard_map(body, mesh=self.mesh,
                         in_specs=(P(), P(self.axis)), out_specs=P())(w, sharded)

    def hessian_diagonal(self, w: Array, sharded: GLMData, l2=0.0) -> Array:
        """Distributed VarianceComputationType SIMPLE (the reference's
        ``HessianDiagonalAggregator`` treeAggregate)."""
        diag = self._psum_of_local("hessian_diagonal", w, sharded)
        return diag + self.objective.reg_curvature(l2)

    def hessian_matrix(self, w: Array, sharded: GLMData, l2=0.0) -> Array:
        """Distributed VarianceComputationType FULL
        (``HessianMatrixAggregator``)."""
        h = self._psum_of_local("hessian_matrix", w, sharded)
        d = w.shape[0]
        return h + jnp.diag(jnp.broadcast_to(
            jnp.asarray(self.objective.reg_curvature(l2)), (d,)))


# ---------------------------------------------------------------------------
# Feature-dimension (tensor-parallel) sharding
# ---------------------------------------------------------------------------


def shard_glm_data_features(data: GLMData, n_shards: int, *,
                            device_put_mesh: Optional[Mesh] = None,
                            axis: str = FEATURE_AXIS) -> tuple[GLMData, int]:
    """Split a :class:`GLMData`'s FEATURE dimension into ``n_shards`` blocks.

    The TP analog of :func:`shard_glm_data` (SURVEY.md §2.10 "TP" row — no
    reference equivalent: breeze held the whole coefficient vector on the
    Spark driver; sharding the feature dim is what lets a fixed-effect model
    outgrow one chip's HBM). Returns ``(sharded, d_pad)`` where ``d_pad`` is
    the feature dim padded to a multiple of ``n_shards``; solve in the padded
    dim (padded columns are all-zero → their coefficients stay exactly 0) and
    slice the model back to ``data.dim``.

    Layouts: dense → ``x`` padded to ``(n, d_pad)``, columns split by the
    mesh axis at shard_map time; sparse → nnz triplets partitioned by column
    block into a stacked ``(n_shards, budget)`` layout with block-local
    column ids.
    """
    d = data.dim
    per = math.ceil(d / n_shards)
    d_pad = per * n_shards

    design = data.design
    if isinstance(design, DenseDesign):
        x = np.asarray(design.x)
        xp = np.zeros((x.shape[0], d_pad), x.dtype)
        xp[:, :d] = x
        sharded_design = DenseDesign(x=jnp.asarray(xp))
        spec = P(None, axis)
    elif isinstance(design, CsrDesign):
        rows = np.asarray(design.rows)
        cols = np.asarray(design.cols)
        vals = np.asarray(design.values)
        block_of = cols // per
        local_col = cols % per
        counts = np.bincount(block_of, minlength=n_shards)
        budget = int(counts.max()) if counts.size else 0
        r = np.zeros((n_shards, budget), np.int32)
        c = np.zeros((n_shards, budget), np.int32)
        v = np.zeros((n_shards, budget), vals.dtype)
        for b in range(n_shards):
            sel = block_of == b
            k = int(counts[b])
            r[b, :k] = rows[sel]
            c[b, :k] = local_col[sel]
            v[b, :k] = vals[sel]
        sharded_design = CsrDesign(
            rows=jnp.asarray(r), cols=jnp.asarray(c), values=jnp.asarray(v),
            n_rows=design.n_rows, n_cols=per)
        spec = P(axis)
    else:
        raise TypeError(type(design))

    out = GLMData(design=sharded_design, labels=jnp.asarray(data.labels),
                  offsets=jnp.asarray(data.offsets),
                  weights=jnp.asarray(data.weights))
    if device_put_mesh is not None:
        dspec = {"design": spec, "rest": P()}
        out = GLMData(
            design=jax.tree.map(
                lambda a: jax.device_put(
                    a, NamedSharding(device_put_mesh, dspec["design"])),
                sharded_design),
            labels=jax.device_put(out.labels, NamedSharding(device_put_mesh, P())),
            offsets=jax.device_put(out.offsets, NamedSharding(device_put_mesh, P())),
            weights=jax.device_put(out.weights, NamedSharding(device_put_mesh, P())),
        )
    return out, d_pad


@dataclasses.dataclass(frozen=True)
class FeatureShardedGLMObjective:
    """Fixed-effect objective with the COEFFICIENT dimension sharded (TP).

    Drop-in for :class:`GLMObjective` over data from
    :func:`shard_glm_data_features`: ``w`` stays replicated from the
    optimizer's point of view (so L-BFGS/OWLQN/TRON run unchanged), but each
    device touches only its feature block — one ``psum`` of the partial
    margins over the ``feature`` axis per evaluation, one ``psum`` to
    assemble the (block-disjoint) gradient. Identity normalization only (the
    normalization reparameterization is a per-feature transform; fold it
    into the data before sharding).
    """

    objective: GLMObjective
    mesh: Mesh
    axis: str = FEATURE_AXIS

    def __post_init__(self):
        if not self.objective.normalization.is_identity:
            raise ValueError(
                "feature-sharded objective requires identity normalization; "
                "pre-transform the design instead")

    # --- per-device helpers -------------------------------------------------
    # Derivatives are CLOSED-FORM here (g = X'(weight*dl), Hv = X'(d2*weight*Xv))
    # rather than autodiff-through-psum: transposing a psum whose operand the
    # varying-axis system cannot prove device-varying re-psums the (replicated)
    # cotangent — an axis-size-fold overcount. The hand-written form needs one
    # margin psum forward and one gradient psum back, nothing subtle.

    def _local(self, blk: GLMData) -> GLMData:
        return blk if isinstance(blk.design, DenseDesign) else \
            dataclasses.replace(blk, design=_unstack(blk.design))

    def _w_local(self, data: GLMData, w_full: Array) -> Array:
        per = data.design.dim
        idx = jax.lax.axis_index(self.axis)
        return jax.lax.dynamic_slice_in_dim(w_full, idx * per, per)

    def _margins_local(self, data: GLMData, w_full: Array) -> Array:
        partial = data.design.matvec(self._w_local(data, w_full))
        return jax.lax.psum(partial, self.axis) + data.offsets

    def _scatter_block(self, data: GLMData, g_local: Array, d_full: int) -> Array:
        """Place this device's block gradient at its offset in a (d_full,)
        zero vector; the caller's psum then assembles disjoint blocks."""
        per = data.design.dim
        idx = jax.lax.axis_index(self.axis)
        z = jnp.zeros((d_full,), g_local.dtype)
        return jax.lax.dynamic_update_slice_in_dim(z, g_local, idx * per, 0)

    def _masked(self, w: Array) -> Array:
        mask = self.objective.reg_mask
        if mask is None:
            return w
        if mask.shape[0] < w.shape[0]:  # pad mask to the padded dim
            mask = jnp.pad(mask, (0, w.shape[0] - mask.shape[0]))
        return w * mask

    def _l2_value(self, w: Array, l2) -> Array:
        wr = self._masked(w)
        return 0.5 * jnp.asarray(l2, w.dtype) * jnp.vdot(wr, wr)

    def _l2_parts(self, w: Array, l2):
        wr = self._masked(w)
        l2 = jnp.asarray(l2, w.dtype)
        return 0.5 * l2 * jnp.vdot(wr, wr), l2 * wr

    def _design_spec(self, sharded: GLMData):
        if isinstance(sharded.design, DenseDesign):
            return DenseDesign(x=P(None, self.axis))
        return CsrDesign(rows=P(self.axis), cols=P(self.axis),
                         values=P(self.axis),
                         n_rows=sharded.design.n_rows,
                         n_cols=sharded.design.n_cols)

    def _data_spec(self, sharded: GLMData) -> GLMData:
        return GLMData(design=self._design_spec(sharded), labels=P(),
                       offsets=P(), weights=P())

    def value_and_grad(self, w: Array, sharded: GLMData, l2=0.0):
        d_full = w.shape[0]

        def body(wv, blk):
            data = self._local(blk)
            m = self._margins_local(data, wv)
            live = data.weights > 0
            m_safe = jnp.where(live, m, 0.0)
            val = jnp.sum(jnp.where(
                live, data.weights * self.objective.loss.loss(m_safe, data.labels),
                0.0))
            dl = jnp.where(live,
                           data.weights * self.objective.loss.d1(m_safe, data.labels),
                           0.0)
            g_local = data.design.rmatvec(dl.astype(wv.dtype))
            g = jax.lax.psum(self._scatter_block(data, g_local, d_full), self.axis)
            return val, g

        val, g = shard_map(body, mesh=self.mesh,
                           in_specs=(P(), self._data_spec(sharded)),
                           out_specs=(P(), P()), check_vma=False)(w, sharded)
        l2_val, l2_grad = self._l2_parts(w, l2)
        return val + l2_val, g + l2_grad

    def value(self, w: Array, sharded: GLMData, l2=0.0):
        def body(wv, blk):
            data = self._local(blk)
            m = self._margins_local(data, wv)
            live = data.weights > 0
            m_safe = jnp.where(live, m, 0.0)
            return jnp.sum(jnp.where(
                live, data.weights * self.objective.loss.loss(m_safe, data.labels),
                0.0))

        val = shard_map(body, mesh=self.mesh,
                        in_specs=(P(), self._data_spec(sharded)),
                        out_specs=P(), check_vma=False)(w, sharded)
        return val + self._l2_value(w, l2)

    def grad(self, w: Array, sharded: GLMData, l2=0.0):
        return self.value_and_grad(w, sharded, l2)[1]

    def hvp(self, w: Array, v: Array, sharded: GLMData, l2=0.0):
        d_full = w.shape[0]

        def body(wv, tangent, blk):
            data = self._local(blk)
            m = self._margins_local(data, wv)
            xv = self._margins_local(
                dataclasses.replace(data, offsets=jnp.zeros_like(data.offsets)),
                tangent)
            live = data.weights > 0
            m_safe = jnp.where(live, m, 0.0)
            d2 = jnp.where(live,
                           data.weights * self.objective.loss.d2(m_safe, data.labels),
                           0.0)
            hv_local = data.design.rmatvec((d2 * xv).astype(wv.dtype))
            return jax.lax.psum(
                self._scatter_block(data, hv_local, d_full), self.axis)

        hv = shard_map(body, mesh=self.mesh,
                       in_specs=(P(), P(), self._data_spec(sharded)),
                       out_specs=P(), check_vma=False)(w, v, sharded)
        return hv + jnp.asarray(l2, w.dtype) * self._masked(v)

    def margins(self, w: Array, sharded: GLMData) -> Array:
        def body(wv, blk):
            data = self._local(blk)
            return self._margins_local(data, wv)

        return shard_map(body, mesh=self.mesh,
                         in_specs=(P(), self._data_spec(sharded)),
                         out_specs=P(), check_vma=False)(w, sharded)
