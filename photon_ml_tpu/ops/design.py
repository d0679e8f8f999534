"""Design-matrix abstractions: dense tiles and padded-COO sparse batches.

The reference stores every sample as a breeze ``SparseVector`` and computes
per-sample dot products in JVM loops
(``photon-api/.../data/LabeledPoint.scala`` +
``function/glm/ValueAndGradientAggregator.scala``). TPUs want the opposite:
large, fixed-shape, batched contractions that XLA can tile onto the MXU.

Three representations, all jit/vmap-safe pytrees:

- :class:`DenseDesign` — an ``(n, d)`` matrix; margins are one matmul. Right
  choice whenever ``d`` is modest (a1a's 123 features) or data is dense after
  bucketing. The matmul rides the MXU; optionally stored bfloat16.
- :class:`CsrDesign` — padded COO triplets ``(rows, cols, values)`` of a fixed
  nnz budget; margins via ``segment_sum`` and the gradient transpose via a
  scatter-add, both XLA-native. Padding entries carry ``value = 0`` so they
  contribute nothing to either pass. Right choice for the reference's
  sparse-feature regime (millions of features, ~hundreds of nnz/row) —
  superseded on TPU by :class:`ChunkedSparseDesign` (below), which replaces
  both per-nnz ops with gathers + chunk partial sums; CsrDesign remains the
  COO container/reference implementation.

Autodiff through ``matvec`` gives the gradient/Hvp aggregation for free —
XLA transposes a matmul into a matmul and a gather into a scatter — which is
what deletes the reference's four hand-written aggregator classes per loss.
"""

from __future__ import annotations

import dataclasses
from typing import Union

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseDesign:
    """Dense ``(n, d)`` design matrix."""

    x: Array

    @property
    def n_samples(self) -> int:
        return self.x.shape[-2]

    @property
    def dim(self) -> int:
        return self.x.shape[-1]

    def matvec(self, w: Array) -> Array:
        """Margins ``X @ w``, accumulated in at least f32 (bf16 storage still
        gets f32 accumulation on the MXU; f64 inputs keep f64)."""
        acc = jnp.promote_types(self.x.dtype, jnp.float32)
        return jnp.einsum("...nd,...d->...n", self.x, w,
                          preferred_element_type=acc)

    def rmatvec(self, g: Array) -> Array:
        acc = jnp.promote_types(self.x.dtype, jnp.float32)
        return jnp.einsum("...nd,...n->...d", self.x, g,
                          preferred_element_type=acc)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CsrDesign:
    """Fixed-nnz padded COO sparse design (TPU-friendly CSR replacement).

    ``rows``/``cols`` are int32 ``(nnz,)``; ``values`` float ``(nnz,)``.
    Padding entries must have ``values == 0`` (rows/cols may point anywhere
    in-range). ``n_samples``/``dim`` are static ints so shapes stay fixed
    under jit.
    """

    rows: Array
    cols: Array
    values: Array
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    n_cols: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_samples(self) -> int:
        return self.n_rows

    @property
    def dim(self) -> int:
        return self.n_cols

    def matvec(self, w: Array) -> Array:
        # Accumulate in at least f32 (bf16 values would otherwise accumulate
        # hundreds of nnz/row in 8-bit mantissa); f64 inputs keep f64.
        acc = jnp.promote_types(jnp.promote_types(self.values.dtype, w.dtype),
                                jnp.float32)
        contrib = (self.values * jnp.take(w, self.cols, axis=0)).astype(acc)
        return jax.ops.segment_sum(contrib, self.rows, num_segments=self.n_rows)

    def rmatvec(self, g: Array) -> Array:
        acc = jnp.promote_types(jnp.promote_types(self.values.dtype, g.dtype),
                                jnp.float32)
        contrib = (self.values * jnp.take(g, self.rows, axis=0)).astype(acc)
        return jnp.zeros((self.n_cols,), dtype=acc).at[self.cols].add(contrib)

    @staticmethod
    def from_scipy(sp_matrix, *, nnz_pad: int | None = None, dtype=np.float32) -> "CsrDesign":
        """Build from a scipy.sparse matrix, padding nnz up to ``nnz_pad``."""
        coo = sp_matrix.tocoo()
        nnz = coo.nnz
        pad = (nnz if nnz_pad is None else nnz_pad) - nnz
        if pad < 0:
            raise ValueError(f"nnz_pad {nnz_pad} < actual nnz {nnz}")
        rows = np.concatenate([coo.row.astype(np.int32), np.zeros(pad, np.int32)])
        cols = np.concatenate([coo.col.astype(np.int32), np.zeros(pad, np.int32)])
        vals = np.concatenate([coo.data.astype(dtype), np.zeros(pad, dtype)])
        return CsrDesign(
            rows=jnp.asarray(rows), cols=jnp.asarray(cols), values=jnp.asarray(vals),
            n_rows=int(sp_matrix.shape[0]), n_cols=int(sp_matrix.shape[1]),
        )


def _chunk_sorted(keys: np.ndarray, payload_idx: np.ndarray, n_keys: int,
                  chunk: int) -> tuple[np.ndarray, np.ndarray]:
    """Chunk entries sorted by ``keys`` into fixed-width groups per key.

    Returns ``(gather, chunk_key)``: ``gather`` is an ``(M, chunk)`` int64 index into
    the payload (−1 = padding slot), ``chunk_key`` ``(M,)`` the key id of
    each chunk. A key with k entries occupies ceil(k/chunk) chunks.
    """
    counts = np.bincount(keys, minlength=n_keys)
    present = np.flatnonzero(counts)
    n_chunks_per = -(-counts[present] // chunk)
    total = int(n_chunks_per.sum())
    chunk_key = np.repeat(present, n_chunks_per).astype(np.int32)
    # entry positions: within-key offset → (chunk row, slot)
    starts = np.zeros(len(present) + 1, np.int64)
    np.cumsum(counts[present], out=starts[1:])
    chunk_starts = np.zeros(len(present) + 1, np.int64)
    np.cumsum(n_chunks_per, out=chunk_starts[1:])
    within = np.arange(len(keys)) - np.repeat(starts[:-1], counts[present])
    chunk_row = np.repeat(chunk_starts[:-1], counts[present]) + within // chunk
    slot = within % chunk
    gather = np.full((total, chunk), -1, np.int64)
    gather[chunk_row, slot] = payload_idx
    return gather, chunk_key


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ChunkedSparseDesign:
    """Dual chunked-COO sparse design: scatters shrunk by chunk partial sums.

    Motivation (measured on a TPU v5e before PR 1, 12.8M nnz, d=100k; not
    measured on the present chip):
    ``CsrDesign``'s per-nnz ``segment_sum`` margins cost ~116 ms and its
    scatter-add transpose ~89 ms, while a gather + fixed-width row-sum of
    the same entries costs ~5 ms — XLA lowers large scatters serially on
    TPU, but gathers and lane reductions stream. So this layout stores the
    entries TWICE, pre-sorted on host at build time:

    - row-major: ``(Mr, C)`` values/col-ids with one row id per chunk —
      margins = per-chunk ``Σ v·w[col]`` then a segment-sum of ONLY
      ``Mr ≈ nnz/C + n`` partials;
    - col-major: ``(Mc, C)`` values/row-ids with one col id per chunk —
      the gradient transpose the same way into ``d`` bins.

    Chunk padding carries ``value = 0`` (contributes nothing). The chunk
    width trades padding (small C) against scatter length (large C); the
    builder defaults to the per-key median rounded to a multiple of 8,
    clamped to [8, 128]. 2x memory vs CsrDesign — the price of replacing
    both big scatters. This is the counterpart of the reference's executor-
    local hash-map gradient accumulation in
    ``function/glm/ValueAndGradientAggregator.scala``, re-shaped for a
    machine that hates random writes and loves wide reads.
    """

    rvals: Array  # (Mr, C) f32
    rcols: Array  # (Mr, C) int32
    rrow: Array  # (Mr,) int32 — row id per chunk (non-decreasing)
    cvals: Array  # (Mc, C) f32
    crows: Array  # (Mc, C) int32
    ccol: Array  # (Mc,) int32 — col id per chunk (non-decreasing)
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    n_cols: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_samples(self) -> int:
        return self.n_rows

    @property
    def dim(self) -> int:
        return self.n_cols

    @staticmethod
    def _gather2d(table: Array, idx: Array) -> Array:
        """``table[idx]`` for a 2D index array via a FLAT gather + reshape —
        XLA lowers a gather with a 2D start-index array ~30x slower on TPU
        (measured 129 ms vs 4.3 ms for 13M indices)."""
        return jnp.take(table, idx.reshape(-1), axis=0).reshape(idx.shape)

    def matvec(self, w: Array) -> Array:
        acc = jnp.promote_types(jnp.promote_types(self.rvals.dtype, w.dtype),
                                jnp.float32)
        part = jnp.sum((self.rvals * self._gather2d(w, self.rcols)
                        ).astype(acc), axis=-1)
        return jax.ops.segment_sum(part, self.rrow, num_segments=self.n_rows,
                                   indices_are_sorted=True)

    def rmatvec(self, g: Array) -> Array:
        acc = jnp.promote_types(jnp.promote_types(self.cvals.dtype, g.dtype),
                                jnp.float32)
        part = jnp.sum((self.cvals * self._gather2d(g, self.crows)
                        ).astype(acc), axis=-1)
        return jax.ops.segment_sum(part, self.ccol, num_segments=self.n_cols,
                                   indices_are_sorted=True)

    def rmatvec_squared(self, g: Array) -> Array:
        """``(X²)ᵀ g`` — the Hessian-diagonal contraction (values squared)."""
        acc = jnp.promote_types(jnp.promote_types(self.cvals.dtype, g.dtype),
                                jnp.float32)
        part = jnp.sum((jnp.square(self.cvals)
                        * self._gather2d(g, self.crows)).astype(acc),
                       axis=-1)
        return jax.ops.segment_sum(part, self.ccol, num_segments=self.n_cols,
                                   indices_are_sorted=True)

    @staticmethod
    def default_chunk(counts: np.ndarray) -> int:
        """Median nnz of the non-empty keys, rounded to 8 in [8, 128]."""
        nz = counts[counts > 0]
        if not len(nz):
            return 8
        med = int(np.median(nz))
        return int(np.clip(-(-med // 8) * 8, 8, 128))

    @staticmethod
    def layout_numpy(rows, cols, vals, *, row_chunk: int | None = None,
                     col_chunk: int | None = None) -> dict:
        """Host-side chunk layouts as numpy arrays (for stacking/sharding)."""
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        vals = np.asarray(vals, np.float32)
        live = vals != 0  # drop explicit zero padding from CSR-style inputs
        rows, cols, vals = rows[live], cols[live], vals[live]
        if row_chunk is None:
            row_chunk = ChunkedSparseDesign.default_chunk(
                np.bincount(rows) if len(rows) else np.zeros(1, np.int64))
        if col_chunk is None:
            col_chunk = ChunkedSparseDesign.default_chunk(
                np.bincount(cols) if len(cols) else np.zeros(1, np.int64))

        def layout(keys, chunk):
            order = np.argsort(keys, kind="stable")
            gather, chunk_key = _chunk_sorted(
                keys[order], order,
                max(int(keys.max()) + 1 if len(keys) else 1, 1), chunk)
            pad = gather < 0
            safe = np.where(pad, 0, gather)
            v = np.where(pad, 0.0, vals[safe] if len(vals) else 0.0
                         ).astype(np.float32)
            return v, safe, chunk_key

        rvals, r_src, rrow = layout(rows, row_chunk)
        cvals, c_src, ccol = layout(cols, col_chunk)
        safe_cols = cols[r_src] if len(cols) else np.zeros_like(r_src)
        safe_rows = rows[c_src] if len(rows) else np.zeros_like(c_src)
        return dict(
            rvals=rvals, rcols=safe_cols.astype(np.int32), rrow=rrow,
            cvals=cvals, crows=safe_rows.astype(np.int32), ccol=ccol,
            row_chunk=row_chunk, col_chunk=col_chunk)

    @staticmethod
    def from_coo(rows, cols, vals, n_rows: int, n_cols: int,
                 row_chunk: int | None = None, col_chunk: int | None = None,
                 ) -> "ChunkedSparseDesign":
        """Build both layouts from host COO triplets. Duplicate (row, col)
        entries occupy separate slots and accumulate in every contraction,
        the same semantics as CsrDesign."""
        lay = ChunkedSparseDesign.layout_numpy(
            rows, cols, vals, row_chunk=row_chunk, col_chunk=col_chunk)
        return ChunkedSparseDesign(
            rvals=jnp.asarray(lay["rvals"]), rcols=jnp.asarray(lay["rcols"]),
            rrow=jnp.asarray(lay["rrow"]),
            cvals=jnp.asarray(lay["cvals"]), crows=jnp.asarray(lay["crows"]),
            ccol=jnp.asarray(lay["ccol"]),
            n_rows=int(n_rows), n_cols=int(n_cols))


Design = Union[DenseDesign, CsrDesign, ChunkedSparseDesign]
