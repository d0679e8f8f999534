"""GAME data layer: global columnar data, fixed-effect and random-effect datasets.

Re-design of the reference's GAME data layer
(``photon-api/.../data/{GameDatum, FixedEffectDataset, RandomEffectDataset,
LocalDataset, RandomEffectDatasetPartitioner}.scala``).

The reference represents data as ``RDD[(UniqueSampleId, GameDatum)]`` and
builds per-coordinate datasets by Spark shuffles (keyBy entity → frequency-
balanced partitioner → groupByKey → per-entity ``LocalDataset``). Here the
global dataset is host-resident columnar numpy (labels / offsets / weights /
per-shard CSR features / per-entity-type id columns), and the "shuffle" is a
vectorized argsort-by-entity. The random-effect dataset then departs from the
reference entirely — instead of millions of ragged per-entity iterables it
builds **fixed-shape size buckets**: entities are grouped by (padded sample
count, padded per-entity feature count), each bucket a dense
``(entities, samples, features)`` tensor ready for a ``vmap``-batched
on-device solve (SURVEY.md §7 "hard parts" #1/#2). Per-entity feature-space
reduction (the reference's ``projector/IndexMapProjector``) happens here too:
each entity's observed feature ids become a compact local index map, so the
bucket feature dim is the max *observed* dim, not the shard vocabulary dim.

Active/passive split follows the reference: an upper bound subsamples an
entity's training rows (reservoir-style), a lower bound drops entities with
too few rows from training entirely; all rows excluded from training remain
"passive" — scored with the trained entity model during coordinate descent.
"""

from __future__ import annotations

import dataclasses
import threading
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.game.projector import ProjectorType, RandomProjector
from photon_ml_tpu.ops.design import CsrDesign, DenseDesign
from photon_ml_tpu.ops.objective import GLMData
from photon_ml_tpu.util import group_starts as _group_starts
from photon_ml_tpu.util import hash_uniform as _hash_uniform
from photon_ml_tpu.util import materialize_thunk

#: guards lazy-thunk materialization (REBucket deferred native fills) —
#: see util.materialize_thunk. Materialization is rare — one lock is enough.
_THUNK_LOCK = threading.Lock()

#: Fixed-effect designs at or below this width always densify (MXU path)
#: when they fit the byte cap; above it the measured crossover rule decides.
DENSE_DESIGN_MAX_DIM = 4096
#: largest measured dim/(nnz-per-row) ratio at which the dense layout still
#: beat the chunked-sparse one on-chip (tools/layout_crossover.py).
DENSE_CROSSOVER_NNZ_MULT = 512
#: per-device byte cap for a densified design — a wide-but-dense shard must
#: not densify itself into an OOM (v5e HBM is 16 GiB; the solve also holds
#: gradients, scores and, under GAME, the RE buckets).
DENSE_DESIGN_MAX_BYTES = 4 << 30
#: HOST byte cap for the densified design: the build materializes the full
#: (n, d) float32 array in host RAM before any device split, so the
#: per-device cap alone would let an 8-shard build allocate 8x it on host.
DENSE_DESIGN_MAX_HOST_BYTES = 8 << 30
#: cap on a random-effect coordinate's device-RESIDENT fat bucket tensors
#: (f32 estimate: x (E,S,D) + labels/weights/gather/scatter (E,S) each);
#: past it the build degrades to upload-and-drop streaming instead of
#: OOMing. 6 GiB of a v5e's 16 GiB HBM: the sweep also holds the shared
#: dense shard image (≤4 GiB by its own cap), score vectors and solver
#: temporaries. Measured (tools/re_scaling_probe.py, power-law entities,
#: dim 8, 5 histogram buckets): 10M rows ≈ 1.9 GiB fat, 30M rows ≈ 8.3 GiB
#: — so the cap admits ~20M resident rows per chip at dim 8 and trips
#: beyond, where entity sharding (--multihost / --mesh entity=K) is the
#: intended scale-out.
RE_FAT_CACHE_MAX_BYTES = 6 << 30


@dataclasses.dataclass(frozen=True)
class FeatureShard:
    """Host CSR feature block over all samples for one feature shard.

    The reference assembles per-shard ``SparseVector`` columns in
    ``data/avro/AvroDataReader.scala``; this is the columnar equivalent.
    Rows are samples; ``dim`` is the shard vocabulary size (intercept
    included if the shard config adds one).
    """

    indptr: np.ndarray  # (n_samples + 1,) int64
    cols: np.ndarray  # (nnz,) int32
    vals: np.ndarray  # (nnz,) float32
    dim: int

    @property
    def n_samples(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def nnz(self) -> int:
        return int(self.cols.shape[0])

    def row_counts(self) -> np.ndarray:
        return np.diff(self.indptr)

    def rows(self) -> np.ndarray:
        """Expand indptr to one row id per nnz."""
        return np.repeat(np.arange(self.n_samples, dtype=np.int64),
                         self.row_counts())

    def take(self, sample_idx: np.ndarray) -> "FeatureShard":
        """Row-subset (and reorder) by sample indices (vectorized — this
        runs per CD sweep on the passive-scoring path)."""
        sample_idx = np.asarray(sample_idx, np.int64)
        counts = self.row_counts()[sample_idx]
        new_indptr = np.zeros(len(sample_idx) + 1, np.int64)
        np.cumsum(counts, out=new_indptr[1:])
        total = int(new_indptr[-1])
        # gather[k] = old nnz position: per-row arange built flat
        row_of_nnz = np.repeat(np.arange(len(sample_idx)), counts)
        offset_in_row = np.arange(total) - np.repeat(new_indptr[:-1], counts)
        gather = self.indptr[sample_idx][row_of_nnz] + offset_in_row
        return FeatureShard(indptr=new_indptr, cols=self.cols[gather],
                            vals=self.vals[gather], dim=self.dim)

    @staticmethod
    def from_coo(rows, cols, vals, n_samples: int, dim: int) -> "FeatureShard":
        """OWNERSHIP: when the inputs are already row-sorted AND in the
        target dtypes, the returned shard ALIASES them (the sorted fast
        path deliberately avoids the copy) — and FREEZES the aliased
        ``cols``/``vals`` buffers via ``writeable=False``, so a caller's
        later in-place write raises ``ValueError`` instead of silently
        corrupting the shard (and any device image derived from it).
        Callers that need to keep mutating their arrays must pass a copy.
        Unsorted inputs are copied by the sort and stay writable."""
        rows = np.asarray(rows, np.int64)
        if rows.size and (np.diff(rows) < 0).any():
            order = np.argsort(rows, kind="stable")
            rows = rows[order]
            cols = np.asarray(cols, np.int32)[order]
            vals = np.asarray(vals, np.float32)[order]
        else:
            # already row-grouped (the native decoder emits nnz in record
            # order; masking a shard's columns preserves it) — the O(nnz)
            # monotonicity check is ~10x cheaper than the argsort+gathers
            cols = np.ascontiguousarray(cols, np.int32)
            vals = np.ascontiguousarray(vals, np.float32)
            # freeze the aliased buffers: a caller mutating them later would
            # silently corrupt this frozen shard and any device image derived
            # from it — make the write raise instead
            cols.flags.writeable = False
            vals.flags.writeable = False
        indptr = np.zeros(n_samples + 1, np.int64)
        np.cumsum(np.bincount(rows, minlength=n_samples), out=indptr[1:])
        return FeatureShard(indptr=indptr, cols=cols, vals=vals, dim=dim)

    def to_dense(self) -> np.ndarray:
        x = np.zeros((self.n_samples, self.dim), np.float32)
        np.add.at(x, (self.rows(), self.cols.astype(np.int64)), self.vals)
        return x


@dataclasses.dataclass(frozen=True)
class GameData:
    """The global host-resident dataset: one row per sample.

    Counterpart of the reference's ``RDD[(UniqueSampleId, GameDatum)]``
    (``data/GameDatum.scala`` + ``data/GameConverters.scala``): response,
    additive offset, weight, per-shard feature vectors, and per-entity-type
    integer id columns (entity ids are pre-indexed into ``[0, n_entities)``
    by ingest; ``-1`` marks a missing id).
    """

    labels: np.ndarray  # (n,) float32
    offsets: np.ndarray  # (n,) float32
    weights: np.ndarray  # (n,) float32
    shards: dict[str, FeatureShard]
    id_columns: dict[str, np.ndarray]  # entity-type -> (n,) int64
    #: device placements derived from this data (dense shard images, label/
    #: weight vectors) — shared by every coordinate built over it.
    #: Everything device-side is built from COMPACT uploads exactly once
    #: per dataset: fewer bytes cross the host→device link (its rate on
    #: the present chip: not measured). ``init=False``:
    #: ``dataclasses.replace`` must NOT share the cache with the copy — the
    #: copy's fields (shards, labels) may differ and would be served stale
    #: device tensors.
    _device_cache: dict = dataclasses.field(
        default_factory=dict, compare=False, repr=False, init=False)

    def __post_init__(self):
        n = self.labels.shape[0]
        if self.offsets.shape[0] != n or self.weights.shape[0] != n:
            raise ValueError(
                f"offsets/weights length ({self.offsets.shape[0]}/"
                f"{self.weights.shape[0]}) != labels length ({n})")
        for name, shard in self.shards.items():
            if shard.n_samples != n:
                raise ValueError(f"shard {name!r}: {shard.n_samples} rows != {n}")
        for name, ids in self.id_columns.items():
            if ids.shape[0] != n:
                raise ValueError(f"id column {name!r}: {ids.shape[0]} != {n}")

    @property
    def n_samples(self) -> int:
        return int(self.labels.shape[0])

    def device_labels(self):
        out = self._device_cache.get("labels")
        if out is None:
            out = jnp.asarray(self.labels)
            self._device_cache["labels"] = out
        return out

    def device_weights(self):
        out = self._device_cache.get("weights")
        if out is None:
            w = self.weights
            # unweighted data (the common case: weight column absent) needs
            # no 4 B/row transfer — build the ones on device (the host scan
            # is ~0.5 ms/1M rows).
            if w.size and w[0] == 1.0 and np.all(w == 1.0):
                out = jnp.ones(w.shape[0], jnp.float32)
            else:
                out = jnp.asarray(w)
            self._device_cache["weights"] = out
        return out

    def device_dense_shard(self, shard_id: str,
                           max_bytes: Optional[int] = None,
                           dtype=jnp.float32):
        """Dense ``(n, dim)`` device image of a feature shard, materialized
        ON DEVICE from a compact CSR upload (per-row counts + narrow column
        ids + values ≈ nnz*5–9 bytes instead of n*dim*4): a 200k×33 design
        with 7 nnz/row is a third of the bytes (the link's rate on the
        present chip: not measured).  With
        ``dtype=bfloat16`` the VALUES ride the wire at 2 bytes too (cast on
        host) — the design-dtype trade end to end, not just in HBM.
        Cached per (shard, dtype); ``None`` when the dense image would
        exceed ``max_bytes`` (default :data:`DENSE_DESIGN_MAX_BYTES`, the
        same cap the fixed-effect layout rule uses) — the budget is applied
        on cache HITS too, so a caller with a tighter budget never receives
        an image a looser caller materialized first."""
        shard = self.shards[shard_id]
        n, d = shard.n_samples, shard.dim
        dtype = jnp.dtype(dtype)
        if max_bytes is None:
            max_bytes = DENSE_DESIGN_MAX_BYTES
        if n * d * dtype.itemsize > max_bytes:
            return None
        key = ("dense_shard", shard_id, dtype.name)
        out = self._device_cache.get(key)
        if out is None:
            counts = shard.row_counts()
            cdt = (np.uint8 if counts.size == 0 or counts.max() < 256
                   else np.int32)
            coldt = (np.uint8 if d <= 256 else
                     np.uint16 if d <= 65536 else np.int32)
            out = _densify_csr(
                jnp.asarray(counts.astype(cdt)),
                jnp.asarray(shard.cols.astype(coldt)),
                jnp.asarray(shard.vals.astype(dtype)), n=n, d=d,
                nnz=shard.nnz)
            self._device_cache[key] = out
        return out

    def clear_device_cache(self) -> None:
        self._device_cache.clear()

    @staticmethod
    def build(labels, shards, offsets=None, weights=None, id_columns=None) -> "GameData":
        labels = np.asarray(labels, np.float32)
        n = labels.shape[0]
        return GameData(
            labels=labels,
            offsets=np.zeros(n, np.float32) if offsets is None
            else np.asarray(offsets, np.float32),
            weights=np.ones(n, np.float32) if weights is None
            else np.asarray(weights, np.float32),
            shards=dict(shards),
            id_columns={k: np.asarray(v, np.int64)
                        for k, v in (id_columns or {}).items()},
        )


@partial(jax.jit, static_argnames=("n", "d", "nnz"))
def _densify_csr(counts, cols, vals, *, n: int, d: int, nnz: int):
    """CSR → dense ``(n, d)`` on device. Duplicate (row, col) entries
    accumulate, matching :meth:`FeatureShard.to_dense`'s ``np.add.at``
    (accumulation always in f32; the image lands in ``vals.dtype``)."""
    rows = jnp.repeat(jnp.arange(n, dtype=jnp.int32),
                      counts.astype(jnp.int32), total_repeat_length=nnz)
    out = jnp.zeros((n, d), jnp.float32).at[
        rows, cols.astype(jnp.int32)].add(vals.astype(jnp.float32))
    return out.astype(vals.dtype)


# ---------------------------------------------------------------------------
# Fixed effect
# ---------------------------------------------------------------------------


def choose_dense_design(shard: FeatureShard, *, n_shards: int = 1,
                        dense_max_dim: Optional[int] = None,
                        itemsize: int = 4) -> bool:
    """Dense vs chunked-sparse layout pick for a fixed-effect design —
    the measured crossover rule (SURVEY.md §7 hard-part #2). With
    ``dense_max_dim`` given, the old hard threshold applies unchanged
    (explicit caller override).

    Measured on a TPU v5e on 2026-07-31, before PR 1, and not measured on
    the present chip (`tools/layout_crossover.py`: chained jitted
    ``value_and_grad`` iterations, min-of-2 passes, D2H sync; k = nnz/row;
    n scaled so the dense tensor is ~1 GB):

    ====== ===== ========= ========== ========
    d      k     dense_ms  sparse_ms  winner
    ====== ===== ========= ========== ========
    512    8     15.1      66.0       dense 4.4x
    512    128   13.6      901.0      dense 66x
    2048   8     16.0      23.1       dense 1.4x
    4096   8     15.9      16.9       dense 1.06x
    8192   8     15.9      12.8       sparse 1.2x
    8192   32    11.7      25.2       dense 2.2x
    16384  32    16.1      21.4       dense 1.3x
    16384  128   19.7      56.7       dense 2.9x
    65536  8-128 (bytes)   17-54      sparse
    ====== ===== ========= ========== ========

    Model behind the numbers: the dense iteration streams ``n*d*4`` bytes
    at ~170 GB/s effective (two-pass closed form), while the chunked
    sparse iteration pays ~16-20 ns/nnz (two XLA random-gather passes) —
    so dense wins while ``d ≲ 600*k``. The rule uses 512, the largest
    measured d/k where dense still won, and caps the dense tensor's
    per-device bytes so a billion-row shard can't densify into an OOM.
    """
    return choose_dense_design_stats(shard.n_samples, shard.dim, shard.nnz,
                                     n_shards=n_shards,
                                     dense_max_dim=dense_max_dim,
                                     itemsize=itemsize)


def choose_dense_design_stats(n_samples: int, dim: int, nnz: int, *,
                              n_shards: int = 1,
                              dense_max_dim: Optional[int] = None,
                              n_local_samples: Optional[int] = None,
                              itemsize: int = 4) -> bool:
    """The rule of :func:`choose_dense_design` on explicit statistics —
    multi-process training calls this with GLOBALLY allreduced (n, nnz) so
    every process picks the same layout (an SPMD program must agree).
    ``n_local_samples`` bounds the HOST materialization (the build holds
    the full local (n, d) float32 array before the device split); defaults
    to ``n_samples`` (single-process: local = global). ``itemsize`` is the
    DEVICE storage width (2 under --design-dtype bfloat16, letting designs
    that fit dense only at 2 bytes still take the dense path); the host
    cap stays at 4 bytes — the build materializes f32 before the cast."""
    if dense_max_dim is not None:
        return dim <= dense_max_dim
    n_local = n_samples if n_local_samples is None else n_local_samples
    if n_local * dim * 4 > DENSE_DESIGN_MAX_HOST_BYTES:
        return False
    if n_samples * dim * itemsize // max(n_shards, 1) \
            > DENSE_DESIGN_MAX_BYTES:
        return False
    if dim <= DENSE_DESIGN_MAX_DIM:
        return True
    return dim <= DENSE_CROSSOVER_NNZ_MULT * (nnz / max(n_samples, 1))


def host_design_for_shard(shard: FeatureShard, *,
                          dense_max_dim: Optional[int] = None,
                          n_shards: int = 1,
                          force_dense: Optional[bool] = None,
                          itemsize: int = 4):
    """Host-resident design for a fixed-effect shard, laid out per
    :func:`choose_dense_design`. The single home of the dense/sparse
    cutover — the single- and multi-process feeds must agree
    (``force_dense`` carries a decision already agreed across processes)."""
    dense = (force_dense if force_dense is not None
             else choose_dense_design(shard, n_shards=n_shards,
                                      dense_max_dim=dense_max_dim,
                                      itemsize=itemsize))
    if dense:
        return DenseDesign(x=shard.to_dense())
    return CsrDesign(
        rows=shard.rows().astype(np.int32),
        cols=shard.cols.astype(np.int32),
        values=shard.vals,
        n_rows=shard.n_samples, n_cols=shard.dim)


def design_dtype_of(dtype) -> "jnp.dtype":
    """Normalize a design-dtype spec — the CLI strings ("float32" /
    "bfloat16") or any dtype-like — to a jnp dtype. The single home of
    the string→dtype mapping."""
    if isinstance(dtype, str):
        dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    return jnp.dtype(dtype)


def cast_dense_design(host_design, dtype):
    """Host-side dtype cast of a DENSE host design: the sharded feeds
    (:func:`~photon_ml_tpu.parallel.distributed.shard_glm_data`, the
    multihost global feed) preserve leaf dtypes, so casting here puts the
    design on the wire and in HBM at 2 bytes under bfloat16. Sparse
    layouts keep f32 values — bf16 is the dense-path trade (same policy
    as train_glm's ``_to_glm_data``). ``dtype`` may be the CLI string."""
    dtype = design_dtype_of(dtype)
    if dtype != jnp.float32 and isinstance(host_design, DenseDesign):
        return DenseDesign(x=np.asarray(host_design.x).astype(dtype))
    return host_design


@dataclasses.dataclass(frozen=True)
class FixedEffectDataset:
    """Device-ready data for one fixed-effect coordinate
    (reference ``data/FixedEffectDataset.scala``).

    Holds the device arrays minus offsets — coordinate descent supplies
    fresh residual offsets every sweep via :meth:`glm_data`.

    With a ``mesh`` carrying a ``"data"`` axis, the design/labels/weights
    are built ONCE in the stacked per-device layout of
    :func:`photon_ml_tpu.parallel.distributed.shard_glm_data` (the
    reference's RDD partitioning); only the per-sweep offsets are re-placed.
    """

    coordinate_id: str
    feature_shard_id: str
    design: object  # DenseDesign | ChunkedSparseDesign (device; stacked when sharded)
    labels: jnp.ndarray
    weights: jnp.ndarray
    dim: int
    n_samples: int = 0
    mesh: Optional[object] = None  # jax.sharding.Mesh with a "data" axis
    n_shards: int = 1

    @staticmethod
    def build(coordinate_id: str, data: GameData, feature_shard_id: str,
              *, dense_max_dim: Optional[int] = None,
              dtype=jnp.float32, mesh=None) -> "FixedEffectDataset":
        shard = data.shards[feature_shard_id]
        from photon_ml_tpu.parallel.mesh import DATA_AXIS

        n_shards = 1
        if mesh is not None and DATA_AXIS in getattr(mesh, "shape", {}):
            n_shards = int(mesh.shape[DATA_AXIS])
        itemsize = design_dtype_of(dtype).itemsize
        if (n_shards == 1
                and choose_dense_design(shard, n_shards=1,
                                        dense_max_dim=dense_max_dim,
                                        itemsize=itemsize)):
            # single-chip dense: materialize the design ON DEVICE from the
            # compact CSR upload — skips both the host densify and the
            # (n, d, 4)-byte host→device transfer; a bfloat16 request ships
            # the values at 2 bytes as well
            x_dev = data.device_dense_shard(
                feature_shard_id, max_bytes=DENSE_DESIGN_MAX_BYTES,
                dtype=dtype)
            if x_dev is not None:
                design = DenseDesign(x=x_dev)
                return FixedEffectDataset(
                    coordinate_id=coordinate_id,
                    feature_shard_id=feature_shard_id,
                    design=design, labels=data.device_labels(),
                    weights=data.device_weights(), dim=shard.dim,
                    n_samples=shard.n_samples)
        # host-resident design first: the sharded branch pads/splits on host
        # and device_puts per-shard blocks directly — never materializing
        # the full design in one device's HBM (the whole point of dp)
        host_design = host_design_for_shard(
            shard, dense_max_dim=dense_max_dim, n_shards=n_shards,
            itemsize=itemsize)
        host_design = cast_dense_design(host_design, dtype)
        if n_shards > 1:
            from photon_ml_tpu.parallel.distributed import shard_glm_data

            sharded = shard_glm_data(
                GLMData(design=host_design, labels=data.labels,
                        offsets=np.zeros(shard.n_samples, np.float32),
                        weights=data.weights),
                n_shards, device_put_mesh=mesh)
            return FixedEffectDataset(
                coordinate_id=coordinate_id,
                feature_shard_id=feature_shard_id,
                design=sharded.design, labels=sharded.labels,
                weights=sharded.weights, dim=shard.dim,
                n_samples=shard.n_samples, mesh=mesh, n_shards=n_shards)
        if isinstance(host_design, DenseDesign):
            design = DenseDesign(x=jnp.asarray(host_design.x, dtype))
        else:
            # single-chip wide-sparse: the chunked dual layout (gathers and
            # chunk sums where CsrDesign scatters every entry — see
            # ops/design.py::ChunkedSparseDesign)
            from photon_ml_tpu.ops.design import ChunkedSparseDesign

            design = ChunkedSparseDesign.from_coo(
                host_design.rows, host_design.cols, host_design.values,
                n_rows=host_design.n_rows, n_cols=host_design.n_cols)
        return FixedEffectDataset(
            coordinate_id=coordinate_id, feature_shard_id=feature_shard_id,
            design=design, labels=jnp.asarray(data.labels),
            weights=jnp.asarray(data.weights), dim=shard.dim,
            n_samples=shard.n_samples)

    def glm_data(self, offsets) -> GLMData:
        """Bind per-sweep residual offsets (host numpy or device array —
        a device residual never round-trips through the host)."""
        if self.n_shards > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            from photon_ml_tpu.parallel.mesh import DATA_AXIS

            import jax

            per = self.labels.shape[1]
            offsets = jnp.asarray(offsets, jnp.float32)
            pad = self.n_shards * per - offsets.shape[0]
            if pad:
                offsets = jnp.concatenate(
                    [offsets, jnp.zeros((pad,), jnp.float32)])
            off = jax.device_put(
                offsets.reshape(self.n_shards, per),
                NamedSharding(self.mesh, PartitionSpec(DATA_AXIS)))
            return GLMData(design=self.design, labels=self.labels,
                           offsets=off, weights=self.weights)
        return GLMData(design=self.design, labels=self.labels,
                       offsets=jnp.asarray(offsets, jnp.float32),
                       weights=self.weights)


# ---------------------------------------------------------------------------
# Random effect
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RandomEffectDatasetConfig:
    """Bounds and projection settings for one random-effect coordinate
    (reference ``data/RandomEffectDataset.scala`` +
    ``RandomEffectDataConfiguration``)."""

    random_effect_type: str  # id-column name, e.g. "userId"
    feature_shard_id: str
    #: max training rows kept per entity (reservoir subsample beyond this);
    #: None = unlimited (reference activeDataUpperBound).
    active_data_upper_bound: Optional[int] = None
    #: entities with fewer rows than this get no model (rows stay passive).
    active_data_lower_bound: int = 1
    #: cap on per-entity features kept (by within-entity support, ties by id;
    #: reference LocalDataset feature pruning). None = all observed.
    max_active_features: Optional[int] = None
    #: feature-space projector (reference ``projector/ProjectorType.scala``):
    #: INDEX_MAP compacts each entity's observed features (default);
    #: RANDOM projects through a shared Gaussian matrix of width
    #: ``projected_dim`` (reference ``RandomProjection``).
    projector_type: ProjectorType = ProjectorType.INDEX_MAP
    projected_dim: Optional[int] = None
    #: bucket shape granularity: per-entity sample/feature counts are padded
    #: up to powers of these growth factors. Every distinct padded
    #: (samples, features) shape is a separate XLA compilation of the
    #: vmapped solver, so coarser growth = fewer compiles but more padded
    #: compute. 4.0 keeps shape count ~log4(max entity size) ≈ a handful.
    sample_bucket_growth: float = 4.0
    feature_bucket_growth: float = 2.0
    #: "geometric" pads each dim to a growth-factor power (above);
    #: "histogram" chooses ≤max_{sample,feature}_buckets padded sizes from
    #: the actual entity-size distribution by a min-total-padding partition
    #: (ROADMAP bucket autotuning). The DP is per-dimension optimal: total
    #: padded samples (resp. features) is minimal for the given shape
    #: budget — so with a budget ≥ the geometric scheme's shape count it
    #: never pads a dimension more than geometric does. (The E·S·D product
    #: is not jointly optimized; a very tight budget can lose on it.)
    #: Correctness is identical either way — padding is masked.
    bucket_strategy: str = "geometric"
    max_sample_buckets: int = 8
    max_feature_buckets: int = 4
    #: keep the static bucket arrays resident on device across CD sweeps
    #: (one upload total instead of one per sweep). Peak HBM then holds ALL
    #: buckets of the coordinate; turn off for coordinates whose total
    #: bucket payload exceeds device memory (reverts to upload-and-drop
    #: per sweep).
    cache_device_buckets: bool = True
    seed: int = 20260729

    def __post_init__(self):
        if (self.projector_type is ProjectorType.RANDOM
                and self.max_active_features is not None):
            raise ValueError(
                "max_active_features applies to the INDEX_MAP projector's "
                "per-entity feature selection; the RANDOM projector replaces "
                "feature selection with a shared projection (set "
                "projected_dim to control its width instead)")
        if self.bucket_strategy not in ("geometric", "histogram"):
            raise ValueError(
                f"unknown bucket_strategy {self.bucket_strategy!r} "
                "(expected 'geometric' or 'histogram')")
        if self.max_sample_buckets < 1 or self.max_feature_buckets < 1:
            raise ValueError(
                "max_sample_buckets and max_feature_buckets must be ≥ 1 "
                f"(got {self.max_sample_buckets}/{self.max_feature_buckets})")

    @property
    def resident(self) -> bool:
        """Whether the coordinate's bucket tensors stay on the device across
        sweeps, so a sweep is one program over all of them. Otherwise the
        coordinate streams: a bucket is uploaded, solved and dropped, and
        peak HBM is one bucket."""
        return self.cache_device_buckets

    @property
    def reads_shared_image(self) -> bool:
        """Whether the solver may gather the buckets on the device from
        ``GameData``'s dense image of the shard. A streaming coordinate
        must not (it would pin the image for the dataset's lifetime), and a
        projected one's buckets hold projected features."""
        return (self.resident
                and self.projector_type is not ProjectorType.RANDOM)




def _geom_at_least(x: np.ndarray, growth: float, floor: int = 1) -> np.ndarray:
    """Elementwise next integer power of ``growth`` ≥ max(x, floor)."""
    x = np.maximum(np.asarray(x, np.int64), floor)
    exp = np.ceil(np.log(x) / np.log(growth) - 1e-9).astype(np.int64)
    out = np.ceil(np.power(growth, exp)).astype(np.int64)
    return np.maximum(out, x)  # guard against fp rounding down


#: unique-size cap for the histogram DP: above this, sizes are pre-quantized
#: to a 2% geometric grid (keeps the O(K·m²) DP trivial at any entity count)
_HIST_MAX_UNIQUE = 512


def _histogram_pad(x: np.ndarray, max_buckets: int, floor: int = 1) -> np.ndarray:
    """Elementwise padded size via a min-total-padding ≤max_buckets partition.

    Power-law entity sizes (SURVEY.md §3 "straggler entities") make fixed
    geometric growth pad-heavy; this picks the padded sizes FROM the observed
    size distribution. DP over the sorted unique sizes: the cost of one
    bucket covering sizes (v_i..v_j] is v_j · (count in the range) — total
    padded rows, since every member pads to the bucket max. O(K·m²) with
    m ≤ _HIST_MAX_UNIQUE after quantization; exact when m is under the cap.
    """
    x = np.maximum(np.asarray(x, np.int64), floor)
    v, c = np.unique(x, return_counts=True)
    if len(v) > _HIST_MAX_UNIQUE:
        # quantize UP to a geometric grid (padding stays valid) whose growth
        # is derived from the observed range, so the grid point count — and
        # with it the DP's m — is actually bounded by _HIST_MAX_UNIQUE at
        # any size range (a fixed 2% growth is not: 1e9/1 spans ~1000 steps)
        lo = max(floor, int(v[0]))
        growth = max(1.02,
                     (float(v[-1]) / lo) ** (1.0 / (_HIST_MAX_UNIQUE - 1)))
        xq = _geom_at_least(x, growth, floor)
        v, c = np.unique(xq, return_counts=True)
        x = xq
    m = len(v)
    k_max = min(max_buckets, m)
    # W[j] = total count of sizes ≤ v_{j-1} (prefix, 1-indexed)
    w = np.zeros(m + 1, np.int64)
    np.cumsum(c, out=w[1:])
    inf = np.int64(1) << 60
    # dp[k][j] = min Σ padded rows covering the first j unique sizes with
    # exactly k buckets; group (i..j] costs v[j-1] * (W[j] - W[i])
    dp = np.full((k_max + 1, m + 1), inf)
    dp[0, 0] = 0
    parent = np.zeros((k_max + 1, m + 1), np.int64)
    lower = np.arange(m)[:, None] <= np.arange(m)[None, :]  # i ≤ j-1
    for k in range(1, k_max + 1):
        # cand[i, j-1] = dp[k-1][i] + v[j-1] * (W[j] - W[i])
        cand = dp[k - 1, :m, None] + v[None, :] * (w[1:][None, :] - w[:m, None])
        cand = np.where(lower & (dp[k - 1, :m, None] < inf), cand, inf)
        dp[k, 1:] = cand.min(axis=0)
        parent[k, 1:] = cand.argmin(axis=0)
    # more buckets never costs more: take the best k for covering all m
    k_best = int(np.argmin(dp[1:, m])) + 1
    bounds = []
    j = m
    for k in range(k_best, 0, -1):
        bounds.append(int(v[j - 1]))
        j = int(parent[k, j])
    bounds = np.array(sorted(set(bounds)), np.int64)
    # pad each size to its bucket boundary
    pos = np.searchsorted(bounds, x, side="left")
    return bounds[pos]


@dataclasses.dataclass(frozen=True)
class REBucket:
    """One fixed-shape bucket of entities: the unit of vmapped solving.

    ``x`` is dense ``(E, S, D)`` in each entity's **local** feature space;
    ``feature_index`` maps local column j of entity e to the shard-global
    feature id (``-1`` on padding columns, whose x-values are all zero).
    ``weights`` is zero on padded sample rows, which the objective treats as
    exactly absent.
    """

    entity_ids: np.ndarray  # (E,) int64 — global entity index
    #: (E, S, D) float32 — the native build installs a zero-arg THUNK
    #: returning ``(x, labels, weights)`` instead when the solver's compact
    #: device path makes the host fill unnecessary (the fill is the
    #: dominant host cost of a bucket build); ``__getattribute__``
    #: materializes transparently on first access.
    x: np.ndarray
    labels: np.ndarray  # (E, S) float32
    offsets_zero: bool  # offsets supplied per sweep; kept for clarity
    weights: np.ndarray  # (E, S) float32 (0 = padding)
    sample_idx: np.ndarray  # (E, S) int64 global sample row of each slot (-1 pad)
    feature_index: np.ndarray  # (E, D) int64 shard-global feature ids (-1 pad)

    def __getattribute__(self, name):
        if name in ("x", "labels", "weights"):
            val = object.__getattribute__(self, name)
            if callable(val):
                materialize_thunk(self, ("x", "labels", "weights"),
                                  _THUNK_LOCK)
                return object.__getattribute__(self, name)
            return val
        return object.__getattribute__(self, name)

    @property
    def n_entities(self) -> int:
        return int(self.entity_ids.shape[0])

    @property
    def tensor_shape(self) -> tuple[int, int, int]:
        """(E, S, D) without materializing a lazy ``x``."""
        e, s = self.sample_idx.shape
        return (e, s, int(self.feature_index.shape[1]))

    @property
    def shape(self) -> tuple[int, int]:
        return self.tensor_shape[1:]


@dataclasses.dataclass(frozen=True)
class RandomEffectDataset:
    """Active data bucketed for vmapped solves + passive remainder.

    The reference's active data is ``RDD[(REId, LocalDataset)]`` hash-sharded
    by ``RandomEffectDatasetPartitioner``; here the load balancing is done by
    construction — same-shaped entities share a bucket, and buckets shard
    evenly over the ``entity`` mesh axis.
    """

    coordinate_id: str
    config: RandomEffectDatasetConfig
    buckets: list[REBucket]
    #: passive rows, scored-only (reference passiveData): global sample rows
    #: plus their entity ids.
    passive_sample_idx: np.ndarray  # (p,) int64
    passive_entity_ids: np.ndarray  # (p,) int64
    n_entities_total: int
    #: set when config.projector_type is RANDOM; buckets then hold projected
    #: features and models train in the projected space.
    projector: Optional[RandomProjector] = None
    #: the GameData this dataset was bucketed from — lets the solver's
    #: compact-upload path rebuild bucket tensors ON DEVICE (gathers through
    #: the shared dense shard image) instead of shipping the padded
    #: (E, S, D) arrays over the host↔device link.
    source_data: Optional[GameData] = dataclasses.field(
        default=None, compare=False, repr=False)
    #: device placements of the static bucket arrays (x, labels, weights),
    #: keyed by (bucket index, mesh) — filled lazily by the solver so a CD
    #: run uploads each bucket's design ONCE, not once per sweep (the
    #: dominant H2D payload; offsets/warm starts stay per-sweep). NOTE this
    #: pins every bucket in HBM while the dataset lives — intended during a
    #: run (each sweep touches every coordinate) and across a tuning loop's
    #: repeated fits; call :meth:`clear_device_cache` when training is done.
    _device_cache: dict = dataclasses.field(
        default_factory=dict, compare=False, repr=False)

    def clear_device_cache(self) -> None:
        """Release the cached device placements (frees the buckets' HBM)."""
        self._device_cache.clear()

    @property
    def n_active_entities(self) -> int:
        return sum(b.n_entities for b in self.buckets)

    @staticmethod
    def build(coordinate_id: str, data: GameData,
              config: RandomEffectDatasetConfig,
              projector: Optional[RandomProjector] = None,
              use_native: Optional[bool] = None,
              sample_uids: Optional[np.ndarray] = None,
              n_entity_shards: int = 1,
              ) -> "RandomEffectDataset":
        """``projector`` overrides the seeded Gaussian matrix for the RANDOM
        path — the factored coordinate passes its LEARNED projection here
        (reference ``FactoredRandomEffectCoordinate``'s per-iteration
        projection update). ``use_native`` pins the bucket packer
        (``native/bucket_pack.cc`` vs the numpy formulation — identical
        outputs, see tests/test_native.py::TestNativeBucketPackParity);
        None auto-picks native when the library loads. ``sample_uids``
        (default ``arange(n)``) are the stable global ids keying the
        active-bound subsample draw — multi-process training passes each
        row's global id so the kept subset is identical under any row
        partition."""
        shard = data.shards[config.feature_shard_id]
        entities = data.id_columns[config.random_effect_type]
        n = data.n_samples
        if sample_uids is None:
            sample_uids = np.arange(n, dtype=np.int64)

        present = entities >= 0
        order = _stable_group_order(entities[present])
        sample_rows = np.flatnonzero(present)[order]  # samples grouped by entity
        ent_sorted = entities[sample_rows]
        # segment boundaries by linear scan — ent_sorted is already sorted,
        # np.unique would pay a second O(n log n) sort for nothing
        if len(ent_sorted):
            bound = np.empty(len(ent_sorted), bool)
            bound[0] = True
            np.not_equal(ent_sorted[1:], ent_sorted[:-1], out=bound[1:])
            seg_start = np.flatnonzero(bound)
            uniq = ent_sorted[seg_start]
            seg_count = np.diff(np.append(seg_start, len(ent_sorted)))
        else:
            seg_start = np.zeros(0, np.int64)
            uniq = np.zeros(0, np.int64)
            seg_count = np.zeros(0, np.int64)

        # --- active/passive split per entity (fully vectorized: no Python
        # loop over entities — this is the path that must survive the
        # reference's "hundreds of millions of entities" regime) -----------
        lower = config.active_data_lower_bound
        upper = config.active_data_upper_bound
        n_rows = len(sample_rows)
        seg_of_row = np.repeat(np.arange(len(uniq)), seg_count)
        entity_active = seg_count >= lower
        keep = np.ones(n_rows, bool)
        if (upper is not None and seg_count.size
                and int(seg_count.max()) > upper):
            # reservoir-equivalent subsample: random rank within each
            # entity's segment, keep ranks < upper (uniform without
            # replacement, one global vectorized pass). Skipped entirely
            # when no entity exceeds the bound — the common case shouldn't
            # pay the O(n log n) lexsort. The rank key is a counter-based
            # hash of (seed, global sample id) — NOT a sequential rng
            # stream — so the kept subset is a pure per-row function:
            # identical under any row partition (multi-process builds) and
            # stable when other entities' rows come or go.
            keys = _hash_uniform(sample_uids[sample_rows], config.seed)
            order2 = np.lexsort((keys, seg_of_row))
            ranks = np.empty(n_rows, np.int64)
            ranks[order2] = np.arange(n_rows) - np.repeat(seg_start, seg_count)
            keep = ranks < upper
        active_mask = entity_active[seg_of_row] & keep
        passive = sample_rows[~active_mask]
        all_active = sample_rows[active_mask]
        active_seg = np.flatnonzero(entity_active)
        act_entity = uniq[active_seg].astype(np.int64)
        n_active = len(act_entity)
        dense_of_seg = np.full(len(uniq), -1, np.int64)
        dense_of_seg[active_seg] = np.arange(n_active)
        #: dense active-entity index per active row (rows stay grouped by
        #: entity and in original order within an entity)
        ent_of_active = dense_of_seg[seg_of_row[active_mask]]

        n_entities_total = int(entities.max()) + 1 if n and present.any() else 0

        if config.projector_type is ProjectorType.RANDOM:
            if projector is None:
                if config.projected_dim is None:
                    raise ValueError("RANDOM projector requires projected_dim")
                projector = RandomProjector.build(
                    shard.dim, config.projected_dim, config.seed)
            buckets = _random_projection_buckets(
                data, shard, all_active, ent_of_active, act_entity,
                projector, config)
            config = _guard_fat_cache(coordinate_id, config, buckets,
                                      n_entity_shards)
            return RandomEffectDataset(
                coordinate_id=coordinate_id, config=config, buckets=buckets,
                passive_sample_idx=passive,
                passive_entity_ids=entities[passive],
                n_entities_total=n_entities_total, projector=projector)

        # --- bucket pack: native single-pass packer when available --------
        buckets = _index_map_buckets(data, shard, all_active, ent_of_active,
                                     act_entity, config, use_native)
        config = _guard_fat_cache(coordinate_id, config, buckets,
                                  n_entity_shards)
        return RandomEffectDataset(
            coordinate_id=coordinate_id, config=config, buckets=buckets,
            passive_sample_idx=passive,
            passive_entity_ids=entities[passive],
            n_entities_total=n_entities_total, source_data=data)


def resident_fat_bytes(buckets) -> int:
    """f32 HBM estimate of a coordinate's device-RESIDENT bucket tensors —
    the :func:`~photon_ml_tpu.game.random_effect._materialize_fat` product:
    x (E,S,D) + labels/weights (E,S) each. The single home of the formula
    (build guard, estimator budget, probe)."""
    return sum(
        e * s * d * 4 + 2 * e * s * 4
        for (e, s, d) in (b.tensor_shape for b in buckets))


def _guard_fat_cache(coordinate_id: str, config: "RandomEffectDatasetConfig",
                     buckets, n_entity_shards: int
                     ) -> "RandomEffectDatasetConfig":
    """Memory-cliff guard: device-resident buckets (the fast path) pin
    EVERY bucket's fat tensors in HBM for the dataset's lifetime. Past the
    per-DEVICE cap — total fat divided by the entity-mesh width, since an
    entity axis shards the lanes 1/K per chip — degrade to upload-and-drop
    streaming (peak HBM = one bucket) instead of OOMing. Measured scaling
    table in tools/re_scaling_probe.py justifies the threshold.
    Cross-coordinate accounting lives in GameEstimator.prepare, which sees
    every coordinate."""
    if not config.cache_device_buckets:
        return config
    fat = resident_fat_bytes(buckets) // max(int(n_entity_shards), 1)
    if fat <= RE_FAT_CACHE_MAX_BYTES:
        return config
    import logging

    logging.getLogger(__name__).warning(
        "random-effect coordinate %s: device-resident buckets would hold "
        "%.1f GiB of fat tensors per device (> %.1f GiB cap) — reverting "
        "to upload-and-drop streaming (peak HBM = one bucket; slower "
        "sweeps). Shard entities across more processes (--multihost) or "
        "chips (--mesh entity=K) to regain the resident path.",
        coordinate_id, fat / 2**30, RE_FAT_CACHE_MAX_BYTES / 2**30)
    return dataclasses.replace(config, cache_device_buckets=False)


def _stable_group_order(ids: np.ndarray) -> np.ndarray:
    """Stable argsort of a dense non-negative id column (entity ids are
    pre-indexed into ``[0, n_entities)`` by ingest) — native O(n) counting
    sort when available (the numpy stable argsort was ~0.25 s per
    coordinate build at 1M rows), numpy fallback."""
    from photon_ml_tpu import native

    if native.available():
        out = native.counting_sort(ids)
        if out is not None:
            return out
    return np.argsort(ids, kind="stable")


def _padded_shapes(n_samp_per_entity: np.ndarray, n_feat_per_entity: np.ndarray,
                   config: RandomEffectDatasetConfig
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Per-entity padded (samples, features) per the configured strategy."""
    if config.bucket_strategy == "histogram":
        return (_histogram_pad(n_samp_per_entity, config.max_sample_buckets),
                _histogram_pad(n_feat_per_entity, config.max_feature_buckets))
    return (_geom_at_least(n_samp_per_entity, config.sample_bucket_growth),
            _geom_at_least(n_feat_per_entity, config.feature_bucket_growth))


def _index_map_buckets(data: GameData, shard: FeatureShard,
                       all_active: np.ndarray, ent_of_active: np.ndarray,
                       act_entity: np.ndarray,
                       config: RandomEffectDatasetConfig,
                       use_native: Optional[bool]) -> list[REBucket]:
    """INDEX_MAP bucket construction, native fast path with numpy fallback.

    Both produce identical buckets (same order, same arrays); the native
    packer (``native/bucket_pack.cc``) replaces the numpy path's full sorts
    of the nnz stream with two linear passes — the difference between ~45 s
    and ~2 s at 10^7 rows (measured before PR 1)."""
    n_active = len(act_entity)
    if not n_active:
        return []
    if use_native is None or use_native:
        from photon_ml_tpu import native

        if native.available():
            bks = _index_map_buckets_native(
                data, shard, all_active, ent_of_active, act_entity, config)
            if bks is not None:
                return bks
        if use_native:
            raise RuntimeError("native bucket packer requested but the "
                               "native library is unavailable")
    return _index_map_buckets_numpy(
        data, shard, all_active, ent_of_active, act_entity, config)


def _index_map_buckets_native(data, shard, all_active, ent_of_active,
                              act_entity, config):
    from photon_ml_tpu import native

    n_active = len(act_entity)
    n_samp_per_entity = np.bincount(ent_of_active, minlength=n_active
                                    ).astype(np.int64)
    ent_starts = np.zeros(n_active + 1, np.int64)
    np.cumsum(n_samp_per_entity, out=ent_starts[1:])
    # dtype/contiguity contract lives in the native wrappers' ndpointer
    # argtypes; FeatureShard/GameData already store these exact dtypes
    indptr, cols, vals = shard.indptr, shard.cols, shard.vals
    aa = np.ascontiguousarray(all_active, np.int64)
    scratch = native.BucketPackScratch(shard.dim)
    n_feat_per_entity = native.re_feature_counts(
        indptr, cols, aa, ent_starts, shard.dim, config.max_active_features,
        scratch)
    if n_feat_per_entity is None:
        return None
    s_pad, d_pad = _padded_shapes(n_samp_per_entity, n_feat_per_entity, config)
    bucket_key = s_pad * np.int64(1 << 40) + d_pad
    labels32, weights32 = data.labels, data.weights
    # indices-only build when the solver's compact device path will
    # reconstruct the fat tensors on device: the (E, S, D) host fill (a
    # ~3-4x-padded memset+scatter) is deferred to a lazy thunk that almost
    # nothing ever calls. Conservative gate — mirrors _compact_shared's
    # densify bound; a config that later needs the fat path just pays the
    # fill at first access.
    indices_only = (config.reads_shared_image
                    and shard.n_samples * shard.dim * 4
                    <= DENSE_DESIGN_MAX_BYTES)
    # one scratch shared by every deferred fill of this build (created on
    # first use): the stamp contract holds — each bucket fills at most once
    # (REBucket caches the materialization) and buckets hold disjoint
    # entities — and per-fill fresh scratch would memset dim-sized arrays
    # per bucket when a fat-path consumer materializes them all
    lazy_scratch: list = []
    buckets: list[REBucket] = []
    for key in np.unique(bucket_key):
        sel = np.flatnonzero(bucket_key == key)
        S, D = int(s_pad[sel[0]]), int(d_pad[sel[0]])
        if indices_only:
            packed = native.re_bucket_indices(
                indptr, cols, aa, ent_starts, sel, S, D,
                config.max_active_features, scratch)
            if packed is None:
                return None
            sample_idx, feature_index = packed

            def fill(sel=sel, S=S, D=D):
                if not lazy_scratch:
                    lazy_scratch.append(native.BucketPackScratch(shard.dim))
                out = native.re_bucket_fill(
                    indptr, cols, vals, aa, ent_starts, labels32, weights32,
                    sel, S, D, shard.dim, config.max_active_features,
                    lazy_scratch[0])
                if out is None:
                    raise RuntimeError(
                        "native library became unavailable for the deferred "
                        "bucket fill")
                return out[0], out[1], out[2]

            buckets.append(REBucket(
                entity_ids=act_entity[sel], x=fill, labels=fill,
                offsets_zero=True, weights=fill, sample_idx=sample_idx,
                feature_index=feature_index))
            continue
        packed = native.re_bucket_fill(
            indptr, cols, vals, aa, ent_starts, labels32, weights32, sel,
            S, D, shard.dim, config.max_active_features, scratch)
        if packed is None:
            return None
        x, labels, weights, sample_idx, feature_index = packed
        buckets.append(REBucket(
            entity_ids=act_entity[sel], x=x, labels=labels,
            offsets_zero=True, weights=weights, sample_idx=sample_idx,
            feature_index=feature_index))
    return buckets


def _index_map_buckets_numpy(data, shard, all_active, ent_of_active,
                             act_entity, config):
    n_active = len(act_entity)
    # --- per-entity local feature maps --------------------------------
    # For each active entity: observed shard features (optionally pruned
    # to the top max_active_features by support), compact-indexed.
    sub = shard.take(all_active)  # CSR over active rows, entity-grouped
    nnz_ent = np.repeat(ent_of_active, sub.row_counts())  # entity per nnz

    # count support per (entity, feature)
    pair_keys = nnz_ent * np.int64(shard.dim) + sub.cols.astype(np.int64)
    uniq_pairs, pair_inv, pair_support = np.unique(
        pair_keys, return_inverse=True, return_counts=True)
    pair_ent = uniq_pairs // shard.dim
    pair_feat = uniq_pairs % shard.dim

    # prune: rank features within entity by (-support, feature id)
    if config.max_active_features is not None:
        rank_order = np.lexsort((pair_feat, -pair_support, pair_ent))
        ranked_ent = pair_ent[rank_order]
        starts = _group_starts(ranked_ent)
        rank_within = np.arange(len(ranked_ent)) - np.repeat(
            starts, np.diff(np.append(starts, len(ranked_ent))))
        kept_sorted = rank_within < config.max_active_features
        kept = np.zeros(len(uniq_pairs), bool)
        kept[rank_order] = kept_sorted
    else:
        kept = np.ones(len(uniq_pairs), bool)

    # local index of each kept pair within its entity (order: feature id)
    local_idx = np.full(len(uniq_pairs), -1, np.int64)
    kept_ent = pair_ent[kept]
    starts_k = _group_starts(kept_ent)
    counts_k = np.diff(np.append(starts_k, len(kept_ent)))
    local_idx[kept] = np.arange(len(kept_ent)) - np.repeat(starts_k, counts_k)
    n_feat_per_entity = np.zeros(n_active, np.int64)
    if len(kept_ent):
        ent_u, ent_c = np.unique(kept_ent, return_counts=True)
        n_feat_per_entity[ent_u] = ent_c

    n_samp_per_entity = np.bincount(ent_of_active, minlength=n_active
                                    ).astype(np.int64)
    # one active-row index per nnz (loop-invariant over buckets)
    nnz_rows_local = np.repeat(
        np.arange(len(all_active)), sub.row_counts())

    # --- bucketing by (padded samples, padded features) ----------------
    buckets: list[REBucket] = []
    s_pad, d_pad = _padded_shapes(n_samp_per_entity, n_feat_per_entity, config)
    bucket_key = s_pad * np.int64(1 << 40) + d_pad
    # bucket id per entity, gathered ONCE onto pairs/nnz/rows: the
    # per-bucket membership tests below are then O(len) compares
    # instead of np.isin's sort-based lookups over the full nnz
    # array per bucket (measured: the dominant build cost at 10^7
    # rows — O(buckets × nnz) turned into O(nnz))
    uniq_keys, bucket_of_entity = np.unique(bucket_key,
                                            return_inverse=True)
    pair_bucket = bucket_of_entity[pair_ent]
    nnz_bucket = bucket_of_entity[nnz_ent]
    row_bucket = bucket_of_entity[ent_of_active]
    nnz_kept = local_idx[pair_inv] >= 0
    for bi, key in enumerate(uniq_keys):
        sel = np.flatnonzero(bucket_key == key)
        S = int(s_pad[sel[0]])
        D = int(d_pad[sel[0]])
        E = len(sel)
        x = np.zeros((E, S, D), np.float32)
        feature_index = np.full((E, D), -1, np.int64)

        slot_of_entity = np.full(n_active, -1, np.int64)
        slot_of_entity[sel] = np.arange(E)

        # features
        sel_pairs = kept & (pair_bucket == bi)
        pe = slot_of_entity[pair_ent[sel_pairs]]
        feature_index[pe, local_idx[sel_pairs]] = pair_feat[sel_pairs]

        # samples: rows of these entities, slot position within entity
        labels, weights, sample_idx, rows_sel, pos, es = \
            _bucket_sample_fill(data, all_active, ent_of_active,
                                slot_of_entity, sel, S,
                                rows_sel=np.flatnonzero(
                                    row_bucket == bi))

        # nnz values into local dense tensor
        nnz_sel = (nnz_bucket == bi) & nnz_kept
        # local sample position for each nnz: position of its active row
        pos_of_active_row = np.full(len(all_active), -1, np.int64)
        pos_of_active_row[rows_sel] = pos
        take = nnz_sel
        e_nnz = slot_of_entity[nnz_ent[take]]
        s_nnz = pos_of_active_row[nnz_rows_local[take]]
        d_nnz = local_idx[pair_inv[take]]
        np.add.at(x, (e_nnz, s_nnz, d_nnz), sub.vals[take])

        buckets.append(REBucket(
            entity_ids=act_entity[sel],
            x=x, labels=labels, offsets_zero=True, weights=weights,
            sample_idx=sample_idx, feature_index=feature_index))

    return buckets


def _bucket_sample_fill(
    data: GameData,
    all_active: np.ndarray,
    ent_of_active: np.ndarray,
    slot_of_entity: np.ndarray,
    sel: np.ndarray,
    n_slots: int,
    rows_sel: np.ndarray | None = None,
):
    """Scatter the selected entities' rows into bucket sample slots.

    Shared by the INDEX_MAP and RANDOM bucket builders. Returns
    ``(labels, weights, sample_idx, rows_sel, pos, es)`` where ``rows_sel``
    indexes ``all_active``, ``pos`` is each row's slot within its entity and
    ``es`` its entity's bucket lane. Callers that already know the selected
    rows (the INDEX_MAP path's precomputed bucket map) pass ``rows_sel``;
    otherwise it is derived here.
    """
    e = len(sel)
    labels = np.zeros((e, n_slots), np.float32)
    weights = np.zeros((e, n_slots), np.float32)
    sample_idx = np.full((e, n_slots), -1, np.int64)
    if rows_sel is None:
        rows_sel = np.flatnonzero(np.isin(ent_of_active, sel))
    ent_rows = ent_of_active[rows_sel]
    row_starts = _group_starts(ent_rows)
    row_counts = np.diff(np.append(row_starts, len(ent_rows)))
    pos = np.arange(len(ent_rows)) - np.repeat(row_starts, row_counts)
    es = slot_of_entity[ent_rows]
    g = all_active[rows_sel]
    labels[es, pos] = data.labels[g]
    weights[es, pos] = data.weights[g]
    sample_idx[es, pos] = g
    return labels, weights, sample_idx, rows_sel, pos, es


def _random_projection_buckets(
    data: GameData,
    shard: FeatureShard,
    all_active: np.ndarray,
    ent_of_active: np.ndarray,
    act_entity: np.ndarray,
    projector: RandomProjector,
    config: RandomEffectDatasetConfig,
) -> list[REBucket]:
    """Fixed-shape buckets in the shared projected space.

    Every entity shares the feature dim (``projected_dim``), so entities
    bucket by padded sample count only; ``feature_index`` is the identity
    into the projected space — model keys live there until
    ``RandomEffectModel.to_shard_space`` back-projects for export.
    """
    buckets: list[REBucket] = []
    n_active = len(act_entity)
    if not n_active:
        return buckets
    sub = shard.take(all_active)
    z = projector.project_rows(sub.cols, sub.vals, sub.rows(), len(all_active))
    d = projector.projected_dim
    n_samp = np.bincount(ent_of_active, minlength=n_active).astype(np.int64)
    if config.bucket_strategy == "histogram":
        s_pad = _histogram_pad(n_samp, config.max_sample_buckets)
    else:
        s_pad = _geom_at_least(n_samp, config.sample_bucket_growth)
    for s_key in np.unique(s_pad):
        sel = np.flatnonzero(s_pad == s_key)
        S, E = int(s_key), len(sel)
        x = np.zeros((E, S, d), np.float32)
        feature_index = np.tile(np.arange(d, dtype=np.int64), (E, 1))

        slot_of_entity = np.full(n_active, -1, np.int64)
        slot_of_entity[sel] = np.arange(E)
        labels, weights, sample_idx, rows_sel, pos, es = _bucket_sample_fill(
            data, all_active, ent_of_active, slot_of_entity, sel, S)
        x[es, pos, :] = z[rows_sel]

        buckets.append(REBucket(
            entity_ids=act_entity[sel],
            x=x, labels=labels, offsets_zero=True, weights=weights,
            sample_idx=sample_idx, feature_index=feature_index))
    return buckets


