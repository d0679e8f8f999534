"""GAME model layer: fixed-effect, random-effect, and composite GAME models.

Re-design of the reference's model layer
(``photon-api/.../model/{GameModel, FixedEffectModel, RandomEffectModel,
DatumScoringModel}.scala``). A ``GameModel`` is an ordered map
coordinateId → per-coordinate model; total score of a sample is the sum of
coordinate scores plus the data offset — the invariant coordinate descent's
residual bookkeeping relies on (SURVEY.md §7 hard-parts #6).

The reference keeps the fixed effect as broadcast coefficients and random
effects as ``RDD[(REId, GLM)]``. Here the fixed effect is a single device
coefficient vector, and a random-effect model is a flat **(entity, feature) →
coefficient** table in host numpy: per-entity coefficient blocks from the
bucketed solves, flattened and key-sorted so scoring any dataset is one
searchsorted join — the vectorized equivalent of the reference's
score-time RDD join (``model/RandomEffectModel.scala``).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Mapping, Optional, Sequence

import numpy as np

from photon_ml_tpu.game.data import FeatureShard, GameData
from photon_ml_tpu.game.projector import RandomProjector
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.util import materialize_thunk

#: guards lazy-thunk materialization (RandomEffectModel coeffs/variances,
#: GameModel.materialize's batched pull) — see util.materialize_thunk.
#: Materialization is rare — one global lock is enough.
_THUNK_LOCK = threading.Lock()


@dataclasses.dataclass(frozen=True)
class FixedEffectModel:
    """Global coefficients for one fixed-effect coordinate
    (reference ``model/FixedEffectModel.scala``)."""

    model: GeneralizedLinearModel
    feature_shard_id: str

    def score(self, data: GameData) -> np.ndarray:
        """Raw margins w·x per sample (no offset; CD owns the accounting)."""
        shard = data.shards[self.feature_shard_id]
        w = np.asarray(self.model.coefficients.means, np.float64)
        out = np.zeros(data.n_samples, np.float64)
        np.add.at(out, shard.rows(),
                  shard.vals.astype(np.float64) * w[shard.cols])
        return out.astype(np.float32)


def sum_coordinate_margins(offsets, margins, xp=np):
    """THE GAME score-summation contract: ``f32(f64(offset) + Σ f64(mᵢ))``
    accumulated in coordinate order.

    Single home of the total-score arithmetic, shared by the batch path
    (:meth:`GameModel.score`, ``GameTransformer``'s per-coordinate
    breakdown total) and the online serving engine
    (:mod:`photon_ml_tpu.serving.engine`) — the online/batch bit-parity
    guarantee rests on both paths running THIS reduction. ``xp`` is numpy
    for the host batch path or ``jax.numpy`` inside the jitted online path
    (where float64 requires ``jax_enable_x64``; without it the engine
    degrades to f32 accumulation and parity is approximate).
    """
    total = xp.asarray(offsets).astype(xp.float64)
    for m in margins:
        total = total + xp.asarray(m).astype(xp.float64)
    return total.astype(xp.float32)


def key_join(keys: np.ndarray, dim: int, entity_ids: np.ndarray,
             feature_ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Sorted-table join for (entity, feature) pairs: ``(pos, found)``.

    The single home of the ``entity·dim + feature`` searchsorted-and-verify
    idiom (model lookup, the passive-scoring cache, the device warm-start
    cache). ``found`` is False for negative entity/feature ids and for pairs
    absent from ``keys``; ``pos`` is clipped in-range everywhere so it is
    always safe to gather with.
    """
    valid = (np.asarray(entity_ids) >= 0) & (np.asarray(feature_ids) >= 0)
    q = (np.maximum(entity_ids, 0).astype(np.int64) * np.int64(dim)
         + np.maximum(feature_ids, 0).astype(np.int64))
    pos = np.searchsorted(keys, q)
    pos = np.minimum(pos, max(len(keys) - 1, 0))
    found = (valid & (keys[pos] == q) if len(keys)
             else np.zeros(q.shape, bool))
    return pos, found


@dataclasses.dataclass(frozen=True)
class RandomEffectModel:
    """Per-entity coefficient table for one random-effect coordinate.

    ``keys`` are ``entity_id * dim + feature_id`` (int64, sorted);
    ``coeffs`` the matching coefficient values; entities absent from the
    table score 0 (the reference's behavior for entities dropped by the
    active-data lower bound). ``variances`` is optional, aligned with
    ``coeffs``.

    With a ``projector`` (reference ``projector/RandomProjection.scala``),
    the table lives in the projected space: ``dim`` is the projected dim,
    feature ids index projected coordinates, and scoring projects shard
    features through the shared matrix first. ``to_shard_space`` exports the
    equivalent original-space model (reference behavior: models projected
    back after training).
    """

    random_effect_type: str
    feature_shard_id: str
    task: TaskType
    dim: int  # key modulus: shard vocabulary size, or projected dim
    keys: np.ndarray  # (k,) int64, sorted
    #: (k,) float32 — the solver may install a zero-arg THUNK returning
    #: ``(coeffs, variances)`` instead of the arrays: the device→host pull
    #: of the coefficient table then happens on first ACCESS, not at
    #: construction, so coordinate descent can dispatch the next
    #: coordinate's programs while this one's are still executing (each
    #: eager pull was a full pipeline barrier). ``__getattribute__``
    #: materializes transparently; everything downstream sees ndarrays.
    coeffs: np.ndarray
    variances: Optional[np.ndarray] = None
    projector: Optional["RandomProjector"] = None
    #: same values as ``coeffs`` still resident on device (set by the
    #: solver; None after IO round-trips) — lets coordinate descent's
    #: passive scoring run on-device instead of re-uploading the table
    coeffs_device: Optional[object] = dataclasses.field(
        default=None, compare=False, repr=False)

    def __getattribute__(self, name):
        if name in ("coeffs", "variances"):
            val = object.__getattribute__(self, name)
            if callable(val):
                materialize_thunk(self, ("coeffs", "variances"), _THUNK_LOCK)
                return object.__getattribute__(self, name)
            return val
        return object.__getattribute__(self, name)

    @property
    def n_entities(self) -> int:
        return int(np.unique(self.keys // self.dim).shape[0]) if len(self.keys) else 0

    def lookup(self, entity_ids: np.ndarray, feature_ids: np.ndarray) -> np.ndarray:
        """Coefficient for each (entity, feature) pair; 0 where absent."""
        pos, found = key_join(self.keys, self.dim, entity_ids, feature_ids)
        out = np.zeros(found.shape, np.float32)
        out[found] = self.coeffs[pos[found]]
        return out

    def entity_coefficients(self, entity_id: int) -> dict[int, float]:
        """Sparse coefficient dict of one entity (for inspection/IO)."""
        lo = np.searchsorted(self.keys, entity_id * self.dim)
        hi = np.searchsorted(self.keys, (entity_id + 1) * self.dim)
        return {int(k % self.dim): float(v)
                for k, v in zip(self.keys[lo:hi], self.coeffs[lo:hi])}

    def score(self, data: GameData,
              sample_idx: Optional[np.ndarray] = None) -> np.ndarray:
        """Margins from this coordinate: sum_j x_j * w[entity, j] per sample.

        With ``sample_idx``, scores only those rows (returned in that order)
        — the passive-data scoring path of coordinate descent.
        """
        shard = data.shards[self.feature_shard_id]
        entities = data.id_columns[self.random_effect_type]
        if sample_idx is not None:
            shard = shard.take(sample_idx)
            entities = entities[sample_idx]
        if self.projector is not None:
            return self._score_projected(shard, entities)
        rows = shard.rows()
        ent_per_nnz = entities[rows]
        valid = ent_per_nnz >= 0
        w = np.zeros(shard.nnz, np.float32)
        if valid.any():
            w[valid] = self.lookup(ent_per_nnz[valid], shard.cols[valid])
        out = np.zeros(shard.n_samples, np.float64)
        np.add.at(out, rows, shard.vals.astype(np.float64) * w)
        return out.astype(np.float32)

    def _score_projected(self, shard: FeatureShard,
                         entities: np.ndarray) -> np.ndarray:
        """Margin v·(Px) per sample: project features to the shared space
        (dense MXU-friendly block), then join per-entity coefficients."""
        z = self.projector.project_rows(
            shard.cols, shard.vals, shard.rows(), shard.n_samples)
        valid = np.flatnonzero(entities >= 0)
        out = np.zeros(shard.n_samples, np.float32)
        if len(valid):
            d = self.dim
            # coefficient table per *unique* entity, then gather per sample —
            # O(u·d) lookups instead of O(n·d)
            uniq, inv = np.unique(entities[valid], return_inverse=True)
            ent = np.repeat(uniq, d)
            feat = np.tile(np.arange(d, dtype=np.int64), len(uniq))
            table = self.lookup(ent, feat).reshape(len(uniq), d)
            out[valid] = np.einsum("nd,nd->n", z[valid], table[inv])
        return out

    def merge(self, update: "RandomEffectModel",
              drop_entities: Sequence[int] = ()) -> "RandomEffectModel":
        """Entity-level patch merge: entities present in ``update`` (or
        listed in ``drop_entities``) have their rows REPLACED by (resp.
        dropped in favor of) the update's; every other entity's rows carry
        forward bit-identically. The continuous-training loop's model-side
        counterpart of :meth:`photon_ml_tpu.serving.store.
        EntityCoefficientStore.apply_patch` — both sides must agree on the
        replace-whole-entity semantics or a patched serving table and the
        published merged model would drift.

        Both models must live in the same key space (same ``dim``, same
        dense entity-id universe, no projector). Variances survive only
        when BOTH sides carry them (a mixed merge would leave the variance
        table misaligned with the coefficients).
        """
        if update.random_effect_type != self.random_effect_type:
            raise ValueError(
                f"merge across random-effect types "
                f"{self.random_effect_type!r} != {update.random_effect_type!r}")
        if update.dim != self.dim:
            raise ValueError(f"merge across dims {self.dim} != {update.dim}")
        if self.projector is not None or update.projector is not None:
            raise ValueError("merge expects shard-space models "
                             "(call to_shard_space() first)")
        upd_entities = (np.unique(update.keys // self.dim)
                        if len(update.keys) else np.zeros(0, np.int64))
        drop = np.union1d(np.asarray(list(drop_entities), np.int64),
                          upd_entities)
        keep = (~np.isin(self.keys // self.dim, drop) if len(self.keys)
                else np.zeros(0, bool))
        keys = np.concatenate([self.keys[keep], update.keys])
        coeffs = np.concatenate([
            np.asarray(self.coeffs, np.float32)[keep],
            np.asarray(update.coeffs, np.float32)])
        variances = None
        if self.variances is not None and update.variances is not None:
            variances = np.concatenate([
                np.asarray(self.variances, np.float32)[keep],
                np.asarray(update.variances, np.float32)])
        order = np.argsort(keys, kind="stable")
        return RandomEffectModel(
            random_effect_type=self.random_effect_type,
            feature_shard_id=self.feature_shard_id, task=self.task,
            dim=self.dim, keys=keys[order], coeffs=coeffs[order],
            variances=None if variances is None else variances[order])

    def remap_entities(self, new_of_old: Mapping[int, int]
                       ) -> "RandomEffectModel":
        """The same coefficients under a different dense entity-id
        universe (``old dense id → new dense id``). Dense ids are a
        per-run artifact of vocabulary order; a patch loaded under its own
        vocabulary must be remapped into the serving store's universe
        before :meth:`merge`. Every entity must be mapped — a silent drop
        here would silently lose a patched entity."""
        if not len(self.keys):
            return self
        ent = self.keys // self.dim
        feat = self.keys % self.dim
        lut = np.full(int(ent.max()) + 1, -1, np.int64)
        for old, new in new_of_old.items():
            if 0 <= int(old) < len(lut):
                lut[int(old)] = int(new)
        new_ent = lut[ent]
        if (new_ent < 0).any():
            missing = np.unique(ent[new_ent < 0])[:5]
            raise KeyError(
                f"remap_entities: no mapping for dense entities "
                f"{missing.tolist()}")
        keys = new_ent * np.int64(self.dim) + feat
        order = np.argsort(keys, kind="stable")
        return dataclasses.replace(
            self, keys=keys[order],
            coeffs=np.asarray(self.coeffs, np.float32)[order],
            variances=(None if self.variances is None
                       else np.asarray(self.variances, np.float32)[order]),
            coeffs_device=None)

    def entity_rows(self, dense_ids: Sequence[int]) -> np.ndarray:
        """Dense ``(len(dense_ids), dim)`` coefficient rows for the given
        entities (0 where absent) — the layout a serving table patch
        overwrites rows with."""
        ids = np.asarray(list(dense_ids), np.int64)
        out = np.zeros((len(ids), self.dim), np.float32)
        if not len(self.keys) or not len(ids):
            return out
        ent = self.keys // self.dim
        feat = self.keys % self.dim
        pos_of = {int(e): i for i, e in enumerate(ids)}
        mask = np.isin(ent, ids)
        rows = np.fromiter((pos_of[int(e)] for e in ent[mask]), np.int64,
                           count=int(mask.sum()))
        out[rows, feat[mask]] = np.asarray(self.coeffs, np.float32)[mask]
        return out

    def to_shard_space(self) -> "RandomEffectModel":
        """Back-project a RANDOM-projected model to original feature space
        (``w = Pᵀ v`` — exact for scoring since margins are linear). The
        result is dense per entity; used for Avro export parity."""
        if self.projector is None:
            return self
        p = self.projector
        d, full = p.projected_dim, p.shard_dim
        if not len(self.keys):
            return dataclasses.replace(self, dim=full, projector=None)
        ent = np.unique(self.keys // d)
        v = np.zeros((len(ent), d), np.float32)
        pos = np.searchsorted(ent, self.keys // d)
        v[pos, self.keys % d] = self.coeffs
        w = p.project_back(v)
        keys = (ent[:, None] * np.int64(full)
                + np.arange(full, dtype=np.int64)).ravel()
        variances = None
        if self.variances is not None:
            var_v = np.zeros((len(ent), d), np.float32)
            var_v[pos, self.keys % d] = self.variances
            variances = p.project_back_variances(var_v).ravel()
        return RandomEffectModel(
            random_effect_type=self.random_effect_type,
            feature_shard_id=self.feature_shard_id, task=self.task,
            dim=full, keys=keys, coeffs=w.ravel().astype(np.float32),
            variances=variances, projector=None)


@dataclasses.dataclass(frozen=True)
class GameModel:
    """Ordered coordinateId → model map (reference ``model/GameModel.scala``)."""

    coordinates: Mapping[str, FixedEffectModel | RandomEffectModel]
    task: TaskType

    def device_wait(self) -> None:
        """Block until every pending device program behind this model's
        tables has finished, WITHOUT pulling the tables host-side: one
        1-element transfer from the last coordinate's device payload.  The
        per-coordinate solve programs are chained by data dependencies
        (each consumes the previous sweep's score state), so that single
        pull transitively drains them all.  Gives stage walls the
        reference's synchronous-stage semantics (GameTrainingDriver's
        ``Timed`` blocks): train = compute, save = IO plus one batched
        transfer.  The pull is a barrier by construction (the value must
        exist to arrive); it is kept as bench.py's timing discipline."""
        import jax

        last = None
        for m in self.coordinates.values():
            if isinstance(m, RandomEffectModel):
                thunk = object.__getattribute__(m, "coeffs")
                dev = getattr(thunk, "device_payload", None) \
                    if callable(thunk) else None
                if dev is not None:
                    last = dev
            elif isinstance(m, FixedEffectModel):
                arr = m.model.coefficients.means
                if isinstance(arr, jax.Array):
                    last = arr
        if last is not None:
            np.asarray(last.reshape(-1)[:1])

    def materialize(self) -> None:
        """Pull every coordinate's device-resident table host-side in ONE
        concatenated transfer (each individual pull pays a full host↔device
        round trip; its cost on the present chip: not measured). Random-effect
        models expose their pending sweep payload on the lazy-coeffs thunk;
        fixed-effect coefficients are jax arrays. No-op when everything is
        already host-resident."""
        import jax

        import jax.numpy as jnp

        # same lock as __getattribute__: a thread touching m.coeffs while
        # the driver materializes must not run a thunk twice
        with _THUNK_LOCK:
            self._materialize_locked(jax, jnp)

    def _materialize_locked(self, jax, jnp) -> None:
        jobs = []  # (install_fn, flat_device_array)
        for m in self.coordinates.values():
            if isinstance(m, RandomEffectModel):
                thunk = object.__getattribute__(m, "coeffs")
                dev = getattr(thunk, "device_payload", None) \
                    if callable(thunk) else None
                if dev is None:
                    continue

                def install_re(flat, m=m, thunk=thunk):
                    c, v = thunk(flat)
                    object.__setattr__(m, "coeffs", c)
                    object.__setattr__(m, "variances", v)

                jobs.append((install_re, dev))
            elif isinstance(m, FixedEffectModel):
                coeffs = m.model.coefficients
                for field in ("means", "variances"):
                    arr = getattr(coeffs, field)
                    if isinstance(arr, jax.Array):

                        def install_fe(flat, coeffs=coeffs, field=field,
                                       shape=arr.shape):
                            # copy out of the shared transfer buffer: a
                            # reshape view would let in-place mutation of
                            # one coordinate's array silently alter
                            # another's (RE installs already build fresh
                            # arrays via mask-indexing — no copy needed)
                            object.__setattr__(coeffs, field,
                                               flat.reshape(shape).copy())

                        jobs.append((install_fe, arr.reshape(-1)))
        if not jobs:
            return
        sizes = [int(d.shape[0]) for _, d in jobs]
        flat = np.asarray(
            jnp.concatenate([d.astype(jnp.float32) for _, d in jobs]))
        bounds = np.cumsum([0] + sizes)
        for (install, _), lo, hi in zip(jobs, bounds[:-1], bounds[1:]):
            install(flat[lo:hi])

    def score(self, data: GameData) -> np.ndarray:
        """Total margin per sample: offsets + sum of coordinate scores."""
        return sum_coordinate_margins(
            data.offsets,
            (m.score(data) for m in self.coordinates.values()))

    def score_by_coordinate(self, data: GameData) -> dict[str, np.ndarray]:
        return {cid: m.score(data) for cid, m in self.coordinates.items()}
