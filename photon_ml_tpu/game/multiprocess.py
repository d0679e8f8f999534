"""Multi-process GAME training: entity-partitioned random effects.

The reference trains random effects sharded across machines: rows are
shuffled so each Spark executor owns complete entities
(``photon-api/.../data/RandomEffectDatasetPartitioner.scala`` — a
frequency-balanced partition map), the per-entity solves then run
executor-local with zero communication
(``algorithm/RandomEffectCoordinate.scala``), and the model stays an RDD
sharded the same way. The multi-controller-JAX analog implemented here:

- **Entity partition** (:func:`balanced_entity_partition`): a deterministic,
  frequency-balanced (longest-processing-time greedy) assignment
  entity → process, computed identically on every process from globally
  allreduced entity row counts.
- **Row shuffle** (:func:`exchange_rows`): each process starts from its own
  arbitrary row shard (host-local Avro reads) and keeps exactly the rows
  whose owner it is. Implemented over the host allgather collective —
  O(total) received per process, like Spark's shuffle volume at its
  reduce side; JAX exposes no host-side point-to-point, and the exchange
  runs once per RE entity type at dataset-build time, not per sweep.
- **Per-process datasets**: the fixed effect feeds the global ``data``-axis
  mesh via :func:`~photon_ml_tpu.parallel.multihost.global_glm_data_multihost`
  (one psum'd global solve — every process participates); each
  :class:`~photon_ml_tpu.game.data.RandomEffectDataset` is built
  per-process over that process's OWN entities only and solved on LOCAL
  devices — the executor-local zero-comm solve, verbatim.
- **Row-local score accounting**: coordinate-descent residuals live on the
  process that owns the row; the score invariant
  ``total = offsets + Σ_c scores[c]`` holds per-process. A random-effect
  coordinate whose entity type differs from the primary row partition
  exchanges residuals/scores through a host allgather per sweep (the
  analog of the reference's per-iteration score join shuffle).
- **Model assembly**: at sweep end the per-process random-effect
  (key, coefficient) tables allgather into the identical global
  :class:`~photon_ml_tpu.game.model.RandomEffectModel` on every process;
  the fixed-effect model is already replicated by the psum'd solve. The
  chief process writes outputs.

Every collective here degenerates to the identity on a single process, so
the whole pipeline runs (and is unit-tested) single-process; the 2-process
loopback test in ``tests/test_multihost.py`` exercises the real collectives
and asserts equality with the single-process result.
"""

from __future__ import annotations

import dataclasses
import heapq
import logging
import os
from typing import Mapping, Optional, Sequence

import numpy as np

from photon_ml_tpu.game.data import (
    FeatureShard,
    GameData,
    RandomEffectDataset,
    RandomEffectDatasetConfig,
    host_design_for_shard,
)
from photon_ml_tpu.game.model import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.ops.objective import GLMData
from photon_ml_tpu.resilience import fault_point, fault_value, heartbeat
from photon_ml_tpu.types import TaskType

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Entity partition (RandomEffectDatasetPartitioner analog)
# ---------------------------------------------------------------------------


def balanced_entity_partition(row_counts: np.ndarray,
                              n_processes: int) -> np.ndarray:
    """Frequency-balanced entity → process assignment.

    Longest-processing-time greedy: entities sorted by row count
    descending (ties by entity id, so the result is deterministic — every
    process must compute the SAME partition from the same counts), each
    assigned to the least-loaded process. The reference's
    ``RandomEffectDatasetPartitioner`` builds the same kind of map from a
    sampled frequency table.

    Returns an ``(n_entities,)`` int32 array of process ids. Entities with
    zero rows are still assigned (they all land on whatever process is
    least-loaded after the real entities — harmless, they carry no data),
    so the map is total.
    """
    counts = np.asarray(row_counts, np.int64)
    n_processes = int(n_processes)
    if n_processes <= 1:
        return np.zeros(len(counts), np.int32)
    order = np.lexsort((np.arange(len(counts)), -counts))
    owner = np.zeros(len(counts), np.int32)
    # (load, process) heap — process index tie-breaks deterministically
    heap = [(0, p) for p in range(n_processes)]
    heapq.heapify(heap)
    for e in order:
        load, p = heapq.heappop(heap)
        owner[e] = p
        heapq.heappush(heap, (load + int(counts[e]), p))
    return owner


# ---------------------------------------------------------------------------
# Row shuffle
# ---------------------------------------------------------------------------


def exchange_rows(game_local: GameData, dest_local: np.ndarray,
                  ) -> tuple[GameData, np.ndarray]:
    """All-to-all row shuffle: keep the rows this process owns.

    ``dest_local`` gives the destination process of each local row. Global
    row ids are defined as (process-order offset + local index) — the
    concatenation order of the host allgather — and the returned rows are
    sorted by global id, so every process's view of "its" rows is a
    deterministic slice of one global ordering (what makes the
    multi-process result comparable to a single-process run row-for-row).

    Returns ``(owned GameData, owned global row ids)``.
    """
    import jax

    from photon_ml_tpu.parallel.multihost import allgather_concat

    me = jax.process_index()
    dest_local = np.asarray(dest_local, np.int32)
    if jax.process_count() == 1:
        keep = np.flatnonzero(dest_local == me)
        return _take_rows(game_local, keep), keep.astype(np.int64)

    dest = allgather_concat(dest_local)
    keep = np.flatnonzero(dest == me).astype(np.int64)

    labels = allgather_concat(game_local.labels)[keep]
    offsets = allgather_concat(game_local.offsets)[keep]
    weights = allgather_concat(game_local.weights)[keep]
    id_columns = {k: allgather_concat(v)[keep]
                  for k, v in game_local.id_columns.items()}
    shards = {}
    for name, shard in game_local.shards.items():
        counts = allgather_concat(shard.row_counts().astype(np.int64))
        cols = allgather_concat(shard.cols)
        vals = allgather_concat(shard.vals)
        indptr = np.zeros(len(counts) + 1, np.int64)
        np.cumsum(counts, out=indptr[1:])
        shards[name] = FeatureShard(
            indptr=indptr, cols=cols, vals=vals, dim=shard.dim).take(keep)
    return GameData(labels=labels, offsets=offsets, weights=weights,
                    shards=shards, id_columns=id_columns), keep


def _take_rows(game: GameData, rows: np.ndarray) -> GameData:
    return GameData(
        labels=game.labels[rows],
        offsets=game.offsets[rows],
        weights=game.weights[rows],
        shards={k: s.take(rows) for k, s in game.shards.items()},
        id_columns={k: v[rows] for k, v in game.id_columns.items()})


def owner_of_rows(entities: np.ndarray, owner_of_entity: np.ndarray,
                  global_rows: np.ndarray, n_processes: int) -> np.ndarray:
    """Destination process per row: the row's entity's owner; rows with no
    entity (id < 0) spread round-robin by global row id so the fixed effect
    still sees balanced shards."""
    entities = np.asarray(entities, np.int64)
    dest = np.where(entities >= 0,
                    owner_of_entity[np.maximum(entities, 0)],
                    (np.asarray(global_rows, np.int64) % n_processes
                     ).astype(np.int32))
    return dest.astype(np.int32)


def process_file_share(reader, input_path) -> list[str]:
    """This process's share of the input file list — the multi-process
    drivers' read assignment (the executor-local reads of the reference).

    Shares are CONTIGUOUS runs of the sorted file list (size-balanced by
    cumulative file bytes), not strided: the global row ids every process
    derives from the process-concat order then coincide with the
    single-process sequential read order, which is what keeps every
    per-global-row-id keyed draw (down-sampling, active-bound subsampling)
    bit-identical to the single-process run. A strided share would permute
    the id ↔ record mapping and silently change the sampled sets.

    Raises when there are fewer files than processes (an empty-handed
    process would feed zero rows and desync shard budgets)."""
    import jax

    all_files = reader.paths(input_path)
    n_proc = jax.process_count()
    if n_proc > 1:
        # agree on the LISTING itself before ANY unilateral exit or further
        # collective: a file landing mid-listing (or a too-few-files exit
        # taken by one process only) must fail cleanly on every process,
        # not crash some and hang the rest at the next collective
        import hashlib

        from photon_ml_tpu.parallel.multihost import allgather_concat
        digest = hashlib.sha256("\0".join(all_files).encode()).digest()[:8]
        h = np.frombuffer(digest, np.uint32).astype(np.float64)
        sig = allgather_concat(
            np.array([float(len(all_files)), h[0], h[1]])).reshape(n_proc, 3)
        if not (sig == sig[:1]).all():
            raise SystemExit(
                "--multihost: the input file listing diverges across "
                "processes (different lengths or names) — every process "
                "must see the same files; re-run once the input directory "
                "is stable")
    # symmetric from here on: every process sees the same listing, so this
    # exit (and every later decision) fires on all processes or none
    if len(all_files) < n_proc:
        raise SystemExit(
            f"--multihost with {n_proc} processes needs at "
            f"least that many input files (got {len(all_files)}; split "
            f"the data)")
    try:
        sizes = np.array([max(os.path.getsize(f), 1) for f in all_files],
                         np.float64)
    except OSError:
        # non-stat-able paths (e.g. remote URIs a reader may accept)
        sizes = None
    if n_proc > 1:
        # stat results can still diverge across hosts (a file renamed
        # between the two passes, host-local disks): keep byte-size
        # balancing only when every process saw the same sizes, else
        # equal-count shares — the cuts below must be IDENTICAL everywhere
        from photon_ml_tpu.parallel.multihost import allgather_concat
        ok = sizes is not None
        local = np.concatenate(
            [[float(ok)], sizes if ok else np.zeros(len(all_files))])
        rows = allgather_concat(local).reshape(n_proc, len(all_files) + 1)
        if (rows[:, 0] == 1.0).all() and (rows == rows[:1]).all():
            sizes = rows[0, 1:]
        else:
            sizes = np.ones(len(all_files), np.float64)
    elif sizes is None:
        sizes = np.ones(len(all_files), np.float64)
    # cut the cumulative-size curve into n_proc near-equal spans, keeping
    # every span non-empty (each process must read at least one file)
    cum = np.cumsum(sizes)
    targets = cum[-1] * (np.arange(1, n_proc) / n_proc)
    cuts = np.searchsorted(cum, targets, side="left") + 1
    # enforce strictly increasing interior cuts within [1, len-...] so no
    # share is empty even with one huge file
    bounds = [0]
    for i, c in enumerate(cuts):
        lo = bounds[-1] + 1
        hi = len(all_files) - (n_proc - 1 - i)
        bounds.append(int(min(max(c, lo), hi)))
    bounds.append(len(all_files))
    pid = jax.process_index()
    return all_files[bounds[pid]:bounds[pid + 1]]


# ---------------------------------------------------------------------------
# Global id agreement (feature index maps + entity vocabularies)
# ---------------------------------------------------------------------------


def reconcile_global_ids(data: GameData, index_maps, vocabs,
                         id_columns=()):
    """Make per-process feature index maps and entity vocabularies GLOBAL.

    Under multi-process training each process reads its own file subset
    (the reference's executor-local HDFS reads), so locally-built feature
    indices and entity vocabularies disagree across processes. This unions
    the key sets through a host allgather, rebuilds them in the canonical
    deterministic order (:func:`~photon_ml_tpu.io.index.build_index_map`'s
    sorted order for features — identical to what a single-process read of
    ALL files would build — and sorted raw ids for vocabularies), and
    remaps this process's columns in place.

    Returns the remapped ``(data, index_maps, vocabs)``. Collective: every
    process must call with the same shard/vocab key sets requested
    (``id_columns`` pins the vocabulary iteration order, since a process
    that saw no rows for a column would otherwise skip its collectives).
    """
    from photon_ml_tpu.io.index import build_index_map
    from photon_ml_tpu.parallel.multihost import allgather_concat_strings
    from photon_ml_tpu.types import INTERCEPT_KEY

    new_maps = {}
    new_shards = dict(data.shards)
    for sid in sorted(index_maps):
        imap = index_maps[sid]
        local_names = imap.names()
        union = set(allgather_concat_strings(local_names))
        gmap = build_index_map(union,
                               add_intercept=INTERCEPT_KEY in union)
        perm = np.array([gmap.key_to_index[k] for k in local_names],
                        np.int32)
        shard = data.shards[sid]
        new_shards[sid] = dataclasses.replace(
            shard, cols=(perm[shard.cols] if len(shard.cols)
                         else shard.cols), dim=len(gmap))
        new_maps[sid] = gmap

    data = dataclasses.replace(data, shards=new_shards)
    data, new_vocabs = reconcile_vocabs(data, vocabs, id_columns)
    return data, new_maps, new_vocabs


def reconcile_vocabs(data: GameData, vocabs, id_columns=()):
    """The entity-vocabulary half of :func:`reconcile_global_ids` alone —
    for drivers whose FEATURE index maps are preset (scoring loads them
    with the model and must not re-key the coefficient tables) but whose
    grouped-metric id tags still need one global id space. Collective;
    identity-shaped at one process (modulo canonical re-sort)."""
    from photon_ml_tpu.parallel.multihost import allgather_concat_strings

    new_vocabs = {}
    new_ids = dict(data.id_columns)
    for col in sorted(set(id_columns) | set(vocabs)):
        vocab = vocabs.get(col, {})
        # vocab values are a permutation of range(len): invert to the
        # local id -> raw string table (every slot gets filled)
        local_names = [""] * len(vocab)
        for k, i in vocab.items():
            local_names[i] = k
        union = sorted(set(allgather_concat_strings(local_names)))
        gvocab = {k: i for i, k in enumerate(union)}
        perm = np.array([gvocab[k] for k in local_names], np.int64)
        ids = data.id_columns.get(col)
        if ids is not None and len(perm):
            new_ids[col] = np.where(ids >= 0, perm[np.maximum(ids, 0)],
                                    np.int64(-1))
        new_vocabs[col] = gvocab

    return dataclasses.replace(data, id_columns=new_ids), new_vocabs


# ---------------------------------------------------------------------------
# Multi-process fixed-effect dataset (global data-axis feed, re-fed offsets)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MultiProcessFixedEffectDataset:
    """Fixed-effect data fed once onto the global ``data``-axis mesh; only
    the per-sweep residual offsets are re-fed (the multi-process analog of
    :class:`~photon_ml_tpu.game.data.FixedEffectDataset`'s per-sweep
    ``glm_data``). Rows are this process's owned rows; every process's
    blocks compose into the one global sharded layout.
    """

    coordinate_id: str
    feature_shard_id: str
    design: object
    labels: object
    weights: object
    dim: int
    n_local_rows: int
    n_local_blocks: int
    rows_per_shard: int
    mesh: object
    n_shards: int

    @staticmethod
    def build(coordinate_id: str, game_owned: GameData,
              feature_shard_id: str, mesh,
              *, dense_max_dim: Optional[int] = None,
              design_dtype: str = "float32",
              ) -> "MultiProcessFixedEffectDataset":
        from photon_ml_tpu.game.data import (
            cast_dense_design,
            choose_dense_design_stats,
            design_dtype_of,
        )
        from photon_ml_tpu.parallel.mesh import DATA_AXIS
        from photon_ml_tpu.parallel.multihost import (
            allreduce_max,
            allreduce_sum,
            global_glm_data_multihost,
            local_axis_blocks,
        )

        shard = game_owned.shards[feature_shard_id]
        # layout decision on GLOBAL stats: local (n, nnz) differ per
        # process, and an SPMD program needs every process on one layout.
        # The host cap uses the LARGEST process's local n (the binding
        # host materialization), max-reduced so everyone agrees.
        g = allreduce_sum(np.array([shard.n_samples, shard.nnz], np.int64))
        n_loc = int(allreduce_max(np.array([shard.n_samples], np.int64))[0])
        dense = choose_dense_design_stats(
            int(g[0]), shard.dim, int(g[1]),
            n_shards=int(mesh.shape[DATA_AXIS]), dense_max_dim=dense_max_dim,
            n_local_samples=n_loc,
            itemsize=design_dtype_of(design_dtype).itemsize)
        host_design = host_design_for_shard(shard, force_dense=dense)
        # every process runs the same CLI flags, so the dtype decision is
        # symmetric; the budget reconciliation below is dtype-independent
        host_design = cast_dense_design(host_design, design_dtype)
        local = GLMData(design=host_design, labels=game_owned.labels,
                        offsets=np.zeros(shard.n_samples, np.float32),
                        weights=game_owned.weights)
        fed = global_glm_data_multihost(local, mesh)
        return MultiProcessFixedEffectDataset(
            coordinate_id=coordinate_id, feature_shard_id=feature_shard_id,
            design=fed.design, labels=fed.labels, weights=fed.weights,
            dim=shard.dim, n_local_rows=shard.n_samples,
            n_local_blocks=local_axis_blocks(mesh, DATA_AXIS),
            rows_per_shard=int(fed.labels.shape[1]), mesh=mesh,
            n_shards=int(mesh.shape[DATA_AXIS]))

    def _feed_rowvec(self, local_values) -> object:
        """Place one per-local-row float32 vector into the global
        ``(n_shards, rows_per_shard)`` data-axis layout (tail zero-padded)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_ml_tpu.parallel.mesh import DATA_AXIS

        per = self.rows_per_shard
        buf = np.zeros(self.n_local_blocks * per, np.float32)
        buf[:self.n_local_rows] = np.asarray(local_values, np.float32)
        return jax.make_array_from_process_local_data(
            NamedSharding(self.mesh, P(DATA_AXIS)),
            buf.reshape(self.n_local_blocks, per),
            (self.n_shards, per))

    def glm_data(self, local_offsets, local_weights=None) -> GLMData:
        """Bind this process's residual offsets into the global layout.
        ``local_weights`` (per-sweep down-sampled weights) replaces the
        static weight vector for this solve only."""
        return GLMData(
            design=self.design, labels=self.labels,
            offsets=self._feed_rowvec(local_offsets),
            weights=(self.weights if local_weights is None
                     else self._feed_rowvec(local_weights)))

    def local_scores(self, scores) -> np.ndarray:
        """Pull this process's rows out of a globally-sharded ``(n_shards,
        rows_per_shard)`` score array (drop local tail padding). Shards are
        deduped by data-axis block: on a mesh with extra axes the score
        vector is replicated across them, and counting each replica would
        duplicate rows."""
        by_block = {}
        for s in scores.addressable_shards:
            by_block.setdefault(s.index[0].start or 0, s)
        flat = np.concatenate([np.asarray(by_block[k].data).reshape(-1)
                               for k in sorted(by_block)])
        return flat[:self.n_local_rows]


# ---------------------------------------------------------------------------
# The multi-process coordinate-descent driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MultiProcessGameResult:
    model: GameModel  # identical on every process
    #: this process's rows: global ids and per-coordinate scores
    global_rows: np.ndarray
    scores: dict[str, np.ndarray]
    #: per-sweep validation metric dicts (empty without a validation set) —
    #: identical on every process
    validation_history: list = dataclasses.field(default_factory=list)


# ---------------------------------------------------------------------------
# Sweep-boundary checkpointing (per-process state files)
# ---------------------------------------------------------------------------
#
# The single-process CoordinateDescent checkpoints per coordinate step
# (io/checkpoint.py). Multi-process state is row-partitioned — each
# process's residual scores cover only ITS rows and its random-effect
# tables only ITS entities — so each process persists its own shard
# (proc-<pid>/sweep-<k>.npz, atomic tmp+rename) at every sweep boundary,
# fingerprint-guarded like the single-process manager. Resume agrees on
# min(latest sweep) across processes, so a process that died mid-save
# just replays its last complete sweep. The reference's recovery story is
# the same shape: deterministic re-entry from written models (SURVEY §5.3).


def _mp_ckpt_dir(root: str) -> str:
    import jax

    return os.path.join(root, f"proc-{jax.process_index()}")


def _mp_ckpt_save(root: str, sweep: int, fingerprint: str,
                  scores: Mapping[str, np.ndarray],
                  re_local_models: Mapping[str, RandomEffectModel],
                  fe_models: Mapping[str, FixedEffectModel],
                  validation_history: Sequence[Mapping] = (),
                  trained_projection_cids: frozenset = frozenset()) -> None:
    import json as _json

    d = _mp_ckpt_dir(root)
    os.makedirs(d, exist_ok=True)
    payload: dict[str, np.ndarray] = {}
    if validation_history:
        # per-sweep metric dicts ride along so a resumed run returns the
        # FULL history, not just the sweeps after the resume point
        payload["history"] = np.frombuffer(
            _json.dumps(list(validation_history)).encode("utf-8"), np.uint8)
    for cid, s in scores.items():
        payload[f"score::{cid}"] = np.asarray(s, np.float32)
    for cid, m in re_local_models.items():
        payload[f"rekeys::{cid}"] = m.keys
        payload[f"recoef::{cid}"] = m.coeffs
        if m.variances is not None:
            payload[f"revar::{cid}"] = m.variances
        payload[f"remeta::{cid}"] = np.array(
            [m.dim], np.int64)
        if m.projector is not None and cid in trained_projection_cids:
            # a FACTORED coordinate's projection is TRAINED state (not
            # seed-derived like the RANDOM projector, which the load path
            # reconstructs from config) — it must survive resume or
            # restored latents would score through the initial P
            payload[f"reproj::{cid}"] = np.asarray(
                m.projector.matrix, np.float32)
    for cid, m in fe_models.items():
        payload[f"few::{cid}"] = np.asarray(m.model.coefficients.means,
                                            np.float32)
        v = m.model.coefficients.variances
        if v is not None:
            payload[f"fev::{cid}"] = np.asarray(v, np.float32)
    payload["fingerprint"] = np.frombuffer(
        fingerprint.encode("utf-8"), np.uint8)

    from photon_ml_tpu.resilience import fault_point, retry

    def attempt() -> None:
        tmp = os.path.join(d, f".sweep-{sweep}.npz.tmp")
        with open(tmp, "wb") as f:
            np.savez(f, **payload)
        # crash-mid-write window: payload fully written, rename pending —
        # a kill here must leave the previous sweep as the loadable latest
        fault_point("ckpt.save", step=sweep, path=d, scope="mp")
        os.replace(tmp, os.path.join(d, f"sweep-{sweep}.npz"))

    retry(attempt, name=f"ckpt.save:mp-sweep-{sweep}")
    # prune like the single-process manager (io/checkpoint.py keep=3): a
    # 10M-row score decomposition is ~10s of MB per sweep per process
    kept = sorted(
        (int(n[len("sweep-"):-len(".npz")]) for n in os.listdir(d)
         if n.startswith("sweep-") and n.endswith(".npz")), reverse=True)
    for old in kept[3:]:
        try:
            os.unlink(os.path.join(d, f"sweep-{old}.npz"))
        except OSError:
            pass


def _mp_ckpt_latest(root: str) -> int:
    """Latest complete sweep saved by THIS process (-1: none)."""
    d = _mp_ckpt_dir(root)
    if not os.path.isdir(d):
        return -1
    best = -1
    for name in os.listdir(d):
        if name.startswith("sweep-") and name.endswith(".npz"):
            try:
                best = max(best, int(name[len("sweep-"):-len(".npz")]))
            except ValueError:
                pass
    return best


def _mp_ckpt_load(root: str, sweep: int, fingerprint: str, task,
                  re_templates: Mapping[str, RandomEffectModel],
                  fe_templates: Mapping[str, object]):
    """Restore this process's (scores, re_local_models, fe_models).

    ``re_templates``/``fe_templates`` carry the non-array fields (types,
    shard ids, the seed-derived projector) from the current configuration
    — state files hold arrays only, and a configuration mismatch is
    caught by the fingerprint (which hashes the run shape AND every
    coordinate's configuration repr)."""
    with np.load(os.path.join(_mp_ckpt_dir(root),
                              f"sweep-{sweep}.npz")) as z:
        saved_fp = bytes(z["fingerprint"]).decode("utf-8")
        if saved_fp != fingerprint:
            raise ValueError(
                f"checkpoint fingerprint mismatch under {root!r}: saved "
                f"{saved_fp!r} != current {fingerprint!r} — the run "
                "configuration or row partition changed; delete the "
                "checkpoint directory to start fresh")
        scores = {k[len("score::"):]: z[k] for k in z.files
                  if k.startswith("score::")}
        re_models = {}
        for k in z.files:
            if not k.startswith("rekeys::"):
                continue
            cid = k[len("rekeys::"):]
            t = re_templates[cid]
            if f"reproj::{cid}" in z.files:
                # trained projection (factored coordinate) restored verbatim
                from photon_ml_tpu.game.projector import RandomProjector

                projector = RandomProjector(matrix=z[f"reproj::{cid}"])
            else:
                # seed-derived, identical on every process — must survive
                # resume or a projected-space model would score raw ids
                projector = t.projector
            re_models[cid] = RandomEffectModel(
                random_effect_type=t.random_effect_type,
                feature_shard_id=t.feature_shard_id, task=task,
                dim=int(z[f"remeta::{cid}"][0]),
                keys=z[f"rekeys::{cid}"], coeffs=z[f"recoef::{cid}"],
                variances=(z[f"revar::{cid}"]
                           if f"revar::{cid}" in z.files else None),
                projector=projector)
        fe_models = {}
        for k in z.files:
            if not k.startswith("few::"):
                continue
            cid = k[len("few::"):]
            fe_models[cid] = FixedEffectModel(
                model=GeneralizedLinearModel(
                    coefficients=Coefficients(
                        means=z[k],
                        variances=(z[f"fev::{cid}"]
                                   if f"fev::{cid}" in z.files else None)),
                    task=task),
                feature_shard_id=fe_templates[cid].feature_shard_id)
        history = []
        if "history" in z.files:
            import json as _json

            history = _json.loads(bytes(z["history"]).decode("utf-8"))
    return scores, re_models, fe_models, history


@dataclasses.dataclass(frozen=True)
class _FactoredPlan:
    """Per-process plan for a factored coordinate: owned rows + config (the
    per-alternation datasets rebuild around the trained projection)."""

    cfg: object  # FactoredRandomEffectCoordinateConfig
    game: GameData
    global_rows: np.ndarray
    primary: bool


@dataclasses.dataclass(frozen=True)
class _REPlan:
    config: RandomEffectDatasetConfig
    optimization: GLMOptimizationConfiguration
    #: owned rows for THIS coordinate's entity type
    game: GameData
    global_rows: np.ndarray
    dataset: RandomEffectDataset
    #: True when this coordinate's rows coincide with the primary partition
    primary: bool


def _train_factored_mp(coord, global_rows: np.ndarray, offsets,
                       warm, fe_mesh):
    """Multi-process factored training: the per-entity LATENT solves run
    process-local exactly like any random effect (rows are grouped with
    their owned entities), and the shared-projection update — a GLM in
    ``vec(P)`` — runs as one psum'd global solve over the data mesh, the
    same machinery as the fixed effect. Mirrors
    :meth:`FactoredRandomEffectCoordinate.train` step for step; global row
    ids key the active-bound subsample so dataset builds stay
    partition-invariant."""
    import jax.numpy as jnp

    from photon_ml_tpu.game.coordinate import _factored_projection_cache
    from photon_ml_tpu.game.factored import FactoredDesign
    from photon_ml_tpu.game.projector import RandomProjector
    from photon_ml_tpu.game.random_effect import RandomEffectSolver
    from photon_ml_tpu.parallel.multihost import global_glm_data_multihost

    shard = coord.data.shards[coord.dataset_config.feature_shard_id]
    if warm is not None and warm.projector is not None:
        p = warm.projector.matrix
    else:
        p = RandomProjector.build(
            shard.dim, coord.latent_dim, coord.dataset_config.seed).matrix
    solver = RandomEffectSolver(task=coord.task, config=coord.config,
                                mesh=coord.mesh)
    x_host = shard.to_dense()
    entities = coord.data.id_columns[coord.dataset_config.random_effect_type]
    # one compiled DISTRIBUTED projection solve per (task, config, mesh):
    # the Khatri-Rao design rows shard over the global data mesh and the
    # solve psums, so every process computes the identical shared projection
    run_fn = _factored_projection_cache(
        coord.task, coord.projection_config, fe_mesh)
    offsets_np = np.asarray(offsets, np.float32)
    latent = warm
    fed = None
    for _ in range(max(1, coord.n_factored_iterations)):
        projector = RandomProjector(matrix=p)
        dataset = RandomEffectDataset.build(
            coord.coordinate_id, coord.data, coord._ds_config,
            projector=projector, sample_uids=global_rows)
        latent, _ = solver.train(dataset, offsets_np, coord.lam,
                                 warm_start=latent)
        v = coord._latent_table(latent, entities).astype(np.float32)
        if fed is None:
            # first alternation pays the full budget-reconciled feed; the
            # design's x / labels / weights / offsets are loop-invariant
            # (the single-chip counterpart builds x_dev once the same way),
            # so later alternations re-feed ONLY v
            local = GLMData(
                design=FactoredDesign(x=x_host, v=v,
                                      latent_dim=coord.latent_dim),
                labels=coord.data.labels, offsets=offsets_np,
                weights=coord.data.weights)
            fed = global_glm_data_multihost(local, fe_mesh)
        else:
            fed = dataclasses.replace(
                fed, design=FactoredDesign(
                    x=fed.design.x, v=_feed_stacked(v, fe_mesh,
                                                    fed.labels.shape[1]),
                    latent_dim=coord.latent_dim))
        result = run_fn(fed, jnp.asarray(p.reshape(-1)),
                        jnp.asarray(coord.lam_projection, jnp.float32))
        p = np.asarray(result.w, np.float32).reshape(
            coord.latent_dim, x_host.shape[1])
    # final latent solve so the returned (v, P) pair is consistent
    projector = RandomProjector(matrix=p)
    dataset = RandomEffectDataset.build(
        coord.coordinate_id, coord.data, coord._ds_config,
        projector=projector, sample_uids=global_rows)
    latent, _ = solver.train(dataset, offsets_np, coord.lam,
                             warm_start=latent)
    return latent, np.asarray(latent.score(coord.data), np.float32)


def _feed_stacked(a: np.ndarray, mesh, per: int):
    """Place one per-local-row array (trailing dims preserved) into the
    mesh's global data-axis layout at an already-agreed ``per`` — the
    cheap re-feed for loop-varying leaves (the factored solve's v).

    LAYOUT CONTRACT with ``parallel.distributed.shard_glm_data``: local
    rows fill CONTIGUOUSLY with zero padding at the tail, then reshape to
    ``(n_local_blocks, per, ...)`` row-major. The re-fed leaf must align
    row-for-row with the labels/weights blocks the first full feed built;
    if shard_glm_data's stacking ever changes, this helper must change
    with it (a mismatch would silently scramble rows)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from photon_ml_tpu.parallel.mesh import DATA_AXIS
    from photon_ml_tpu.parallel.multihost import local_axis_blocks

    a = np.asarray(a, np.float32)
    n_local = local_axis_blocks(mesh, DATA_AXIS)
    buf = np.zeros((n_local * per,) + a.shape[1:], np.float32)
    buf[:a.shape[0]] = a
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(DATA_AXIS)),
        buf.reshape((n_local, per) + a.shape[1:]),
        (int(mesh.shape[DATA_AXIS]), per) + a.shape[1:])


def _allgather_rowvec(global_rows: np.ndarray, values: np.ndarray,
                      n_global: int) -> np.ndarray:
    """Assemble a replicated global row vector from per-process slices."""
    from photon_ml_tpu.parallel.multihost import allgather_concat

    rows = allgather_concat(np.asarray(global_rows, np.int64))
    vals = allgather_concat(np.asarray(values, np.float32))
    out = np.zeros(n_global, np.float32)
    out[rows] = vals
    return out


def train_game_multiprocess(
    game_local: GameData,
    task: TaskType,
    coordinate_configs: Mapping[str, object],
    update_sequence: Sequence[str],
    lam: Mapping[str, float],
    n_cd_iterations: int = 1,
    fe_mesh=None,
    re_mesh=None,
    checkpoint_dir: Optional[str] = None,
    resume: bool = False,
    initial_models: Optional[Mapping[str, object]] = None,
    locked: Sequence[str] = (),
    validation: Optional[tuple] = None,
    guard=None,  # Optional[photon_ml_tpu.resilience.DivergenceGuard]
) -> MultiProcessGameResult:
    """Run GAME coordinate descent across all processes.

    ``game_local`` is THIS process's row shard (any partition — e.g. its
    host-local Avro files); ``coordinate_configs`` maps coordinate id to
    :class:`~photon_ml_tpu.game.estimator.FixedEffectCoordinateConfig` or
    :class:`~photon_ml_tpu.game.estimator.RandomEffectCoordinateConfig`.
    The primary row partition follows the FIRST random-effect coordinate in
    ``update_sequence`` (additional RE types exchange residuals per sweep);
    with no random effects, rows stay on their reading process.

    ``fe_mesh`` must be a global mesh with a ``data`` axis (default:
    :func:`~photon_ml_tpu.parallel.multihost.make_multihost_mesh`);
    ``re_mesh`` an optional LOCAL mesh with an ``entity`` axis for the
    per-process bucket solves.

    ``initial_models``/``locked`` are the reference's partial-retrain path,
    with single-process semantics: every process holds the (identical,
    loaded-from-disk) initial models, scores are seeded row-locally, locked
    coordinates keep their model and are never retrained. ``validation``
    (``(GameData, evaluators)``; the validation data must be read in full
    on EVERY process) enables per-sweep validation tracking: the global
    model is assembled at each sweep boundary and evaluated — identical on
    every process since model and data are. History is in the result.

    ``guard`` (a :class:`~photon_ml_tpu.resilience.DivergenceGuard`)
    enables divergence rollback: each coordinate step's NaN/Inf verdict is
    allreduce-maxed so every process rolls back (bumping the coordinate's
    regularization) or freezes the coordinate in lockstep. Fault plans in
    multi-process runs must be seeded identically on every process —
    injected faults then fire symmetrically, which is what keeps the
    collective schedule aligned through a recovery.
    """
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.coordinate import (
        RandomEffectCoordinate,
        _fixed_train_fn_dist,
    )
    from photon_ml_tpu.game.estimator import (
        FactoredRandomEffectCoordinateConfig,
        FixedEffectCoordinateConfig,
        RandomEffectCoordinateConfig,
    )
    from photon_ml_tpu.parallel.multihost import (
        allgather_concat,
        allreduce_max,
        allreduce_sum,
        make_multihost_mesh,
    )

    n_proc = jax.process_count()
    locked = set(locked)
    initial_models = dict(initial_models or {})
    for cid in locked:
        if cid not in initial_models:
            raise KeyError(f"locked coordinate {cid!r} needs an initial model")
    missing_seq = locked - set(update_sequence)
    if missing_seq:
        # single-process semantics (GameEstimator._check_sequence): a locked
        # coordinate outside the sequence would silently drop from the model
        raise ValueError(
            f"locked coordinates {sorted(missing_seq)} must appear in the "
            f"update sequence")
    for cid in update_sequence:
        if cid not in coordinate_configs and cid not in locked:
            raise KeyError(f"update sequence names unknown coordinate {cid!r}")

    n_local = game_local.n_samples
    # one gather yields both the global row count and this process's base
    counts = allgather_concat(np.array([n_local], np.int64))
    n_global = int(counts.sum())
    base = int(np.concatenate([[0], np.cumsum(counts)])[jax.process_index()])
    local_global_rows = base + np.arange(n_local, dtype=np.int64)

    # --- entity partitions: one owner map per RE entity type --------------
    # locked coordinates never train, so they need no dataset build, no
    # entity partition, and no say in the primary row partition
    re_types = [coordinate_configs[cid].dataset.random_effect_type
                for cid in update_sequence
                if cid not in locked
                and isinstance(coordinate_configs[cid],
                               (RandomEffectCoordinateConfig,
                                FactoredRandomEffectCoordinateConfig))]
    owner_by_type: dict[str, np.ndarray] = {}
    for t in dict.fromkeys(re_types):  # ordered unique
        ents = game_local.id_columns[t]
        n_ent = int(allreduce_max(
            np.array([ents.max() + 1 if len(ents) else 0], np.int64))[0])
        counts = allreduce_sum(np.bincount(
            ents[ents >= 0], minlength=max(n_ent, 1)).astype(np.int64))
        owner_by_type[t] = balanced_entity_partition(counts, n_proc)

    # --- primary row partition + shuffle ----------------------------------
    primary_type = re_types[0] if re_types else None
    if primary_type is None:
        # fixed-effects only: rows stay where they were read — no shuffle
        game_primary, primary_rows = game_local, local_global_rows
    else:
        # ship only what the primary-partition coordinates read: fixed
        # shards + the primary RE coordinate's shard and entity column
        # (non-primary coordinates run their own slim exchange below)
        need_shards = set()
        for cid in update_sequence:
            if cid in locked:
                continue
            cfg = coordinate_configs[cid]
            if isinstance(cfg, FixedEffectCoordinateConfig):
                need_shards.add(cfg.feature_shard_id)
            elif (isinstance(cfg, (RandomEffectCoordinateConfig,
                                   FactoredRandomEffectCoordinateConfig))
                  and cfg.dataset.random_effect_type == primary_type):
                need_shards.add(cfg.dataset.feature_shard_id)
        slim_primary = GameData(
            labels=game_local.labels, offsets=game_local.offsets,
            weights=game_local.weights,
            shards={k: v for k, v in game_local.shards.items()
                    if k in need_shards},
            id_columns={primary_type: game_local.id_columns[primary_type]})
        dest = owner_of_rows(game_local.id_columns[primary_type],
                             owner_by_type[primary_type],
                             local_global_rows, n_proc)
        game_primary, primary_rows = exchange_rows(slim_primary, dest)

    # --- per-coordinate builds --------------------------------------------
    if fe_mesh is None:
        fe_mesh = make_multihost_mesh()
    fe_datasets: dict[str, MultiProcessFixedEffectDataset] = {}
    re_plans: dict[str, _REPlan] = {}
    factored_plans: dict[str, _FactoredPlan] = {}
    for cid in update_sequence:
        if cid in locked:
            continue  # frozen: no dataset, scores seeded from the model
        cfg = coordinate_configs[cid]
        if isinstance(cfg, FixedEffectCoordinateConfig):
            # (downsamplers are supported: the per-sweep draw is the keyed
            # per-global-row-id hash, identical under any row partition)
            fe_datasets[cid] = MultiProcessFixedEffectDataset.build(
                cid, game_primary, cfg.feature_shard_id, fe_mesh,
                design_dtype=cfg.design_dtype)
        elif isinstance(cfg, (RandomEffectCoordinateConfig,
                              FactoredRandomEffectCoordinateConfig)):
            t = cfg.dataset.random_effect_type
            if t == primary_type:
                game_c, rows_c, is_primary = game_primary, primary_rows, True
            else:
                # exchange only what this coordinate reads — its feature
                # shard and entity column — not the whole dataset (the
                # allgather otherwise ships every shard to every process)
                slim = GameData(
                    labels=game_local.labels, offsets=game_local.offsets,
                    weights=game_local.weights,
                    shards={cfg.dataset.feature_shard_id:
                            game_local.shards[cfg.dataset.feature_shard_id]},
                    id_columns={t: game_local.id_columns[t]})
                dest_c = owner_of_rows(
                    game_local.id_columns[t], owner_by_type[t],
                    local_global_rows, n_proc)
                game_c, rows_c = exchange_rows(slim, dest_c)
                is_primary = False
            if isinstance(cfg, FactoredRandomEffectCoordinateConfig):
                # latent solves are process-local like any random effect;
                # datasets rebuild per alternation (the projector is the
                # trained object), so the plan carries data, not a dataset
                factored_plans[cid] = _FactoredPlan(
                    cfg=cfg, game=game_c, global_rows=rows_c,
                    primary=is_primary)
                continue
            # rows of owned entities are complete here by construction, so
            # the per-process dataset covers exactly its entities; global
            # row ids key the active-bound subsample draw so the kept
            # subset matches the single-process build exactly
            ds = RandomEffectDataset.build(cid, game_c, cfg.dataset,
                                           sample_uids=rows_c)
            re_plans[cid] = _REPlan(
                config=cfg.dataset, optimization=cfg.optimization,
                game=game_c, global_rows=rows_c, dataset=ds,
                primary=is_primary)
        else:
            raise TypeError(
                f"coordinate {cid!r}: multi-process training supports fixed, "
                f"random, and factored random effects "
                f"(got {type(cfg).__name__})")

    # --- coordinate descent with row-local score accounting ---------------
    scores: dict[str, np.ndarray] = {
        cid: np.zeros(len(primary_rows), np.float32)
        for cid in update_sequence}
    models: dict[str, object] = {}
    re_local_models: dict[str, RandomEffectModel] = {}

    # seed from initial models (partial-retrain warm start; single-process
    # CD semantics): scores computed ROW-LOCALLY on the original read
    # partition — game_local holds every shard/id column, where the slim
    # primary exchange ships only what training reads — then mapped onto
    # the primary partition through the replicated global vector
    for cid, m0 in initial_models.items():
        if cid not in update_sequence:
            continue
        models[cid] = m0
        if isinstance(m0, RandomEffectModel) and cid not in locked:
            # the GLOBAL table warm-starts the local solves (the bucket →
            # key-table join handles the superset transparently)
            re_local_models[cid] = m0
        sc_local = np.asarray(m0.score(game_local), np.float32)
        g = _allgather_rowvec(local_global_rows, sc_local, n_global)
        scores[cid] = g[primary_rows].astype(np.float32)

    start_sweep = 0
    fingerprint = None
    resumed_history: list = []
    if checkpoint_dir is not None:
        import hashlib
        import json

        fingerprint = hashlib.sha1(json.dumps({
            "n_proc": n_proc,
            "task": str(task),
            "sequence": list(update_sequence),
            "lam": sorted((c, float(lam.get(c, 0.0)))
                          for c in update_sequence),
            # every coordinate's full configuration (optimizer, bounds,
            # regularization, shard ids) — resuming under a changed config
            # must fail loudly, not blend incompatible state
            "configs": {c: repr(coordinate_configs.get(c))
                        for c in update_sequence},
            "locked": sorted(locked),
            # resuming under different seed models must fail loudly too
            "initial": {c: hashlib.sha1(np.asarray(
                m.coeffs if isinstance(m, RandomEffectModel)
                else m.model.coefficients.means,
                np.float32).tobytes()).hexdigest()
                for c, m in sorted(initial_models.items())},
            "n_global": n_global,
            "rows": hashlib.sha1(
                np.ascontiguousarray(primary_rows).tobytes()).hexdigest(),
        }, sort_keys=True).encode()).hexdigest()
        if resume:
            # every process resumes from the newest sweep ALL of them
            # completed (a process that died mid-save replays its last
            # complete one)
            latest = -allreduce_max(
                np.array([-_mp_ckpt_latest(checkpoint_dir)], np.int64))
            agreed = int(latest[0])
            if agreed >= 0:
                re_templates = {
                    cid: RandomEffectModel(
                        random_effect_type=p.config.random_effect_type,
                        feature_shard_id=p.config.feature_shard_id,
                        task=task, dim=0, keys=np.zeros(0, np.int64),
                        coeffs=np.zeros(0, np.float32),
                        projector=p.dataset.projector)
                    for cid, p in re_plans.items()}
                re_templates.update({
                    cid: RandomEffectModel(
                        random_effect_type=p.cfg.dataset.random_effect_type,
                        feature_shard_id=p.cfg.dataset.feature_shard_id,
                        task=task, dim=0, keys=np.zeros(0, np.int64),
                        coeffs=np.zeros(0, np.float32),
                        projector=None)  # learned P rides in the state file
                    for cid, p in factored_plans.items()})
                from photon_ml_tpu.resilience import retry as _retry

                (saved_scores, saved_re, fe_models,
                 resumed_history) = _retry(
                    lambda: _mp_ckpt_load(
                        checkpoint_dir, agreed, fingerprint, task,
                        re_templates, fe_datasets),
                    name=f"ckpt.restore:mp-sweep-{agreed}")
                re_local_models.update(saved_re)
                scores.update(saved_scores)
                models.update(fe_models)
                # the RE coordinates' contribution to the GLOBAL model also
                # comes back from the local tables at assembly time below
                start_sweep = agreed + 1
                logger.info("mp resumed from checkpoint sweep %d", agreed)

    total = game_primary.offsets.astype(np.float32) + sum(
        scores[cid] for cid in update_sequence)

    # memo for the assembly: the final model after the last sweep is the
    # same object the last validation step assembled — don't repeat the
    # RE-table allgathers. Cleared whenever any coordinate trains.
    assembled_memo: list = []

    def _assemble_global_model() -> GameModel:
        """Allgather the per-process RE tables into the (identical on every
        process) global model — at sweep boundaries when validation tracks
        per-sweep metrics, and once at the end."""
        if assembled_memo:
            return assembled_memo[0]
        out = dict(models)
        for cid, local_model in re_local_models.items():
            if local_model is initial_models.get(cid):
                continue  # still the seeded global table — nothing local
            keys = allgather_concat(local_model.keys)
            coeffs = allgather_concat(local_model.coeffs)
            has_var = local_model.variances is not None
            variances = (allgather_concat(local_model.variances)
                         if has_var else None)
            order = np.argsort(keys, kind="stable")
            out[cid] = RandomEffectModel(
                random_effect_type=local_model.random_effect_type,
                feature_shard_id=local_model.feature_shard_id,
                task=task, dim=local_model.dim,
                keys=keys[order], coeffs=coeffs[order],
                variances=None if variances is None else variances[order],
                # RANDOM-projected models keep their (shared, seed-derived —
                # identical on every process) projector so scoring still
                # maps shard features into the projected key space
                projector=local_model.projector)
        gm = GameModel(
            coordinates={cid: out[cid] for cid in update_sequence},
            task=task)
        assembled_memo.append(gm)
        return gm

    validation_history: list[dict] = list(resumed_history)
    lam = dict(lam)  # guard retries bump a coordinate's weight in place
    for sweep in range(start_sweep, n_cd_iterations):
        heartbeat("mp.sweep")
        fault_point("worker.stall", sweep=sweep)
        for cid in update_sequence:
            heartbeat("mp.step")
            if cid in locked:
                continue  # frozen: scores stay as seeded
            if (guard is not None and cid in guard.frozen
                    and (cid in models or cid in re_local_models)):
                # diverged earlier THIS run: locked at last good model (a
                # fresh run sharing the guard — the next grid point —
                # retrains under its new regularization)
                continue
            cfg = coordinate_configs[cid]
            while True:
                residual = total - scores[cid]
                prev_fe = models.get(cid)
                prev_re = re_local_models.get(cid)
                step_error = None
                new_model = None
                new_scores = None
                try:
                    if cid in fe_datasets:
                        ds = fe_datasets[cid]
                        w_sweep = None
                        if cfg.downsampler is not None:
                            # keyed per-global-row-id draw: the kept set is
                            # a pure per-row function, so every partition of
                            # the rows — including the single-process run —
                            # samples identically
                            w_sweep = cfg.downsampler.downsample(
                                game_primary.labels, game_primary.weights,
                                sweep=sweep, uids=primary_rows)
                        data = ds.glm_data(residual, local_weights=w_sweep)
                        w0 = (jnp.zeros((ds.dim,), jnp.float32)
                              if cid not in models else
                              jnp.asarray(models[cid].model.coefficients.means))
                        train_fn = _fixed_train_fn_dist(
                            task, cfg.optimization, fe_mesh)
                        result, variances, g_scores = train_fn(
                            data, w0,
                            jnp.asarray(lam.get(cid, 0.0), jnp.float32))
                        new_scores = ds.local_scores(g_scores)
                        models[cid] = new_model = FixedEffectModel(
                            model=GeneralizedLinearModel(
                                coefficients=Coefficients(
                                    means=np.asarray(result.w),
                                    variances=(None if variances is None
                                               else np.asarray(variances))),
                                task=task),
                            feature_shard_id=ds.feature_shard_id)
                    else:
                        plan = re_plans.get(cid) or factored_plans[cid]
                        if plan.primary:
                            res_c = residual
                        else:
                            # residuals live on primary owners; this
                            # coordinate's rows live on ITS entity owners —
                            # exchange via the replicated global vector (the
                            # reference's score join)
                            g_res = _allgather_rowvec(primary_rows, residual,
                                                      n_global)
                            res_c = g_res[plan.global_rows]
                        if cid in re_plans:
                            coord = RandomEffectCoordinate(
                                coordinate_id=cid, dataset=plan.dataset,
                                data=plan.game, task=task,
                                config=plan.optimization,
                                lam=lam.get(cid, 0.0), mesh=re_mesh,
                                design_dtype=getattr(coordinate_configs[cid],
                                                     "design_dtype",
                                                     "float32"))
                            model_c, scores_c = coord.train(
                                res_c, re_local_models.get(cid), sweep=sweep)
                        else:
                            from photon_ml_tpu.game.factored import (
                                FactoredRandomEffectCoordinate,
                            )

                            fcfg = plan.cfg
                            fcoord = FactoredRandomEffectCoordinate(
                                coordinate_id=cid, data=plan.game,
                                dataset_config=fcfg.dataset, task=task,
                                config=fcfg.optimization,
                                projection_config=fcfg.projection_optimization,
                                lam=lam.get(cid, 0.0),
                                lam_projection=fcfg.lam_projection,
                                n_factored_iterations=fcfg.n_factored_iterations,
                                mesh=re_mesh)
                            model_c, scores_c = _train_factored_mp(
                                fcoord, plan.global_rows, res_c,
                                re_local_models.get(cid), fe_mesh)
                        re_local_models[cid] = new_model = model_c
                        sc = np.asarray(scores_c, np.float32)
                        if plan.primary:
                            new_scores = sc
                        else:
                            g_sc = _allgather_rowvec(plan.global_rows, sc,
                                                     n_global)
                            new_scores = g_sc[primary_rows]
                    new_scores = fault_value("optimizer.step", new_scores,
                                             coordinate=cid, sweep=sweep)
                except FloatingPointError as e:
                    # only a non-finite report (jax_debug_nans) is
                    # divergence; any other exception propagates — see
                    # game/coordinate_descent.py
                    if guard is None:
                        raise
                    # deterministic faults raise SYMMETRICALLY (the plan's
                    # decisions are a pure function of seeded counters), so
                    # every process lands here together and the verdict
                    # collective below stays aligned
                    step_error = e
                if guard is None:
                    break
                # the guard verdict is COLLECTIVE: a local isfinite could
                # differ across row shards, and a split verdict would
                # desync every later collective — allreduce_max so all
                # processes roll back (or not) in lockstep
                local_ok = (step_error is None
                            and guard.healthy(new_model, new_scores))
                bad = int(allreduce_max(
                    np.array([0 if local_ok else 1], np.int64))[0]) > 0
                if not bad:
                    break
                # roll back this coordinate's in-process state to the last
                # good model (identical on every process, like the verdict)
                if prev_fe is None:
                    models.pop(cid, None)
                else:
                    models[cid] = prev_fe
                if prev_re is None:
                    re_local_models.pop(cid, None)
                else:
                    re_local_models[cid] = prev_re
                action = guard.on_divergence(
                    cid, sweep=sweep,
                    has_good_model=(prev_fe is not None
                                    or prev_re is not None
                                    or cid in initial_models),
                    error=step_error)
                if action == "freeze":
                    new_scores = None
                    break
                lam[cid] = guard.next_lam(lam.get(cid, 0.0))
            if new_scores is None:
                continue  # frozen mid-sweep: nothing to commit
            assembled_memo.clear()  # model state changed
            total = residual + new_scores
            scores[cid] = new_scores
            logger.info("mp sweep %d coordinate %s done", sweep, cid)
        if validation is not None:
            # per-sweep validation tracking (single-process CD semantics:
            # CoordinateDescent evaluates every sweep). Model and
            # validation data are identical on every process, so each
            # evaluates independently and identically — no collective.
            from photon_ml_tpu.evaluation import evaluate_all

            vdata, evaluators = validation
            gm = _assemble_global_model()
            results = evaluate_all(
                evaluators, gm.score(vdata), vdata.labels,
                weights=vdata.weights, id_tags=vdata.id_columns)
            validation_history.append(results.as_dict())
            logger.info("mp sweep %d validation: %s", sweep, results)
        if checkpoint_dir is not None:
            # saved AFTER the sweep's validation entry so a resume returns
            # the full per-sweep history, not just the post-resume tail
            _mp_ckpt_save(checkpoint_dir, sweep, fingerprint, scores,
                          {cid: m for cid, m in re_local_models.items()
                           if m is not initial_models.get(cid)},
                          {cid: m for cid, m in models.items()
                           if cid in fe_datasets},
                          validation_history=validation_history,
                          trained_projection_cids=frozenset(factored_plans))
        # fleet-metrics fold point. COLLECTIVE when --metrics-port installed
        # the fold hook: every process reaches this line once per sweep (the
        # loop above is already collective-symmetric), so the allgather
        # inside the hook stays aligned. No hook (the default) is a no-op.
        from photon_ml_tpu.telemetry.aggregate import sweep_boundary

        sweep_boundary(sweep=sweep)

    # --- model assembly: allgather RE tables ------------------------------
    model = _assemble_global_model()
    return MultiProcessGameResult(
        model=model, global_rows=primary_rows, scores=scores,
        validation_history=validation_history)
