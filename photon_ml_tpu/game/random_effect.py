"""Random-effect training: vmapped per-entity solves over fixed-shape buckets.

TPU-native replacement for the reference's per-entity training
(``photon-api/.../algorithm/RandomEffectCoordinate.scala`` +
``optimization/game/{RandomEffectOptimizationProblem,
SingleNodeOptimizationProblem}.scala``): where the reference zips an RDD of
per-entity breeze problems with per-entity local datasets and runs millions of
scalar-loop solves inside executors, here every size bucket is ONE batched
compiled solve whose lanes are the bucket's entities. An L-BFGS bucket (every
cell's) runs ``optimize/lbfgs.py::minimize_lbfgs_lanes``: one ``while_loop``
over the batched state, one evaluation a lane a trip, every lane at its own
place in its own solve, so the bucket runs what its slowest lane evaluates
(``passes`` on the ``game.re.solve`` span). OWL-QN and TRON buckets are still
the single solve's nested loops under ``vmap`` (``minimize_owlqn``,
``minimize_tron``: convergence is per-lane masked inside them, a finished
lane stops changing), which share the inner loop's trips among the running
lanes; the fixed effect's single solve keeps the nested ``minimize_lbfgs``,
which works a direction out once an iteration (``lbfgs.py`` says why the
loops are two).

A coordinate's sweep is one traced body, :func:`_sweep_fused_impl`: it
scatters the residual offsets into the buckets' padded slots, then a bucket at
a time joins the warm start out of the previous sweep's coefficient table,
solves, takes margins and flattens the coefficients, and at the end looks
every row's score up among the buckets' margins; rows go in and come out
through one index of each row's slot (:meth:`RandomEffectSolver._row_slots`).
A resident dataset (``RandomEffectDatasetConfig.resident``) runs it over all
its buckets as one program a sweep; a streaming one runs the same body a
bucket a program, and waits for each before the next bucket uploads, so peak
HBM stays one bucket.
One compilation serves every sweep, cold and warm.

Padding correctness: padded sample rows carry weight 0 (contribute nothing);
padded feature columns are all-zero in x, so with zero init their gradient
component is 0 and coefficients stay exactly 0.

Entity parallelism (the reference's ``RandomEffectDatasetPartitioner``
hash-sharding of entities over executors): pass a mesh with an ``"entity"``
axis and the bucket's entity lanes shard over it via ``shard_map`` — every
chip solves its slice of entities with ZERO communication (the solves are
independent by construction), the direct analog of the reference's
executor-local ``mapValues`` solves.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.game.data import RandomEffectDataset, REBucket
from photon_ml_tpu.game.model import RandomEffectModel
from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration, OptimizationProblem
from photon_ml_tpu.ops.design import DenseDesign, lookup
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.objective import GLMData, GLMObjective
from photon_ml_tpu.parallel.mesh import ENTITY_AXIS, replicated
from photon_ml_tpu.telemetry import profiling, tracing
from photon_ml_tpu.types import TaskType, VarianceComputationType


# Sweep-program signatures this PROCESS has already compiled+executed once.
# _warm_compile's zero-data warm run exists to pay the XLA compile (and its
# jit-dispatch-cache insertion) off the critical path — but a driver called
# twice in one process (bench warm runs, sweeps over configs, notebooks)
# would re-EXECUTE the whole zero sweep on device per call: ~0.9 s of the
# warm e2e wall was train() joining a background thread that was re-running
# an already-compiled program on zeros. Holds HASHES of (solver, sample
# count, bucket shapes, warm-table length) signatures — storing the tuples
# themselves would retain solvers/meshes forever in a long sweep process; a
# hash collision merely skips one warm-up (jit compiles at first real call).
_PRECOMPILED: set[int] = set()

#: the span a bucket's solve records (one per bucket per coordinate step),
#: by which the benchmark's readers find the per-lane counts
SOLVE_SPAN = "game.re.solve"


def _bucket_keys(bucket: REBucket, shard_dim: int) -> np.ndarray:
    """Model-table keys for one bucket's kept (entity, feature) slots —
    ``entity_id * shard_dim + shard_feature_id`` over ``feature_index >= 0``,
    in bucket slot order. The single home of the key layout: the host table
    assembly and the dataset-static key cache must agree exactly."""
    fmask = bucket.feature_index >= 0
    ent = np.broadcast_to(bucket.entity_ids[:, None],
                          bucket.feature_index.shape)
    return ent[fmask] * np.int64(shard_dim) + bucket.feature_index[fmask]


@dataclasses.dataclass(frozen=True)
class RandomEffectSolver:
    """Per-coordinate solver configuration bound to a task type.

    ``mesh``/``entity_axis`` opt into entity-parallel solves: bucket entity
    lanes are padded to a multiple of the axis size and sharded over it.
    """

    task: TaskType
    config: GLMOptimizationConfiguration
    mesh: Optional[Mesh] = None
    entity_axis: str = ENTITY_AXIS
    #: "float32" or "bfloat16" — per-entity design dtype on device and on
    #: the wire (labels/weights/coefficients stay f32; margins accumulate
    #: f32 via preferred_element_type)
    design_dtype: str = "float32"
    #: engage the single-pass Pallas entity kernel inside the bucket solves
    #: (ops/pallas_re.py): each L-BFGS evaluation then reads the bucket's
    #: design ONCE, laid entities-last once a solve, instead of XLA's
    #: margins-then-gradient double pass.
    #: Inert off-TPU (without ``fused_interpret``) and for lanes whose
    #: ``(S, D)`` the kernel's gate declines (VMEM-oversized ones) — those
    #: keep the XLA closed form transparently, same gate discipline as the
    #: fixed effect's ``GLMObjective(fused=True)``.
    fused: bool = True
    #: testing only: run the entity kernel through the Pallas interpreter
    #: on non-TPU backends (orders of magnitude slower than XLA)
    fused_interpret: bool = False

    @property
    def _x_dtype(self):
        return jnp.bfloat16 if self.design_dtype == "bfloat16" \
            else jnp.float32

    def __post_init__(self):
        if (self.mesh is not None
                and self.entity_axis not in getattr(self.mesh, "shape", {})):
            # a data-only (or feature-only) mesh has no entity lanes to
            # shard over — solve unsharded rather than KeyError
            object.__setattr__(self, "mesh", None)
        if self.config.optimizer_config.track_states:
            # traces would be carried per entity lane; force off
            object.__setattr__(self, "config", dataclasses.replace(
                self.config, optimizer_config=dataclasses.replace(
                    self.config.optimizer_config, track_states=False)))

    def _problem(self) -> OptimizationProblem:
        objective = GLMObjective(loss=loss_for_task(self.task),
                                 fused_entity=self.fused,
                                 fused_interpret=self.fused_interpret)
        return OptimizationProblem(objective, self.config)

    def _lane_axes(self) -> tuple[str, ...]:
        """Every mesh axis name, entity last — bucket lanes shard over ALL
        of them, for two reasons. Correctness: the lane shard_map runs with
        ``check_vma=False`` (the while_loop carries defeat the checker), so
        an out_spec that left a mesh axis unmentioned would make the
        output's replication over that axis UNVERIFIED — and GSPMD
        consumers then disagree about it (a gather takes one replica, a
        reshape/concatenate sums them: the exact-``data``-width inflation
        the 2D-mesh estimator tests pinned). Mentioning every axis leaves
        nothing unverified. Parallelism: the per-entity solves have no
        cross-lane communication at all, so a 2D ``(data, entity)`` mesh
        solves ``data*entity`` lanes at once instead of idling the data
        groups."""
        names = [a for a in self.mesh.axis_names if a != self.entity_axis]
        return tuple(names) + (self.entity_axis,)

    def _put(self, a, pad_value=0):
        """Pad the entity dim to the mesh axis size and shard lanes over it.

        Padded lanes carry all-zero data and weights (``pad_value=0``), so
        their gradient is exactly the L2 term at w=0 (zero) — they converge
        immediately and their coefficients stay 0; :meth:`train` slices them
        off. The compact index arrays pad with ``-1`` instead: their masks
        (row/col >= 0) then treat padded lanes as fully absent.
        """
        a = np.asarray(a)
        if self.mesh is None:
            return jnp.asarray(a)
        # lanes shard over EVERY mesh axis (see _lane_axes): pad to the full
        # device count so each device owns a whole number of lanes
        n_dev = int(np.prod([self.mesh.shape[ax]
                             for ax in self._lane_axes()]))
        e = a.shape[0]
        e_pad = -(-e // n_dev) * n_dev
        if e_pad != e:
            a = np.concatenate(
                [a, np.full((e_pad - e,) + a.shape[1:], pad_value, a.dtype)])
        return jax.device_put(a, NamedSharding(self.mesh,
                                               P(self._lane_axes())))

    def _static_arrays(self, dataset: RandomEffectDataset, i: int,
                       bucket: REBucket):
        """Device placements of the per-sweep-invariant bucket arrays
        ``(x, labels, weights)``, cached on the dataset so each CD sweep
        re-uploads only the small dynamic inputs (warm starts). A streaming
        dataset caches nothing: upload and drop (peak HBM = one bucket
        instead of all).

        When the dataset carries source data and the shard densifies
        (:meth:`_compact_shared`), the fat tensors are materialized ON
        DEVICE by one gather through the compact index maps instead of
        being filled on host and shipped over the host→device link — the
        padded tensors are 3-4x the compact form. The gather runs ONCE per
        dataset (cached): inside the sweep program every solve would pay it
        again."""

        def build():
            shared = self._compact_shared(dataset)
            if shared is not None:
                perm_d, counts_d, fi_d = self._compact_arrays(
                    dataset, i, bucket)
                fi = bucket.feature_index
                identity = (fi.shape[1] == shared[0].shape[1]
                            and bool((fi == np.arange(fi.shape[1])).all()))
                return _materialize_fat(
                    *shared, perm_d, counts_d, fi_d,
                    S=int(bucket.sample_idx.shape[1]),
                    identity_cols=identity)
            return (self._put(bucket.x.astype(self._x_dtype)
                              if self.design_dtype != "float32"
                              else bucket.x),
                    self._put(bucket.labels),
                    self._put(bucket.weights))

        if not dataset.config.resident:
            return build()
        # the design dtype keys the cache — the built x tensors land in
        # _x_dtype, and a dataset reused across solvers with different
        # dtypes must not hit the other's cache (device_dense_shard keys by
        # dtype for the same reason)
        key = (i, self.mesh, self.entity_axis, self.design_dtype)
        cached = dataset._device_cache.get(key)
        if cached is None:
            cached = build()
            dataset._device_cache[key] = cached
        return cached

    def _compact_shared(self, dataset: RandomEffectDataset):
        """Per-run shared device arrays for the compact-upload sweep:
        ``(dense shard image, labels, weights)`` — or None when the dataset
        carries no source data or the shard is too wide to densify.

        The padded ``(E, S, D)`` bucket tensors are pure gathers of these
        through the bucket's sample/feature index maps, so shipping the
        indices and gathering ON DEVICE replaces ~3-4x-inflated bucket
        uploads with one compact CSR upload shared by every coordinate on
        the same shard — fewer bytes moved on any hardware."""
        data = dataset.source_data
        if data is None or not dataset.config.reads_shared_image:
            return None
        if self.mesh is not None:
            # entity-mesh runs keep the fat path: its per-bucket tensors
            # shard 1/n_dev per device, whereas the shared dense image would
            # be REPLICATED into every device's HBM by GSPMD — near the
            # densify byte cap that regresses peak memory by n_dev x
            return None
        shard_x = data.device_dense_shard(dataset.config.feature_shard_id,
                                          dtype=self._x_dtype)
        if shard_x is None:
            return None
        return shard_x, data.device_labels(), data.device_weights()

    def _sweep_inputs(self, dataset: RandomEffectDataset, ks, n: int,
                      warm: Optional[RandomEffectModel], shard_dim: int):
        """The sweep body's inputs for buckets ``ks``: ``(statics,
        warm_ctxs, cidxs, e_reals, row_slots)``, the first four a bucket
        each (single home, shared by train() and _warm_compile() so they
        can never pre-compile different layouts)."""
        buckets = [(k, dataset.buckets[k]) for k in ks]
        return (tuple(self._static_arrays(dataset, k, b)
                      for k, b in buckets),
                tuple(self._warm_ctx(dataset, k, b, warm, shard_dim)
                      for k, b in buckets),
                tuple(self._coef_idx(dataset, k, b) for k, b in buckets),
                tuple(b.n_entities for _, b in buckets),
                self._row_slots(dataset, tuple(ks), n))

    def _row_slots(self, dataset: RandomEffectDataset, ks: tuple, n: int):
        """Where each of the ``n`` rows stands among the padded slots of
        buckets ``ks``, their flat ``(entities, rows)`` layouts end to end:
        ``(1, n)`` int32, as ``ops/design.py::lookup`` takes an index; a row
        that none of them holds points one past the last slot. The inverse
        of the buckets' sample indices, and the one index the sweep body
        moves rows by, both ways: a row has one slot at the most, so a
        gather or a scatter over every padded slot is a scatter or a gather
        over the ``n`` rows. Built on the host once a dataset (a streaming
        dataset builds a bucket's a sweep and drops it, as its statics)."""
        key = ("rowslots", ks, n)
        slots = dataset._device_cache.get(key)
        if slots is None:
            sizes = [dataset.buckets[k].sample_idx.size for k in ks]
            host = np.full(n, sum(sizes), np.int32)
            for k, base in zip(ks, np.cumsum([0] + sizes)):
                flat = dataset.buckets[k].sample_idx.reshape(-1)
                live = np.flatnonzero((flat >= 0) & (flat < n))
                host[flat[live]] = base + live
            slots = jnp.asarray(host[None, :])
            if dataset.config.resident:
                dataset._device_cache[key] = slots
        return slots

    def _compact_arrays(self, dataset: RandomEffectDataset, i: int,
                        bucket: REBucket):
        """Device placements of one bucket's index maps (the ONLY per-bucket
        upload in compact mode), shipped PADDING-FREE: the (E, S) sample_idx
        tensor is ~4–5x its information content (histogram buckets pad S to
        the bucket cap), so it rides as ``perm`` (the active sample rows in
        entity order — the native fill packs each entity's slots at the
        front) plus per-entity ``counts``; :func:`_materialize_fat`
        rebuilds the padded index on device. feature_index (E, D) is small
        and uploads directly. This cut the 1M-row driver's index upload
        from 36 MB to ~10 MB."""
        key = ("compact", i, self.mesh, self.entity_axis)
        cached = dataset._device_cache.get(key)
        if cached is None:
            si = bucket.sample_idx
            mask = si >= 0
            counts = mask.sum(axis=1).astype(np.int32)
            perm = si[mask].astype(np.int32)
            cached = (
                jnp.asarray(perm),
                jnp.asarray(counts),
                self._put(bucket.feature_index.astype(np.int32),
                          pad_value=-1))
            dataset._device_cache[key] = cached
        return cached

    def _record_solve(self, dataset: RandomEffectDataset, i: int,
                      bucket: REBucket, counts: dict) -> None:
        """One ``game.re.solve`` span for bucket ``i``'s solve. ``counts``
        (:func:`_solve_bucket_impl`'s) are attached as device values, which
        ``telemetry/tracing.py`` resolves when the record is read or
        written: nothing here waits for the device, and where no sink or
        profiler listens nothing is kept. What the host knows of the bucket
        (its real rows, whether the entity kernel serves its lanes) is
        worked out once per dataset."""
        key = ("solve_span", i, self.design_dtype)
        static = dataset._device_cache.get(key)
        if static is None:
            _, s, d = bucket.tensor_shape
            lane = DenseDesign(x=jax.ShapeDtypeStruct((s, d), self._x_dtype))
            rows = int(np.count_nonzero(bucket.sample_idx >= 0))
            static = {
                "rows": rows, "s_max": s, "dim": d,
                # what the sweep indexes for this bucket: its real rows on
                # the way in and again on the way out, no padded slot
                "moved_slots": 2 * rows,
                "kernel": "pallas" if self._problem().objective
                ._entity_kernel_serves(lane, s, d) else "closed_form"}
            dataset._device_cache[key] = static
        with tracing.span(SOLVE_SPAN, coordinate=dataset.coordinate_id,
                          bucket=i, **static) as span:
            span.set(**counts)

    def _warm_ctx(self, dataset: RandomEffectDataset, i: int,
                  bucket: REBucket, warm: Optional[RandomEffectModel],
                  shard_dim: int):
        """(pos, found) join of bucket slots into the model key table, for
        the sweep body's in-program gather: the one warm-start route. A
        projected model's keys are ``entity * projected_dim + slot``, joined
        like any other. With no warm model (``train`` passes None for one it
        cannot use) the cached zero-join (found all-False) keeps the program
        signature — and so the compilation — identical to warm sweeps."""
        if warm is not None:
            key = ("warmidx", i, self.mesh, self.entity_axis)
            ctx = dataset._device_cache.get(key)
            # validate against the cached key TABLE, not just its shape: a
            # warm model keyed differently (trained on another dataset
            # in-process) would otherwise gather wrong coefficients through
            # a stale join. In the production CD chain keys are identical
            # every sweep, so this is one memcmp per bucket per sweep.
            if ctx is not None and not (
                    len(ctx[0]) == len(warm.keys)
                    and np.array_equal(ctx[0], warm.keys)):
                ctx = None
            if ctx is None:
                from photon_ml_tpu.game.model import key_join

                fi = bucket.feature_index  # (E, D_local)
                ent = np.broadcast_to(bucket.entity_ids[:, None], fi.shape)
                pos, found = key_join(warm.keys, shard_dim, ent, fi)
                # _put entity-pads with zeros: found pads False, so padded
                # lanes warm-start at exactly 0
                ctx = (warm.keys, self._put(pos), self._put(found))
                dataset._device_cache[key] = ctx
            return ctx[1], ctx[2]
        key = ("zeroctx", i, self.mesh, self.entity_axis)
        ctx = dataset._device_cache.get(key)
        if ctx is None:
            shape = bucket.feature_index.shape
            ctx = (self._put(np.zeros(shape, np.int64)),
                   self._put(np.zeros(shape, bool)))
            dataset._device_cache[key] = ctx
        return ctx

    def _coef_idx(self, dataset: RandomEffectDataset, i: int,
                  bucket: REBucket):
        ck = ("coeffidx", i)
        cidx = dataset._device_cache.get(ck)
        if cidx is None:
            cidx = jnp.asarray(
                np.flatnonzero(bucket.feature_index >= 0).astype(np.int32))
            dataset._device_cache[ck] = cidx
        return cidx

    def _key_table_len(self, dataset: RandomEffectDataset) -> int:
        """Length of the model key table this dataset will produce (one key
        per kept (entity, feature) slot) — the warm-coefficient arg size."""
        return sum(int((b.feature_index >= 0).sum()) for b in dataset.buckets)

    def _zero_coeffs(self, dataset: RandomEffectDataset):
        """All-zero warm-coefficient table sized like the real one, so the
        cold sweep shares the warm sweeps' compilation (cached: the fused
        program's cache also keys on argument identity-ish placement)."""
        key = ("zerocoeffs",)
        z = dataset._device_cache.get(key)
        if z is None:
            z = jnp.zeros((max(self._key_table_len(dataset), 1),),
                          jnp.float32)
            dataset._device_cache[key] = z
        return z

    @staticmethod
    def _join_warm(dataset: RandomEffectDataset) -> None:
        """Wait for a background pre-compile started at estimator
        prepare() time (so its cache loads overlap the fixed-effect
        stage)."""
        import threading

        th = getattr(dataset, "_warm_thread", None)
        if th is not None and th is not threading.current_thread():
            th.join()

    def _warm_compile(self, dataset: RandomEffectDataset, n: int) -> None:
        """Pre-compile a resident dataset's sweep program, for ``n``
        samples, on the real static arrays — which also performs the bucket
        uploads and join builds train() will reuse — against an all-zero
        offsets/warm signature that matches every later sweep. Keyed per
        dataset; later sweeps hit the program's own cache. A streaming
        dataset compiles a program a bucket at its first sweep: uploading
        here would hold what streaming exists to drop.
        """
        # a background pre-compile started at estimator prepare() time
        # finishes first; train then finds the flag set and skips
        self._join_warm(dataset)
        if getattr(dataset, "_warm_compiled", None) == (self.mesh,):
            return
        if not (dataset.config.resident and dataset.buckets):
            return
        buckets = dataset.buckets
        # the uploads/joins below are per-DATASET work train() reuses —
        # always worth doing here (overlapped with the fixed-effect
        # stage); only the zero-data execution is skippable when this
        # process already compiled the program
        statics, warm_ctxs, cidxs, e_reals, row_slots = self._sweep_inputs(
            dataset, range(len(buckets)), n, None, 0)
        sig = hash((self, n,
                    tuple((b.tensor_shape, b.n_entities) for b in buckets),
                    self._key_table_len(dataset)))
        # under a mesh the program's signature includes the placement
        # of the caller's residual vector (train() keeps a data-sharded
        # score layout), which is not known here: compiling against a
        # one-device stand-in would build a program no sweep ever runs
        if sig not in _PRECOMPILED and self.mesh is None:
            out = _sweep_fused_jit(
                self, jnp.zeros((n,), jnp.float32),
                jnp.zeros((), jnp.float32), statics, warm_ctxs,
                self._zero_coeffs(dataset), cidxs, e_reals, row_slots)
            np.asarray(out[1][:1])  # D2H pull: waits for the program
            _PRECOMPILED.add(sig)
        object.__setattr__(dataset, "_warm_compiled", (self.mesh,))

    def train(
        self,
        dataset: RandomEffectDataset,
        offsets,
        lam: float,
        warm_start: Optional[RandomEffectModel] = None,
        dim: Optional[int] = None,
    ) -> tuple[RandomEffectModel, jnp.ndarray]:
        """Train all buckets; returns (model, per-sample active scores).

        ``offsets`` is the global residual-offset vector coordinate descent
        supplies — host numpy or a device array; it stays on device either
        way (bucket gathers use device-cached sample indices, so a CD sweep
        moves no O(n_samples) data host→device). ``scores`` is a DEVICE
        vector of this coordinate's margin on every active sample
        (0 elsewhere — passive scoring is the model's job).
        """
        if dataset.projector is not None:
            # projected space: keys/coefficients live in projected_dim
            shard_dim = dataset.projector.projected_dim
        else:
            shard_dim = dim if dim is not None else _shard_dim(dataset)
        n = offsets.shape[0]
        offsets_dev = jnp.asarray(offsets, jnp.float32)
        lam_dev = jnp.asarray(lam, jnp.float32)
        self._join_warm(dataset)
        nb = len(dataset.buckets)
        if not nb:
            return (self._model(dataset, shard_dim,
                                np.zeros((0,), np.float32), None),
                    jnp.zeros(n, jnp.float32))
        if (warm_start is None or not len(warm_start.keys)
                or warm_start.dim != shard_dim):
            # a table keyed by another modulus would join another entity's
            # slots: start cold
            warm_start = None
            coeffs_warm = self._zero_coeffs(dataset)
        elif warm_start.coeffs_device is not None:
            coeffs_warm = warm_start.coeffs_device
        else:
            coeffs_warm = jnp.asarray(np.asarray(warm_start.coeffs,
                                                 np.float32))
        if self.mesh is not None:
            # the zero table (one device) and the previous sweep's table
            # (replicated over the mesh) must reach the program under
            # ONE placement, or the first warm sweep recompiles it
            coeffs_warm = jax.device_put(coeffs_warm, replicated(self.mesh))
        # preserve a caller-supplied data sharding on the score vector
        # (sharded-score prototype; None = default single-layout path)
        off_sharding = getattr(offsets_dev, "sharding", None)
        out_sharding = (off_sharding
                        if isinstance(off_sharding, NamedSharding)
                        and tuple(off_sharding.spec) else None)

        def sweep(ks):
            statics, warm_ctxs, cidxs, e_reals, row_slots = \
                self._sweep_inputs(dataset, ks, n, warm_start, shard_dim)
            scores, payload, coeffs_unsorted, counts, evaluations = \
                _sweep_fused_jit(
                    self, offsets_dev, lam_dev, statics, warm_ctxs,
                    coeffs_warm, cidxs, e_reals, row_slots,
                    out_sharding=out_sharding)
            for k, counts_k in zip(ks, counts):
                self._record_solve(dataset, k, dataset.buckets[k], counts_k)
            return scores, payload, coeffs_unsorted, evaluations

        if dataset.config.resident:
            scores, payload, coeffs_unsorted, evaluations = sweep(range(nb))
        else:
            # a bucket a program, and the host waits for it and pulls its
            # flat coefficients before the next bucket uploads: queued
            # programs would pin every bucket's design in HBM, which is
            # what streaming bounds. A bucket's rows are its own, so the
            # buckets' score vectors add up to the sweep's.
            scores, payload, coef_parts, evaluations = None, [], [], 0
            for k in range(nb):
                scores_k, payload_k, coeffs_k, evaluations_k = sweep((k,))
                payload.append(np.asarray(payload_k))
                scores = scores_k if scores is None else scores + scores_k
                coef_parts.append(coeffs_k)
                evaluations = evaluations + evaluations_k
            coeffs_unsorted = jnp.concatenate(coef_parts)
        tracing.set_on_enclosing("cd.step", evaluations=evaluations)
        return (self._model(dataset, shard_dim, payload, coeffs_unsorted),
                scores)

    def _model(self, dataset: RandomEffectDataset, shard_dim: int, payload,
               coeffs_unsorted) -> RandomEffectModel:
        """The model of one sweep, from the sweep body's flat ``payload``
        (every bucket's ``(entities, local-dim)`` coefficients, then every
        bucket's variances) and its kept coefficients in bucket slot order
        (``coeffs_unsorted``, for the device mirror). A device payload is
        pulled at the first access of ``coeffs``: coordinate descent can
        dispatch the NEXT coordinate while this one's program is still
        executing (the eager pull was a full pipeline barrier per
        coordinate). A streaming sweep's payload, the list of its buckets'
        pulls, is on the host already and is split at once."""
        cfg = dataset.config
        buckets = dataset.buckets
        want_var = self.config.variance_type != VarianceComputationType.NONE
        d_of = [b.tensor_shape[2] for b in buckets]
        w_sizes = [b.n_entities * d for b, d in zip(buckets, d_of)]
        v_sizes = [b.n_entities * (d if want_var else 0)
                   for b, d in zip(buckets, d_of)]
        bounds = np.cumsum([0] + w_sizes + v_sizes)
        nb = len(buckets)
        if isinstance(payload, list):
            payload = np.concatenate(
                [p[:w] for p, w in zip(payload, w_sizes)]
                + [p[w:] for p, w in zip(payload, w_sizes)])
        # the key table and its sort order are DATASET-static (derived
        # from bucket entity/feature indexes, not coefficients) — cached
        hk_key = ("hostkeys", shard_dim)
        hk = dataset._device_cache.get(hk_key)
        if hk is None:
            kp = [_bucket_keys(b, shard_dim) for b in buckets]
            keys_all = (np.concatenate(kp) if kp
                        else np.zeros((0,), np.int64))
            order0 = np.argsort(keys_all, kind="stable")
            hk = (keys_all[order0], order0)
            dataset._device_cache[hk_key] = hk
        keys_sorted, order = hk

        def host_tables(injected=None):
            # ``injected`` lets GameModel.materialize batch this pull
            # with every other coordinate's into one transfer.
            batched = np.asarray(payload if injected is None else injected)
            cp, vp = [], []
            for k, bucket in enumerate(buckets):
                fmask = bucket.feature_index >= 0
                w_np = batched[bounds[k]:bounds[k + 1]].reshape(
                    bucket.n_entities, -1)
                cp.append(w_np[fmask].astype(np.float32))
                if want_var:
                    v_np = batched[bounds[nb + k]:bounds[nb + k + 1]
                                   ].reshape(bucket.n_entities, -1)
                    if v_np.size:
                        vp.append(v_np[fmask].astype(np.float32))
            coeffs = (np.concatenate(cp) if cp
                      else np.zeros((0,), np.float32))
            variances = (np.concatenate(vp)[order]
                         if want_var and vp else None)
            return coeffs[order], variances

        if isinstance(payload, np.ndarray):
            coeffs, variances = host_tables()
        else:
            host_tables.device_payload = payload
            coeffs = host_tables
            variances = host_tables if want_var else None
        coeffs_device = None
        if coeffs_unsorted is not None:
            # device mirror of the sorted coefficient table (static
            # permutation, cached) — consumed by the coordinate's
            # on-device passive scoring and the next sweep's warm start
            ok = ("order",)
            order_dev = dataset._device_cache.get(ok)
            if order_dev is None:
                order_dev = jnp.asarray(np.asarray(order, np.int32))
                dataset._device_cache[ok] = order_dev
            coeffs_device = coeffs_unsorted[order_dev]
        return RandomEffectModel(
            random_effect_type=cfg.random_effect_type,
            feature_shard_id=cfg.feature_shard_id,
            task=self.task, dim=shard_dim, keys=keys_sorted,
            coeffs=coeffs, variances=variances,
            projector=dataset.projector,
            coeffs_device=coeffs_device)


def _solve_bucket_impl(solver, x, labels, offsets, weights, w0, lam):
    """Batched bucket solve, traced inside the sweep body: x (E,S,D),
    labels/offsets/weights (E,S), w0 (E,D) give ``(w, variances, converged,
    counts)``, the first three per lane, ``counts`` the bucket's device
    scalars for its ``game.re.solve`` span (lanes that weigh something, the
    sums of their iterations and evaluations, plain and weighted by each
    lane's real rows, how many converged, and the most evaluations any lane
    made)."""
    problem = solver._problem()
    objective = problem.objective

    def batch(x, labels, offsets, weights, w0, lam):
        # Pre-pad the entity batch to the Pallas kernel's block plan with
        # weight-0 lanes (zero data ⇒ gradient = L2 at w0=0 = 0: they
        # converge immediately, exactly like _put's mesh padding), so the
        # flat loop's (d, E) arrays are as wide as the kernel's operands
        # and the kernel takes its iterate as it is. run_lanes lays the
        # padded bucket entities-last, once, before its loop (the pad
        # fuses into that one copy of the design). Zero when the
        # objective's gate keeps the XLA closed form or the plan already
        # divides; under shard_map this runs per shard, so each device
        # pads and lays out its own slice.
        e_real = x.shape[0]
        pad = objective.entity_pad(x)
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
            labels = jnp.pad(labels, ((0, pad), (0, 0)))
            offsets = jnp.pad(offsets, ((0, pad), (0, 0)))
            weights = jnp.pad(weights, ((0, pad), (0, 0)))
            w0 = jnp.pad(w0, ((0, pad), (0, 0)))
        data = GLMData(design=DenseDesign(x=x), labels=labels,
                       offsets=offsets, weights=weights)
        # the one place that knows its solves are a batch: an L-BFGS
        # bucket runs one evaluation a lane a trip (optimize/lbfgs.py)
        result, passes = problem.run_lanes(data, w0, lam)
        variances = jax.vmap(
            lambda w, lane: problem.compute_variances(w, lane, lam))(
                result.w, data)
        if variances is None:
            variances = jnp.zeros((x.shape[0], 0), x.dtype)
        per_lane = (result.w, variances, result.converged,
                    result.iterations, result.evaluations)
        if pad:
            per_lane = tuple(a[:e_real] for a in per_lane)
        # one count a shard; none where the solve's loops count no passes
        passes = (jnp.zeros((0,), jnp.int32) if passes is None
                  else passes[None])
        return (*per_lane, passes)

    def counted(w_out, variances, conv, iterations, evaluations, passes):
        # a lane that weighs nothing (the mesh's pad lanes) solved nothing
        rows = jnp.sum(weights > 0, axis=1)
        real = rows > 0
        total = lambda a: jnp.sum(jnp.where(real, a, 0).astype(jnp.int32))
        # float32: rows x evaluations summed over a bucket can pass 2**31
        by_rows = lambda a: jnp.sum(rows.astype(jnp.float32) * a)
        counts = {
            "lanes": total(real), "iterations": total(iterations),
            "evaluations": total(evaluations),
            # the passes the bucket's program has to run at the least
            "max_lane_evaluations": jnp.max(evaluations),
            "converged": total(conv),
            # each lane's count weighted by its real rows: the bucket's
            # required passes and its evaluations in rows read
            "row_iterations": by_rows(iterations),
            "row_evaluations": by_rows(evaluations)}
        if passes.size:
            # the passes it did run, counted by the loop itself (under a
            # mesh the shards run side by side: the longest shard's)
            counts["passes"] = jnp.max(passes)
        return w_out, variances, conv, counts

    if solver.mesh is None:
        return counted(*batch(x, labels, offsets, weights, w0, lam))
    # Entity-parallel: each device solves its contiguous slice of lanes.
    # No collectives in the body — independence is the whole point. The
    # lane specs mention EVERY mesh axis (solver._lane_axes): with
    # check_vma off, an unmentioned axis would leave the outputs'
    # replication unverified and downstream GSPMD consumers disagree on it
    # (gather takes one replica, concatenate sums them).
    s = P(solver._lane_axes())
    # check_vma off: the body is collective-free by construction, and the
    # optimizers' constant-initialized while_loop carries would otherwise
    # trip the varying-axis check against lane-varying outputs.
    return counted(*shard_map(
        batch, mesh=solver.mesh,
        in_specs=(s, s, s, s, s, P()),
        out_specs=(s, s, s, s, s, s), check_vma=False,
    )(x, labels, offsets, weights, w0, lam))


def _sweep_fused_impl(solver, offsets_dev, lam, statics, warm_ctxs,
                      coeffs_warm, cidxs, e_reals, row_slots,
                      out_sharding=None):
    """The sweep body: one program for the sweep of the buckets it is
    given — all of a resident dataset's, one of a streaming dataset's —
    and the only code that moves the residual offsets into the buckets'
    padded slots, per bucket gathers warm starts from the previous sweep's
    coefficient table, solves and computes margins, and moves the margins
    back into a score vector (zero on a row that none of these buckets
    holds); plus the flat coefficient/variance payload for the model's D2H,
    the device coefficient mirror (passive scoring), and each bucket's
    counts for its ``game.re.solve`` span.

    Rows move both ways by ``row_slots``, each row's slot among the
    buckets' flat padded layouts end to end
    (:meth:`RandomEffectSolver._row_slots`), because on the chip a random
    access costs by the index, whatever it fetches, and a bucket's padded
    slots are five to six times its rows (PERF.md, sections 5 and 6, PR
    33). In: the ``n`` offsets are scattered to their slots, and a slot
    that holds no row stays zero; gathering the offsets over every bucket's
    padded ``(entities, rows)`` index moved the same values. Out: the
    ``n`` scores are looked up among the margins at the same slots
    (``ops/design.py::lookup``: whole 128-lane rows of its table gathered,
    the lane picked); scattering every padded slot's margin to its row
    moved the same values, after a sort of the slots. The warm start's and
    the coefficient mirror's gathers, ``entities x dim`` elements a bucket,
    go through the same look-up: from tables this small it runs at a third
    of a scalar gather's time.

    ``coeffs_warm`` is sized to the dataset's full key-table length from
    sweep 0 (zeros — every ``found`` is False), so a single compilation
    serves the cold sweep and every warm sweep.

    Statics are the fat 3-tuple per bucket — ``(x, labels, weights)`` —
    either uploaded from host fills or materialized on device from the
    compact index maps (:func:`_materialize_fat`); the sweep program is
    identical either way.
    """
    sizes = [e_real * wt_d.shape[1] for (_, _, wt_d), e_real
             in zip(statics, e_reals)]
    # a row that no bucket holds points past the last slot: dropped here,
    # and reading the zero appended below
    slots_off = jnp.zeros((sum(sizes),), jnp.float32).at[row_slots[0]].set(
        offsets_dev, mode="drop")
    margins: list[jnp.ndarray] = []
    flat_w: list[jnp.ndarray] = []
    flat_v: list[jnp.ndarray] = []
    coef_parts: list[jnp.ndarray] = []
    counts: list[dict] = []
    for (x_d, lab_d, wt_d), (pos_d, found_d), cidx, e_real, part in zip(
            statics, warm_ctxs, cidxs, e_reals,
            jnp.split(slots_off, np.cumsum(sizes)[:-1])):
        # the mesh's pad lanes (past e_real) hold no row
        boff = jnp.pad(part.reshape(e_real, -1),
                       ((0, wt_d.shape[0] - e_real), (0, 0)))
        # zero where the weight is: the margin must stay finite
        boff = boff * (wt_d > 0)
        # the join's positions lie in the table (model.py::key_join)
        w0 = jnp.where(
            found_d,
            lookup(coeffs_warm, pos_d.reshape(1, -1)).reshape(pos_d.shape),
            0.0).astype(jnp.float32)
        w_dev, variances, _conv, counts_k = _solve_bucket_jit(
            solver, x_d, lab_d, boff, wt_d, w0, lam)
        counts.append(counts_k)
        margins.append(_margins_bucket(x_d, w_dev)[:e_real].reshape(-1))
        flat_w.append(w_dev[:e_real].reshape(-1))
        flat_v.append(jnp.asarray(variances)[:e_real].reshape(-1))
        coef_parts.append(
            lookup(flat_w[-1], cidx[None, :])[0].astype(jnp.float32))
    scores = lookup(jnp.concatenate(
        margins + [jnp.zeros((1,), jnp.float32)]), row_slots)[0]
    if out_sharding is not None:
        # keep the score vector in the caller's (e.g. data-axis) layout:
        # without the constraint GSPMD replicates the looked-up vector,
        # silently un-sharding the CD score decomposition
        # (tests/test_sharded_scores.py — ROADMAP item 5 prototype)
        scores = jax.lax.with_sharding_constraint(scores, out_sharding)
    batched = jnp.concatenate(flat_w + flat_v)
    evaluations = sum(c["evaluations"] for c in counts)
    return (scores, batched, jnp.concatenate(coef_parts), tuple(counts),
            evaluations)


@jax.jit
def _margins_bucket(x, w):
    return jnp.einsum("esd,ed->es", x, w,
                      preferred_element_type=jnp.float32)


#: plain jits, only ever traced inside the sweep body, where all they do is
#: keep a ``func.call`` around the solve and the margins in the lowered
#: program. Inlined, the same operations compiled to a module whose entry and
#: loop bodies listed their instructions in another order (CHANGES.md, PR 30),
#: and a PR that may not move the cells' program cannot take that: they go
#: with the first PR that may.
_solve_bucket_jit = jax.jit(_solve_bucket_impl, static_argnames=("solver",))

#: the sweep's one profiled executable (``fn="game.re.sweep_fused"``: the
#: per-coordinate compile counter the flat-recompile contract watches),
#: module-level so the per-signature compiled cache is shared by every
#: solver instance of a process — RandomEffectSolver is a frozen
#: value-equal dataclass, so the ``solver`` static keys by configuration
_sweep_fused_jit = profiling.profile_jit(
    _sweep_fused_impl, "game.re.sweep_fused",
    static_argnames=("solver", "e_reals", "out_sharding"))


@partial(jax.jit, static_argnames=("S", "identity_cols"))
def _materialize_fat(shard_x, labels_g, weights_g, perm_d, counts_d, fi_d,
                     *, S: int, identity_cols: bool = False):
    """One device-side program turning compact index maps into the fat
    bucket tensors ``(x, labels, weights)`` — the exact 3-tuple the
    host-fill path uploads, built from the shared dense
    shard image instead of shipped over the wire. Runs once per bucket per
    dataset (the caller caches the result). The (E, S) sample index is
    itself derived on device from the padding-free ``perm``/``counts``
    upload (active rows are front-packed per entity — bucket_pack.cc).
    ``identity_cols`` marks a bucket whose local feature map is exactly
    ``arange(shard_dim)`` for every entity (the common small-dim case:
    every feature observed) — the (E, S, D) element gather then collapses
    to a plain ROW gather, which the TPU executes several times faster."""
    starts = jnp.cumsum(counts_d) - counts_d  # (E,) exclusive prefix
    slot = jnp.arange(S, dtype=jnp.int32)
    valid = slot[None, :] < counts_d[:, None]
    if perm_d.shape[0]:
        pos = starts[:, None] + slot[None, :]
        idx_d = jnp.where(valid, jnp.take(perm_d, pos, mode="clip"), -1)
    else:  # bucket of only zero-row (padding) entities
        idx_d = jnp.full(valid.shape, -1, jnp.int32)
    clip = jnp.maximum(idx_d, 0)
    rmask = idx_d >= 0
    if identity_cols:
        x = shard_x[clip] * rmask[:, :, None]
    else:
        fclip = jnp.maximum(fi_d, 0)
        cmask = fi_d >= 0
        x = (shard_x[clip[:, :, None], fclip[:, None, :]]
             * rmask[:, :, None] * cmask[:, None, :])
    labels = labels_g[clip] * rmask
    weights = weights_g[clip] * rmask
    return x, labels, weights


def _shard_dim(dataset: RandomEffectDataset) -> int:
    top = 0
    for b in dataset.buckets:
        if b.feature_index.size:
            top = max(top, int(b.feature_index.max()) + 1)
    return top
