"""Multi-host (multi-slice) support: global meshes and host-local data feed.

The reference scales across racks with Spark's driver/executor tree
(``RDD.treeAggregate`` over netty RPC — SURVEY.md §5.8). The TPU-native
equivalent is multi-controller JAX: every host runs THIS same program,
``jax.distributed.initialize`` forms the job, and one global
:class:`jax.sharding.Mesh` spans all slices — collectives ride ICI within a
slice and DCN between slices. No framework code changes between 1 host and
N: the mesh axes are the same, the ``shard_map`` bodies are the same.

Mesh layout rule (the scaling-book recipe): put the axis with the
highest-volume collectives (``data`` — one psum of grad-sized arrays per
optimizer iteration) INNERMOST so it maps to ICI; put low-volume axes
(``entity`` — zero collectives; only host-side gather at sweep end) across
DCN. :func:`make_multihost_mesh` orders axes accordingly.

Data feed: each host reads its own Avro shard (the reference's executor-local
HDFS reads) and contributes host-local blocks;
:func:`global_glm_data_from_local` assembles the global sharded
:class:`GLMData` with ``jax.make_array_from_process_local_data``.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.ops.design import ChunkedSparseDesign, DenseDesign
from photon_ml_tpu.ops.objective import GLMData
from photon_ml_tpu.parallel.distributed import (
    ShardBudget,
    shard_budget,
    shard_glm_data,
)
from photon_ml_tpu.parallel.mesh import DATA_AXIS, ENTITY_AXIS


_initialized = False


def _enable_cpu_collectives() -> None:
    """Multi-process jobs on the CPU backend (loopback test fleets, the
    supervised 2-process chaos cells) need a cross-process collectives
    implementation — the bare CPU client refuses multiprocess computations
    outright ("Multiprocess computations aren't implemented"). jaxlib
    ships gloo in the wheel but leaves it off by default, and the config
    flag only takes effect BEFORE backend/client creation — which is why
    this lives in :func:`initialize` (documented to run before any
    backend-touching call) rather than at first collective. TPU/GPU
    platforms keep their native ICI/NCCL paths untouched."""
    import os

    platforms = str(jax.config.jax_platforms
                    or os.environ.get("JAX_PLATFORMS", ""))
    if "cpu" not in platforms.split(","):
        return
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               *, auto: bool = False, retry_policy=None) -> None:
    """Form the multi-controller job (idempotent). On single-host runs this
    is a no-op; on TPU pods the args come from the environment.

    Resolution order: explicit args → ``PHOTON_COORDINATOR_ADDRESS`` /
    ``PHOTON_NUM_PROCESSES`` / ``PHOTON_PROCESS_ID`` env vars (how the
    drivers' ``--multihost`` flag is fed on CPU/GPU clusters) → with
    ``auto=True``, bare ``jax.distributed.initialize()`` (JAX's own cluster
    auto-detection: TPU pod metadata, Slurm, etc.).

    Connection attempts run under ``retry_policy`` (default: the
    process-wide resilience policy — the drivers' ``--max-retries`` /
    ``--retry-deadline-s`` flags), and a coordinator that stays
    unreachable raises a :class:`RuntimeError` naming the address, this
    process's index, and the attempt budget — not a raw backend hang or
    traceback.

    Must run before ANY backend-touching JAX call — even
    ``jax.process_count()`` initializes the XLA backend, after which
    ``jax.distributed.initialize`` refuses to run; hence the module-level
    flag rather than querying JAX state.
    """
    global _initialized
    if _initialized:
        return
    if coordinator_address is None and num_processes is None:
        import os

        coordinator_address = os.environ.get("PHOTON_COORDINATOR_ADDRESS")
        n = os.environ.get("PHOTON_NUM_PROCESSES")
        if bool(coordinator_address) != bool(n):
            # one without the other would fall through to
            # jax.distributed.initialize with a None field and die with an
            # obscure backend error; name the missing variable instead.
            # (PHOTON_PROCESS_ID stays optional: it defaults to the
            # process_id argument, and a leftover value on a single-host
            # run is harmless.)
            missing = ("PHOTON_NUM_PROCESSES" if coordinator_address
                       else "PHOTON_COORDINATOR_ADDRESS")
            raise ValueError(
                f"multi-host environment is partially set: {missing} is "
                "missing — set both PHOTON_COORDINATOR_ADDRESS and "
                "PHOTON_NUM_PROCESSES (or neither, for single-host)")
        num_processes = int(n) if n else None
        pid = os.environ.get("PHOTON_PROCESS_ID")
        process_id = int(pid) if pid else process_id
        if coordinator_address is None and num_processes is None:
            if auto:
                jax.distributed.initialize()
                _initialized = True
            return  # single-host
    from photon_ml_tpu.resilience import fault_point, get_default_policy, \
        retry

    _enable_cpu_collectives()
    policy = retry_policy if retry_policy is not None \
        else get_default_policy()
    # the deadline must be HARD: jax.distributed.initialize BLOCKS
    # internally (~300s default) waiting for the coordinator, so without
    # capping its own timeout the retry deadline would never get a chance
    # to fire. Budget each attempt an equal share of the deadline.
    init_kwargs = {}
    if policy.deadline_s is not None:
        init_kwargs["initialization_timeout"] = max(
            1, int(np.ceil(policy.deadline_s / policy.max_attempts)))
    attempts = [0]

    def attempt() -> None:
        attempts[0] += 1
        from photon_ml_tpu.resilience import heartbeat

        heartbeat("initialize")
        fault_point("collective", op="initialize",
                    coordinator=coordinator_address)
        if (process_id not in (None, 0) and coordinator_address
                and ":" in coordinator_address):
            # reachability preflight (non-chief only — process 0 hosts the
            # coordinator itself): some jax versions answer an unreachable
            # coordinator with a C++ LOG(FATAL) process abort, which no
            # Python handler can turn into the actionable error below;
            # probing the socket first keeps the failure catchable. A
            # worker legitimately starting BEFORE the coordinator must
            # wait, not die — poll within this attempt's budget (jax's own
            # default wait is 300s), through the retry module's sanctioned
            # sleep so the wait is visible to the hygiene accounting.
            import socket

            from photon_ml_tpu.resilience.retry import _sleep

            host, port = coordinator_address.rsplit(":", 1)
            budget = init_kwargs.get("initialization_timeout", 300)
            t_start = _time.monotonic()
            while True:
                try:
                    socket.create_connection((host, int(port)),
                                             timeout=min(budget, 10)).close()
                    break
                except OSError:
                    if _time.monotonic() - t_start >= budget:
                        raise
                    _sleep(0.2)
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id, **init_kwargs)

    import time as _time

    t0 = _time.monotonic()
    try:
        retry(attempt, policy, name="multihost.initialize")
    except Exception as e:
        raise RuntimeError(
            f"could not join the multi-controller job: coordinator "
            f"{coordinator_address!r} unreachable from process "
            f"{process_id if process_id is not None else '?'} of "
            f"{num_processes} after {attempts[0]} attempt(s) over "
            f"{_time.monotonic() - t0:.1f}s "
            f"(deadline {policy.deadline_s}s, max attempts "
            f"{policy.max_attempts}). Check that the coordinator process "
            f"is up, PHOTON_COORDINATOR_ADDRESS is its reachable "
            f"host:port, and every process agrees on "
            f"PHOTON_NUM_PROCESSES; last error: {e!r}") from e
    _initialized = True


def is_chief() -> bool:
    """True on the process that should write outputs (the reference's
    driver/executor asymmetry collapses to "process 0 writes, everyone
    computes" — collectives keep all processes in lockstep either way)."""
    return jax.process_index() == 0


def make_multihost_mesh(data_per_slice: Optional[int] = None,
                        entity_over_slices: bool = False) -> Mesh:
    """Global mesh over all processes' devices.

    Default: one ``data`` axis over every chip (psum tree spans DCN exactly
    once at the top, like treeAggregate's depth-2 tree). With
    ``entity_over_slices``, a 2D ``(entity, data)`` grid: the ``entity``
    axis runs across slices (DCN) and ``data`` stays within a slice (ICI) —
    the right layout when random-effect solves dominate, because they need
    no collectives at all. ``data_per_slice`` overrides the data-axis width
    (default: one process's device count).
    """
    devices = np.array(jax.devices())
    n = len(devices)
    if not entity_over_slices and data_per_slice is None:
        return jax.make_mesh((n,), (DATA_AXIS,))
    per = (data_per_slice if data_per_slice is not None
           else n // max(jax.process_count(), 1))
    if per <= 0 or n % per:
        raise ValueError(
            f"data axis width {per} must divide device count {n}")
    dev_grid = devices.reshape(n // per, per)
    return Mesh(dev_grid, (ENTITY_AXIS, DATA_AXIS))


def allreduce_shard_budget(local: ShardBudget) -> ShardBudget:
    """Max-reduce a :class:`ShardBudget` across all processes so every host
    builds identically-shaped shard stacks (identity on single-process
    runs). The max is correct field-wise: a larger rows-per-shard or chunk
    count only adds inert zero-padding on the smaller hosts."""
    if jax.process_count() == 1:
        return local
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(local.to_array())
    return ShardBudget.from_array(np.max(np.asarray(gathered), axis=0))


def _gather_stack(x: np.ndarray) -> np.ndarray:
    """``process_allgather`` with a stacked leading process axis, safe for
    any 64-bit payload even when ``jax_enable_x64`` is off (jax would
    silently downcast; entity keys ``entity*dim + feature`` overflow int32,
    and float64 would lose precision only on P>1 runs — the worst kind of
    divergence). 8-byte dtypes ride through as uint32 word pairs."""
    from jax.experimental import multihost_utils

    from photon_ml_tpu.resilience import fault_point, heartbeat

    # injection-only, never retried: a unilateral second attempt at a
    # collective would desync every other process — fault recovery for
    # collectives is the caller's (symmetric) job. The heartbeat marks
    # the collective BOUNDARY: a process whose peer died blocks inside
    # the gather below with this beat as its last sign of life, which is
    # exactly the staleness the fleet supervisor's stall detection reads.
    heartbeat("collective")
    fault_point("collective", op="allgather", shape=tuple(x.shape))
    x = np.ascontiguousarray(x)
    if x.dtype.itemsize == 8 and not jax.config.jax_enable_x64:
        dtype = x.dtype
        words = x.view(np.uint32).reshape(x.shape + (2,))
        gathered = np.asarray(multihost_utils.process_allgather(words))
        assert gathered.dtype == np.uint32, gathered.dtype
        return np.ascontiguousarray(gathered).view(dtype).reshape(
            gathered.shape[:-1])
    return np.asarray(multihost_utils.process_allgather(x))


def allgather_concat(x: np.ndarray) -> np.ndarray:
    """Concatenate each process's (variable-length, axis-0) array in process
    order — the host-side collective behind multi-process model assembly and
    the entity-shuffle (reference: Spark's shuffle/collect). Identity on
    single-process runs. Shapes beyond axis 0 must agree; axis-0 lengths are
    equalized by zero-padding to the max before the gather (collectives need
    equal shapes), then the padding is dropped per-process."""
    x = np.asarray(x)
    if jax.process_count() == 1:
        return x
    lens = _gather_stack(np.array([x.shape[0]], np.int64)).reshape(-1)
    m = int(lens.max())
    if m == 0:
        return x
    pad = [(0, m - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    gathered = _gather_stack(np.pad(x, pad))
    return np.concatenate(
        [gathered[p, :int(lens[p])] for p in range(len(lens))], axis=0)


def allreduce_sum(x: np.ndarray) -> np.ndarray:
    """Element-wise sum across processes (identity single-process) — e.g.
    global entity row counts from per-process bincounts."""
    x = np.asarray(x)
    if jax.process_count() == 1:
        return x
    return _gather_stack(x).sum(axis=0).astype(x.dtype)


def allgather_concat_strings(strings) -> list[str]:
    """Concatenate every process's list of strings in process order
    (identity single-process) — the collective behind global feature-index
    and entity-vocabulary agreement. Strings ride as a lengths gather plus
    one flat utf-8 byte gather (jax collectives carry no string dtype)."""
    strings = list(strings)
    if jax.process_count() == 1:
        return strings
    data = [s.encode("utf-8") for s in strings]
    lens = allgather_concat(np.array([len(b) for b in data], np.int64))
    buf = allgather_concat(
        np.frombuffer(b"".join(data), np.uint8).copy()
        if data else np.zeros(0, np.uint8))
    out, off = [], 0
    for ln in lens:
        ln = int(ln)
        out.append(bytes(buf[off:off + ln]).decode("utf-8"))
        off += ln
    return out


def allgather_text(text: str) -> list[str]:
    """Every process's ``text`` in process order (identity single-process)
    — the transport behind the fleet metrics fold
    (:mod:`photon_ml_tpu.telemetry.aggregate`): each process contributes
    one rendered registry snapshot per sweep boundary and process 0 merges
    the gathered list. One string per process keeps the collective at a
    single lengths-gather plus one flat byte gather."""
    return allgather_concat_strings([text])


def allreduce_max(x: np.ndarray) -> np.ndarray:
    """Element-wise max across processes (identity single-process)."""
    x = np.asarray(x)
    if jax.process_count() == 1:
        return x
    return _gather_stack(x).max(axis=0).astype(x.dtype)


def local_axis_blocks(mesh: Mesh, axis: str = DATA_AXIS) -> int:
    """How many distinct ``axis`` coordinates this process's devices cover —
    the number of data blocks this process must feed. NOT simply
    ``local_device_count``: on a 2D ``(entity, data)`` mesh each data block
    is replicated across the entity lanes, so feeding one block per local
    device would over-split the data (and the per-device leading dim would
    silently drop rows in the shard_map body's ``[0]`` unstack)."""
    names = list(mesh.axis_names)
    axis_pos = names.index(axis)
    devs = np.asarray(mesh.devices)
    me = jax.process_index()
    coords = {idx[axis_pos] for idx in np.ndindex(devs.shape)
              if devs[idx].process_index == me}
    if not coords:
        raise ValueError(f"process {me} owns no devices in mesh {mesh}")
    return len(coords)


def global_glm_data_multihost(host_data: GLMData, mesh: Mesh,
                              axis: str = DATA_AXIS) -> GLMData:
    """One-call multi-host feed: shard this process's host-resident data
    into its share of the mesh's ``axis`` blocks, reconcile the layout
    budget across processes, and assemble the globally-sharded
    :class:`GLMData`.

    The two-pass build (local layout → budget allreduce → rebuild only when
    another host needs bigger blocks) is the TPU-native analog of the
    reference letting Spark pick partition sizes per executor: here shapes
    must agree globally, so hosts agree on the max and pad with weight-0
    rows / zero-value chunks, which contribute exactly nothing.
    """
    n_local = local_axis_blocks(mesh, axis)
    # host_stage: the stack stays in numpy — make_array_from_process_local_data
    # below is the one host→device transfer (a jnp stack would detour the
    # whole local dataset through the default device's HBM).
    #
    # Two agreement rounds, both unconditional (allgather is a collective —
    # every process must call it the same number of times):
    # 1. agree on the bucket GEOMETRY (rows-per-shard, chunk widths) — a
    #    host given a larger ``per`` re-buckets rows into fewer, denser
    #    blocks, so chunk COUNTS measured at the old geometry are invalid;
    # 2. re-measure chunk counts at the agreed geometry, then agree on
    #    their max. Padding to a larger count is always legal, so round 2
    #    is a fixed point — no host can need a third round.
    local = shard_glm_data(host_data, n_local, host_stage=True)
    b0 = shard_budget(local)
    geo = allreduce_shard_budget(b0)
    if (geo.rows_per_shard, geo.row_chunk, geo.col_chunk) != (
            b0.rows_per_shard, b0.row_chunk, b0.col_chunk):
        local = shard_glm_data(
            host_data, n_local, host_stage=True,
            budget=ShardBudget(rows_per_shard=geo.rows_per_shard,
                               row_chunk=geo.row_chunk,
                               col_chunk=geo.col_chunk))
    b1 = shard_budget(local)
    final = allreduce_shard_budget(b1)
    if final != b1:
        local = shard_glm_data(host_data, n_local, budget=final,
                               host_stage=True)
    return global_glm_data_from_local(local, mesh, axis)


def global_glm_data_from_local(local: GLMData, mesh: Mesh,
                               axis: str = DATA_AXIS) -> GLMData:
    """Assemble a globally-sharded :class:`GLMData` from each process's
    host-local block (stacked per-block layout, as produced by
    ``shard_glm_data(local, local_axis_blocks(mesh))``).

    Every process contributes its own rows; the result's leading dim is the
    global device count, laid out for the ``data``-axis ``shard_map``
    objective. Labels/offsets/weights and the design — dense, or the
    chunked sparse layout (each of whose six leaves stacks the same way) —
    all feed through ``jax.make_array_from_process_local_data`` (the
    host→device bridge the reference gets from Spark partition locality;
    ``function/glm/DistributedGLMLossFunction.scala`` reads its partitions
    off executor-local HDFS the same one-host-one-block way).

    Cross-host contract (unverifiable locally, like any SPMD invariant):
    every process must present identical leaf shapes — same rows-per-device
    ``per``, and for sparse designs the same chunk widths and padded chunk
    counts. :func:`allreduce_shard_budget` reconciles per-host budgets;
    :func:`global_glm_data_multihost` does the whole dance in one call.
    """
    sharding = NamedSharding(mesh, P(axis))
    n_local = local_axis_blocks(mesh, axis)
    n_axis = mesh.shape[axis]
    if n_axis % n_local:
        raise ValueError(
            f"this process covers {n_local} of the {n_axis} {axis!r}-axis "
            f"blocks — non-uniform process layouts are not supported")
    scale = n_axis // n_local
    if jax.process_count() > 1:
        # Each data-axis block must be OWNED by exactly one process: if a
        # block's replicas span processes (e.g. the entity axis crosses
        # hosts), every owner would feed its own different rows into what
        # the sharding declares to be one replicated block — silently
        # dropping every non-zeroth host's data from psums. Partition the
        # data axis across processes (make_multihost_mesh() default) and
        # put cross-host axes on entity only when data is within-host.
        names = list(mesh.axis_names)
        axis_pos = names.index(axis)
        devs = np.asarray(mesh.devices)
        owners: dict[int, set[int]] = {}
        for idx in np.ndindex(devs.shape):
            owners.setdefault(idx[axis_pos], set()).add(
                devs[idx].process_index)
        shared = [c for c, procs in owners.items() if len(procs) > 1]
        if shared:
            raise ValueError(
                f"{axis!r}-axis blocks {shared[:4]} are replicated across "
                f"processes in this mesh; the per-process feed cannot "
                f"guarantee replicas agree — use a mesh whose {axis!r} "
                f"axis partitions processes")

    def feed(x) -> jax.Array:
        x = np.asarray(x)
        if x.shape[0] != n_local:
            raise ValueError(
                f"local stack has {x.shape[0]} blocks; this process's "
                f"devices cover {n_local} {axis!r}-axis blocks — build with "
                f"shard_glm_data(data, local_axis_blocks(mesh))")
        global_shape = (x.shape[0] * scale,) + x.shape[1:]
        return jax.make_array_from_process_local_data(sharding, x, global_shape)

    design = local.design
    from photon_ml_tpu.game.factored import FactoredDesign

    if isinstance(design, DenseDesign):
        fed = DenseDesign(x=feed(design.x))
    elif isinstance(design, FactoredDesign):
        fed = FactoredDesign(x=feed(design.x), v=feed(design.v),
                             latent_dim=design.latent_dim)
    elif isinstance(design, ChunkedSparseDesign):
        fed = ChunkedSparseDesign(
            rvals=feed(design.rvals), rcols=feed(design.rcols),
            rrow=feed(design.rrow), cvals=feed(design.cvals),
            crows=feed(design.crows), ccol=feed(design.ccol),
            n_rows=design.n_rows, n_cols=design.n_cols)
    else:
        raise TypeError(
            f"multi-host feed takes the stacked per-block layout from "
            f"shard_glm_data (DenseDesign, FactoredDesign, or "
            f"ChunkedSparseDesign); got "
            f"{type(design).__name__} — run shard_glm_data("
            f"local, local_axis_blocks(mesh)) first, or use "
            f"global_glm_data_multihost for the whole dance")
    return GLMData(
        design=fed,
        labels=feed(local.labels),
        offsets=feed(local.offsets),
        weights=feed(local.weights),
    )
