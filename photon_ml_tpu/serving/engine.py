"""Jitted online scoring engine: shape-bucketed, zero steady-state recompiles.

Requests arrive at arbitrary batch sizes; XLA compiles one executable per
input SHAPE. Left alone, that means a recompile (seconds) the first time
any new size shows up — a latency cliff in the middle of serving traffic. The engine therefore pads every batch up to
a power-of-two bucket (1, 2, 4, … ``max_batch``): the executable set is
fixed and small (log₂ max_batch + 1 shapes), :meth:`ScoringEngine.warmup`
pre-traces all of them, and steady-state serving performs **zero**
recompiles no matter how request sizes vary. ``compile_count`` exposes the
trace counter the serving bench asserts on.

Numeric contract: per-coordinate margins are accumulated in float64 (when
``jax_enable_x64`` is on — the serve CLI enables it on CPU backends) and the
total runs :func:`photon_ml_tpu.game.model.sum_coordinate_margins` — the
same reduction, same coordinate order, as the batch scorer. Online scores
are bit-identical to ``score_game`` output (tests/test_serving.py locks
this). Without x64 (TPU serving) accumulation degrades to f32 and parity is
approximate. Quantized coefficient tables (``--table-dtype bfloat16/int8``)
trade that exactness for footprint: rows dequantize in-trace
(:func:`photon_ml_tpu.serving.store.gather_rows`) and scores hold the
documented relative tolerances instead (bf16 ≤ 1e-2, int8 ≤ 5e-2 — the
score-parity gates in tests/test_serving.py).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Mapping, Optional, Sequence

import numpy as np

from photon_ml_tpu.game.model import (
    FixedEffectModel,
    GameModel,
    sum_coordinate_margins,
)
from photon_ml_tpu.io.data_reader import FeatureShardConfig, _record_features
from photon_ml_tpu.io.index import IndexMap
from photon_ml_tpu.resilience.faults import fault_point
from photon_ml_tpu.types import INTERCEPT_KEY
from photon_ml_tpu.serving import overload as _overload
from photon_ml_tpu.serving import stages as _stages
from photon_ml_tpu.serving import store as _store
from photon_ml_tpu.serving.store import EntityCoefficientStore
from photon_ml_tpu.telemetry import metrics as _metrics
from photon_ml_tpu.telemetry import profiling as _profiling

#: engine-side scoring latency per padded bucket shape (dispatch + D2H)
_SCORE_LATENCY = _metrics.histogram(
    "photon_serving_score_latency_seconds",
    "Engine scoring time per padded batch bucket", labels=("bucket",))

#: per-stage request-path critical path (same family the HTTP front end
#: and the microbatcher feed) — the engine owns the batch_assemble stage
#: (record → host arrays packing) and the execute stage (pad + jit
#: dispatch + D2H across every chunk of a batch)
_STAGE_SECONDS = _metrics.histogram(
    "photon_serving_stage_seconds",
    "Serving request time per request-path stage "
    "(parse | queue_wait | batch_assemble | execute | respond)",
    labels=("stage",))

#: the fn label serving's traces count under — the SAME
#: ``photon_compiles_total{fn}`` family the training paths use
#: (telemetry/profiling.py), so one scrape expression covers every
#: recompile contract in the system. The engine keeps its own jit (the
#: power-of-two bucket machinery IS the zero-recompile design) and counts
#: traces from inside the traced body via ``profiling.record_compile``.
SCORING_FN_LABEL = "serving.score"


def next_bucket(n: int) -> int:
    """Smallest power of two ≥ max(n, 1)."""
    return 1 << max(int(n) - 1, 0).bit_length()


class _ScoreProgram:
    """One jitted scoring program + its trace counter, shareable across
    engine instances. A patch-derived model has the same coordinate
    structure as its parent — only the table CONTENTS differ, and those
    ride as jit arguments — so the derived engine reuses the parent's
    executables outright (``ScoringEngine(share_from=parent)``): a patch
    activation that appends no new table rows compiles NOTHING, on any
    host. The counter lives here (not on the engine) so ``compile_count``
    tells the truth for shared programs too."""

    __slots__ = ("jit", "compiles")

    def __init__(self):
        self.jit = None
        #: bumped from inside the traced body (trace time only — jit
        #: serializes traces), deliberately not lock-annotated
        self.compiles = 0


@dataclasses.dataclass(frozen=True)
class RequestBatch:
    """Host arrays for one batch of scoring requests: per-shard dense
    designs, per-random-effect-coordinate store rows, offsets."""

    n: int
    offsets: np.ndarray  # (n,) float32
    xs: tuple  # per shard config: (n, dim) float32
    rows: tuple  # per RE coordinate: (n,) int32 store rows


class ScoringEngine:
    """Scores request records against one loaded GAME model version.

    One engine per :class:`~photon_ml_tpu.serving.registry.ServingModel`
    version — hot-swapping installs a fresh engine, so an engine's jit
    cache always matches its coefficients. Thread-safe: concurrent
    :meth:`score` calls share the compiled executables.
    """

    def __init__(self, model: GameModel,
                 shard_configs: Sequence[FeatureShardConfig],
                 index_maps: Mapping[str, IndexMap],
                 stores: Mapping[str, EntityCoefficientStore],
                 *, max_batch: int = 1024,
                 share_from: "Optional[ScoringEngine]" = None):
        import jax
        import jax.numpy as jnp

        self.model = model
        self.shard_configs = tuple(shard_configs)
        self.index_maps = dict(index_maps)
        self.stores = dict(stores)
        self.max_batch = next_bucket(max_batch)
        self._shard_order = [c.shard_id for c in self.shard_configs]
        # coordinate walk order is the model's — the summation contract is
        # order-sensitive and the batch path iterates the same dict
        self._coords = list(model.coordinates.items())
        self._re_order = [cid for cid, cm in self._coords
                          if not isinstance(cm, FixedEffectModel)]
        for cid in self._re_order:
            if cid not in self.stores:
                raise ValueError(f"no EntityCoefficientStore for "
                                 f"random-effect coordinate {cid!r}")
        # model parameters ride as jit ARGUMENTS, not closure constants:
        # constants get baked into every bucket's executable (compile-time
        # and image bloat proportional to table size × bucket count).
        # Random-effect tables ride as (table, scales) pairs — possibly
        # quantized storage, dequantized in-trace by store.gather_rows
        self._params = {
            "fe": {cid: jnp.asarray(
                np.asarray(cm.model.coefficients.means, np.float32))
                for cid, cm in self._coords
                if isinstance(cm, FixedEffectModel)},
            "re": {cid: self.stores[cid].device_params
                   for cid in self._re_order},
        }
        self._lock = threading.Lock()
        self._n_calls = 0  # guarded-by: _lock
        self._n_scored = 0  # guarded-by: _lock
        #: optional photon_ml_tpu.quality.QualityMonitor, attached by the
        #: registry at load time. Accumulation is host-side numpy over
        #: arrays score_batch already holds — the jitted program, the f32
        #: bit-parity and the zero-recompile contract are untouched.
        self.monitor = None
        self._accum = jnp.float64 if jax.config.jax_enable_x64 \
            else jnp.float32
        #: the structural signature executable sharing keys on: same
        #: shard order, same coordinate walk (id, kind, feature shard),
        #: same accumulation dtype ⇒ byte-identical traced program
        self._signature = (
            tuple(self._shard_order),
            tuple((cid, isinstance(cm, FixedEffectModel),
                   cm.feature_shard_id) for cid, cm in self._coords),
            str(self._accum.__name__),
        )
        if share_from is not None \
                and share_from._signature == self._signature:
            self._program = share_from._program
        else:
            self._program = self._build_program()

    def _build_program(self) -> _ScoreProgram:
        """Build this engine's jitted program. The closure captures ONLY
        structural constants (coordinate walk, shard order) and the
        program's own trace counter — never a specific version's tables —
        so patch-derived engines can share it verbatim."""
        import jax
        import jax.numpy as jnp

        program = _ScoreProgram()
        accum = self._accum
        shard_order = tuple(self._shard_order)
        re_order = tuple(self._re_order)
        coords = tuple((cid, isinstance(cm, FixedEffectModel),
                        cm.feature_shard_id) for cid, cm in self._coords)

        def _score_padded(params, offsets, xs, rows):
            # body runs at TRACE time only — one increment per compiled
            # bucket shape, the recompile counter the serving bench asserts
            program.compiles += 1
            _profiling.record_compile(SCORING_FN_LABEL)
            margins = []
            i_x = {sid: i for i, sid in enumerate(shard_order)}
            i_r = {cid: i for i, cid in enumerate(re_order)}
            for cid, is_fixed, feature_shard_id in coords:
                x = xs[i_x[feature_shard_id]].astype(accum)
                if is_fixed:
                    m = x @ params["fe"][cid].astype(accum)
                else:
                    # quantized tables dequantize HERE, fused into the
                    # scoring trace (store.gather_rows is the sanctioned
                    # home of the table numeric format — hygiene rule 5)
                    tab = _store.gather_rows(params["re"][cid],
                                             rows[i_r[cid]], accum)
                    m = jnp.sum(x * tab, axis=1)
                margins.append(m.astype(jnp.float32))
            # the per-coordinate f32 margins are program outputs too: the
            # fleet router merges THESE (fleet/router.py) through the same
            # sum_coordinate_margins reduction — the single-host path
            # simply never fetches them (async dispatch, total-only D2H)
            total = sum_coordinate_margins(offsets, margins, xp=jnp)
            return total, tuple(margins)

        program.jit = jax.jit(_score_padded)
        return program

    # --- stats ------------------------------------------------------------
    @property
    def compile_count(self) -> int:
        """Distinct jitted traces of this engine's PROGRAM so far (== XLA
        compiles). Constant after :meth:`warmup` — the zero-recompile
        contract. A patch-derived engine shares its parent's program, so
        the count carries across activation: a delta of 0 over a swap IS
        the zero-recompile-activation proof. The process-wide scrape
        equivalent is ``photon_compiles_total{fn="serving.score"}``."""
        return self._program.compiles

    @property
    def n_scored(self) -> int:
        return self._n_scored

    # --- request packing --------------------------------------------------
    def pack(self, records: Sequence[dict]) -> RequestBatch:
        """Records (TrainingExampleAvro-shaped dicts: ``features`` list,
        ``metadataMap``, optional ``offset``) → host arrays.

        Feature handling mirrors the batch reader exactly — bag filtering,
        index-map lookup (unknown keys dropped), intercept column, duplicate
        (row, col) entries accumulating in f32 — so packing introduces no
        online/batch skew.
        """
        n = len(records)
        offsets = np.zeros(n, np.float32)
        for i, rec in enumerate(records):
            off = rec.get("offset")
            if off is not None:
                offsets[i] = off
        xs = []
        for cfg in self.shard_configs:
            imap = self.index_maps[cfg.shard_id]
            x = np.zeros((n, len(imap)), np.float32)
            get = imap.key_to_index.get
            for i, rec in enumerate(records):
                for key, value in _record_features(rec, cfg.feature_bags):
                    j = get(key)
                    if j is not None:
                        x[i, j] += np.float32(value)
                if cfg.has_intercept:
                    x[i, imap.key_to_index[INTERCEPT_KEY]] += np.float32(1.0)
            xs.append(x)
        rows = []
        for cid in self._re_order:
            store = self.stores[cid]
            raw = [
                (rec.get("metadataMap") or {}).get(store.random_effect_type)
                for rec in records]
            rows.append(store.rows_for(raw))
        return RequestBatch(n=n, offsets=offsets, xs=tuple(xs),
                            rows=tuple(rows))

    # --- scoring ----------------------------------------------------------
    def score(self, records: Sequence[dict]) -> np.ndarray:
        """Total GAME score per record (float32, batch-path parity)."""
        # the serving-side chaos site: one visit per scoring call, BEFORE
        # any stage work — an injected fault fails this batch (its Futures
        # get the error, the batcher worker survives) and a request shed by
        # admission control never even reaches this point
        fault_point("serving.execute", n=len(records))
        with _STAGE_SECONDS.labels(stage="batch_assemble").time() as t:
            batch = self.pack(records)
        _stages.record("batch_assemble", t.seconds)
        return self.score_batch(batch)

    def score_margins(self, records: Sequence[dict]):
        """Scores PLUS the per-coordinate f32 margins and offsets — the
        fleet router's merge inputs (f32 values widened to double in JSON
        are exact, so the router re-running ``sum_coordinate_margins``
        over them reproduces this host's totals bit-for-bit). Returns
        ``(scores (n,) f32, offsets (n,) f32, [(cid, (n,) f32), ...])``
        in the model's coordinate order."""
        fault_point("serving.execute", n=len(records))
        with _STAGE_SECONDS.labels(stage="batch_assemble").time() as t:
            batch = self.pack(records)
        _stages.record("batch_assemble", t.seconds)
        scores, margins = self.score_batch(batch, with_margins=True)
        return scores, batch.offsets, \
            [(cid, m) for (cid, _cm), m in zip(self._coords, margins)]

    def score_batch(self, batch: RequestBatch, with_margins: bool = False):
        out = np.empty(batch.n, np.float32)
        margins = [np.empty(batch.n, np.float32)
                   for _ in self._coords] if with_margins else None
        # batches past the largest bucket chunk — per-sample independence
        # makes the split score-invariant
        with _STAGE_SECONDS.labels(stage="execute").time() as exec_t:
            for lo in range(0, batch.n, self.max_batch):
                hi = min(lo + self.max_batch, batch.n)
                chunk, chunk_margins = self._score_chunk(
                    batch, lo, hi, with_margins=with_margins)
                out[lo:hi] = chunk
                if with_margins:
                    for j, m in enumerate(chunk_margins):
                        margins[j][lo:hi] = m
        _stages.record("execute", exec_t.seconds)
        with self._lock:
            self._n_calls += 1
            self._n_scored += batch.n
        monitor = self.monitor
        if monitor is not None and _overload.is_shed("quality"):
            # brownout level 2+: quality accumulation is optional work —
            # shed it before shedding traffic (SERVING.md overload ladder)
            monitor = None
        if monitor is not None:
            # live quality accumulation (quality/monitor.py): fallback-row
            # hits per coordinate + nonzero design cells per shard are
            # host facts this batch already materialized; the score
            # binning itself happens inside the monitor (hygiene rule 6)
            cold = {
                cid: int(np.count_nonzero(
                    np.asarray(r) == self.stores[cid].fallback_row))
                for cid, r in zip(self._re_order, batch.rows)}
            coverage = {
                cfg.shard_id: (int(np.count_nonzero(x)), int(x.size))
                for cfg, x in zip(self.shard_configs, batch.xs)}
            monitor.observe(out, cold=cold, coverage=coverage)
        return (out, margins) if with_margins else out

    def _score_chunk(self, batch: RequestBatch, lo: int, hi: int,
                     with_margins: bool = False):
        n = hi - lo
        b = next_bucket(n)
        offsets = np.zeros(b, np.float32)
        offsets[:n] = batch.offsets[lo:hi]
        xs = []
        for x in batch.xs:
            xp = np.zeros((b, x.shape[1]), np.float32)
            xp[:n] = x[lo:hi]
            xs.append(xp)
        rows = []
        for cid, r in zip(self._re_order, batch.rows):
            rp = np.full(b, self.stores[cid].fallback_row, np.int32)
            rp[:n] = r[lo:hi]
            rows.append(rp)
        # the np.asarray D2H pull belongs inside the timed region: jax
        # dispatch is async, so the jit call alone returns before the
        # device finishes. Margins are fetched only when asked (the fleet
        # margin-merge path); the single-host path pulls the total alone.
        with _SCORE_LATENCY.labels(bucket=str(b)).time():
            scores, margins = self._program.jit(
                self._params, offsets, tuple(xs), tuple(rows))
            out = np.asarray(scores)[:n]
            out_margins = ([np.asarray(m)[:n] for m in margins]
                           if with_margins else None)
        return out, out_margins

    def warmup(self, max_bucket: Optional[int] = None) -> int:
        """Pre-trace every bucket executable (1, 2, 4, … ``max_batch``) so
        live traffic never waits on a compile. Returns the number of
        compiles performed."""
        top = self.max_batch if max_bucket is None else next_bucket(max_bucket)
        before = self._program.compiles
        b = 1
        while b <= top:
            empty = RequestBatch(
                n=b, offsets=np.zeros(b, np.float32),
                xs=tuple(np.zeros((b, len(self.index_maps[c.shard_id])),
                                  np.float32) for c in self.shard_configs),
                rows=tuple(np.full(b, self.stores[cid].fallback_row,
                                   np.int32) for cid in self._re_order))
            self._score_chunk(empty, 0, b)
            b <<= 1
        return self._program.compiles - before
