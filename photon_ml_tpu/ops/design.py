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
import functools
import math
from typing import Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Array = jax.Array

#: the span around :meth:`ChunkedSparseDesign.from_coo`'s build
BUILD_SPAN = "design.build"


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


def _entries_in_key_order(keys: Array, other: Array, vals: Array,
                          n_keys: int) -> tuple[Array, Array, Array, Array]:
    """The entries ordered by key, dropped ones (value 0) last under the key
    ``n_keys``, and each key's first position in that order
    (``(n_keys + 1,)``: a key's run is ``starts[k]:starts[k + 1]``). Entries
    that come in key order (a CSR's rows) are not sorted again; the others go
    through one device sort by key (a key's entries in no
    stated order: every contraction sums over them)."""
    keys = _masked_keys(keys, vals, n_keys=n_keys)
    if not bool(_in_order(keys)):
        # the sort takes its operands' memory: the caller's arrays go as copies
        keys, other, vals = _sort_entries(keys, jnp.copy(other),
                                          jnp.copy(vals))
    return keys, other, vals, _run_starts(keys, n_keys=n_keys)


@functools.partial(jax.jit, static_argnames=("n_keys",))
def _masked_keys(keys, vals, *, n_keys):
    return jnp.where(vals != 0, keys, jnp.int32(n_keys))


@jax.jit
def _in_order(keys):
    return jnp.all(keys[1:] >= keys[:-1])


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _sort_entries(keys, other, vals):
    # by key, then by the other id: a (row, col) pair's duplicates end up
    # side by side, which the busy bins' planes need (one program for every
    # sort of a build: the sort is what compiles longest)
    return lax.sort((keys, other, vals), num_keys=2)


@functools.partial(jax.jit, static_argnames=("n_keys",))
def _run_starts(keys, *, n_keys):
    return jnp.searchsorted(
        keys, jnp.arange(n_keys + 1, dtype=jnp.int32)).astype(jnp.int32)


def _slots_of(flat, at, live):
    """``flat[at]`` where ``live``, else 0, for ``(slots, M)`` positions, as
    ``(M, slots)``. Slots-major while it is made: the long axis last is the
    one the chip's tiles do not pad; the transposition at the end is free, a
    result of ``(M, slots)`` being held long axis last too."""
    if not flat.shape[0]:  # no entries: every slot is padding
        return jnp.zeros(at.shape, flat.dtype).T
    got = jnp.take(flat, at.reshape(-1), axis=0).reshape(at.shape)
    return jnp.where(live, got, jnp.zeros((), flat.dtype)).T


@functools.partial(jax.jit, static_argnames=("chunk", "n_chunks", "skip"))
def _chunk_runs(other, vals, starts, *, chunk, n_chunks, skip=0):
    """Cut every key's run into rows of ``chunk`` slots: ``(values, other
    ids, key of each row)`` with ``n_chunks`` rows, a key of k entries taking
    ceil(k / chunk) of them, in key order; with ``skip``, of every run what
    follows its first ``skip`` entries. A slot past its run's end holds value
    0 and id 0. Every row reads ``chunk`` neighbours of the ordered
    entries."""
    counts = starts[1:] - starts[:-1]
    begin = starts[:-1] + jnp.minimum(counts, skip)
    counts = jnp.maximum(counts - skip, 0)
    n_keys = counts.shape[0]
    per_key = (counts + (chunk - 1)) // chunk
    last = jnp.cumsum(per_key, dtype=jnp.int32)  # a key's rows end here
    row = jnp.arange(n_chunks, dtype=jnp.int32)
    key = jnp.searchsorted(last, row, side="right").astype(jnp.int32)
    key = jnp.minimum(key, n_keys - 1)
    within = (row - (last - per_key)[key]) * chunk  # the key's entries before
    lane = jnp.arange(chunk, dtype=jnp.int32)[:, None]
    live = lane < (counts[key] - within)[None, :]
    at = jnp.where(live, (begin[key] + within)[None, :] + lane, 0)
    return _slots_of(vals, at, live), _slots_of(other, at, live), key


@functools.partial(jax.jit, static_argnames=("chunk", "fold"))
def _first_chunks(other, vals, starts, *, chunk, fold):
    """Every key's first ``chunk`` entries, ``fold`` keys a row: ``(values,
    other ids)`` of ``(m, fold * chunk)``, ``m = ceil(keys / fold)``, key
    ``k * m + j`` in row ``j`` from slot ``k * chunk``. A slot past its run's
    end, or of a key past the last, holds value 0 and id 0."""
    n = starts.shape[0] - 1
    m = -(-n // fold)
    # (fold * chunk, m): what ``a`` holds for each slot's key
    spread = lambda a: jnp.repeat(jnp.pad(a, (0, fold * m - n)).reshape(
        fold, m), chunk, axis=0)
    lane = jnp.tile(jnp.arange(chunk, dtype=jnp.int32), fold)[:, None]
    live = lane < spread(starts[1:] - starts[:-1])
    at = jnp.where(live, spread(starts[:-1]) + lane, 0)
    return _slots_of(vals, at, live), _slots_of(other, at, live)


@functools.partial(jax.jit, static_argnames=("n_keys",))
def _mixed_values(keys, vals, starts, *, n_keys):
    """Per key, how often the value changes inside its run of the ordered
    entries: 0 where all its entries carry one value."""
    change = (keys[1:] == keys[:-1]) & (vals[1:] != vals[:-1])
    upto = jnp.concatenate([jnp.zeros((2,), jnp.int32),
                            jnp.cumsum(change, dtype=jnp.int32)])
    return upto[starts[1:]] - upto[starts[:-1]]


@jax.jit
def _places(keys, rows, place_of):
    """Each entry's place among the busy bins (``place_of[bin]``, -1 for a bin
    that is none), -1 too for every entry of a row in a busy bin but the
    first: the entries come ordered by (bin, row)."""
    again = jnp.concatenate([
        jnp.zeros((1,), bool),
        (keys[1:] == keys[:-1]) & (rows[1:] == rows[:-1])])
    return jnp.where(again, -1, jnp.take(place_of, keys, axis=0))


@functools.partial(jax.jit, static_argnames=("shape", "by_row"))
def _plane(place, rows, *, shape, by_row):
    """The busy bins' bit planes, ``shape`` ``(words, lanes)``: with
    ``by_row`` bit ``place % 32`` of ``[place // 32, row]``, else bit
    ``row % 32`` of ``[row // 32, place]``, set for every entry that has a
    place. Bits are added: no (row, place) comes twice. One scatter into the
    flat plane (a position fits int32: :func:`_hot_tier` sees to it)."""
    packed, lane = (place, rows) if by_row else (rows, place)
    at = jnp.where(place >= 0, (packed // _WORD) * shape[1] + lane,
                   shape[0] * shape[1])  # past the end: dropped
    bit = jnp.uint32(1) << (packed % _WORD).astype(jnp.uint32)
    flat = jnp.zeros((shape[0] * shape[1],), jnp.uint32)
    return flat.at[at].add(bit, mode="drop").reshape(shape)


@functools.partial(jax.jit, donate_argnums=(0,))
def _without(vals, place):
    """The values with the planes' entries zeroed, and how many those are."""
    return (jnp.where(place >= 0, jnp.zeros((), vals.dtype), vals),
            jnp.sum(place >= 0, dtype=jnp.int32))


def _hot_tier(rows, cols, vals, n_rows: int, n_cols: int,
              hot_columns: int | None):
    """The busy bins of a design taken out of its entries: ``(rows, cols,
    vals, planes)``: the entries that stay in the chunks, ordered by (bin,
    row), and the fields ``hot_*`` of the design with ``hot_entries`` (none
    but that, and the entries as they came, where no bin qualifies). Every
    large step ends before the next is asked for: the chip reserves a
    program's temporaries when it is enqueued."""
    keys = _masked_keys(cols, vals, n_keys=n_cols)
    keys, by, of = _sort_entries(keys, jnp.copy(rows), jnp.copy(vals))
    starts = _run_starts(keys, n_keys=n_cols)
    counts = np.diff(np.asarray(starts))
    fit = (counts > 0) & (np.asarray(_mixed_values(
        keys, of, starts, n_keys=n_cols)) == 0)
    if hot_columns is None:
        fit &= counts.astype(np.int64) * _HOT_ONE_IN >= n_rows
        hot_columns = _HOT_MAX
    order = np.argsort(-counts, kind="stable")
    busiest = order[fit[order]][:hot_columns]
    k = -(-len(busiest) // 256) * 256  # whole words, whole tiles of lanes
    words = -(-n_rows // _WORD)
    if not k or max(k // _WORD * n_rows, words * k) >= np.iinfo(np.int32).max:
        return rows, cols, vals, {"hot_entries": 0}
    hot_cols = np.zeros(k, np.int32)
    hot_cols[:len(busiest)] = busiest
    place_of = np.full(n_cols + 1, -1, np.int32)
    place_of[busiest] = np.arange(len(busiest), dtype=np.int32)
    first = np.zeros(k, np.int32)
    first[:len(busiest)] = np.asarray(starts)[busiest]
    hot_vals = jnp.where(jnp.arange(k) < len(busiest),
                         jnp.take(of, jnp.asarray(first)), 0)
    place = _places(keys, by, jnp.asarray(place_of))
    by_row = jax.block_until_ready(
        _plane(place, by, shape=(k // _WORD, n_rows), by_row=True))
    by_bin = jax.block_until_ready(
        _plane(place, by, shape=(words, k), by_row=False))
    of, n_hot = jax.block_until_ready(_without(of, place))
    del place
    # what stays, first: one more sort, of these arrays' own memory
    left = int(counts.sum()) - int(n_hot)
    keys = _masked_keys(keys, of, n_keys=n_cols)
    keys, by, of = _sort_entries(keys, by, of)
    return by[:left], keys[:left], of[:left], dict(
        hot_cols=jnp.asarray(hot_cols), hot_vals=hot_vals, hot_by_row=by_row,
        hot_by_bin=by_bin, hot_entries=int(n_hot))


#: lanes of a table row that :func:`lookup` gathers whole
_LANES = 128
#: table rows that one gather of :func:`lookup` fetches (512 B each): 1.3 GB
#: of them. What was measured, at column chunks of 16 (PERF.md, section 6, PR
#: 32): gathered blocks of (16, 131072, 128) ran 4.4 times as long an index
#: as blocks of (16, 163840, 128), which this constant gives. The cause is not
#: known: the row side's blocks of a 1M-entry table, (40, 65536, 128), are a
#: power of two as well and run at the full rate. Timed on the chip since
#: (PERF.md, section 6, PR 36, call 1), the row side's look-ups from the
#: sparse cell's 1M-entry coefficients, 50M-80M indices: chunks 16, 12, 11,
#: 10 (as 16 x 5M, 12 x 5M, 24 x 2.5M, 88 x 625,000, 11 x 5M, 40 x 1.25M)
#: gathered at 1.76-1.80 ns an index and picked their lanes at 0.68-0.88;
#: this constant's blocks and those of 1 << 21, 3 << 20 and 5 << 18 were
#: within 4% of one another there
_LOOKUP_ROWS = 5 << 19


def lookup(table: Array, idx: Array) -> Array:
    """``table[idx]`` for a 1-D ``table`` and ``(C, M)`` indices that lie in
    range, ``M`` the long axis.

    Not ``jnp.take``: on the chip XLA serialises a gather of scalars, one
    element at a time whatever the table's size, and fetches whole rows of
    128 lanes four times as fast an index (PERF.md, section 6, PR 32), as
    long as the compiler stages the table in VMEM: up to about 5M entries;
    from 6M or more the same gather runs at 10 to 16 ns an index, as slow
    as the scalars' (PERF.md, section 6, PR 33). So the table is read as
    ``(T / 128, 128)``, the row ``idx >> 7`` of every index is gathered and
    the lane ``idx & 127`` picked by comparison, ``M`` in blocks so that the
    gathered rows of one block stay near a gigabyte (``_LOOKUP_ROWS``). The
    indices stay ``(C, M)`` throughout: the long axis last is the one the
    chip's tiles do not pad.
    """
    c, m = idx.shape[-2:]
    if idx.ndim != 2:
        return jax.vmap(lookup, in_axes=(None, 0))(table, idx)
    rows = jnp.pad(table, (0, -table.shape[0] % _LANES)).reshape(-1, _LANES)
    lane = jnp.arange(_LANES, dtype=idx.dtype)

    def pick(i):
        got = rows.at[i // _LANES].get(mode="promise_in_bounds")
        return jnp.sum(jnp.where((i % _LANES)[..., None] == lane, got, 0),
                       axis=-1)

    block = max(_LOOKUP_ROWS // max(c, 1) // 1024 * 1024, 1024)
    whole = m // block
    out = []
    if whole:
        blocks = lax.map(
            lambda b: pick(lax.dynamic_slice_in_dim(idx, b * block, block,
                                                    axis=1)),
            jnp.arange(whole, dtype=jnp.int32))
        out.append(jnp.moveaxis(blocks, 0, 1).reshape(c, whole * block))
    if m % block or not whole:
        out.append(pick(idx[:, whole * block:]))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


#: sublanes of the chip's (8, 128) tile of 32-bit values: a ``(M, C)`` chunk
#: array lies with C in them, padded to a multiple of 8
_SUBLANES = 8
#: the widest chunk a build makes
_MAX_CHUNK = 128
#: what :meth:`ChunkedSparseDesign.row_widths` weighs, in ns: a stored slot
#: of a row chunk (its whole table row gathered, 1.77-1.80, and the lane
#: picked, 0.68-0.88) and an overflow chunk's sum through the segment-sum
#: (8.76-8.98), as the chip ran them from a 1M-entry table (PERF.md, section
#: 6, PR 36, call 1)
_SLOT_NS = 2.55
_SUM_NS = 8.8

#: bits of a word of the busy bins' planes
_WORD = 32
#: a bin is busy where it holds an entry in one row of this many or more:
#: below it a bit a row costs more than the bin's few entries looked up
_HOT_ONE_IN = 1024
#: the most busy bins that get planes (a plane is a bit a row, stored twice)
_HOT_MAX = 4096
#: designs of fewer entries keep every bin in the chunks
_HOT_MIN_ENTRIES = 1 << 19


def _planes_dot(bits: Array, coef: Array) -> Array:
    """``Σ_a Σ_j bit j of bits[a, b] * coef[a, j]`` for ``(A, B)`` words and
    ``(A, 32)`` coefficients: one pass over the words, a sum along the major
    axis, ``B`` in the lanes."""
    acc = jnp.zeros(bits.shape, coef.dtype)
    for j in range(_WORD):
        on = (bits & jnp.uint32(1 << j)) != 0
        acc = acc + jnp.where(on, coef[:, j][:, None], 0)
    return jnp.sum(acc, axis=0)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ChunkedSparseDesign:
    """Dual chunked-COO sparse design: scatters shrunk by chunk partial sums.

    ``CsrDesign`` takes one ``segment_sum`` of every entry for the margins
    and one scatter-add of every entry for the gradient transpose, and XLA
    runs a scatter-add on a TPU one element at a time, while reductions
    along a fixed width stream. So this layout stores the entries TWICE,
    each side ordered by its key at build time (:meth:`layout`, on the
    device), and only chunk sums are scattered:

    - row-major: ``(Mr, C)`` values/col-ids with one row id per chunk —
      margins = per-chunk ``Σ v·w[col]`` then a segment-sum of ONLY
      ``Mr ≈ nnz/C + n`` partials;
    - col-major: ``(Mc, C)`` values/row-ids with one col id per chunk —
      the gradient transpose the same way into ``d`` bins.

    Chunk padding carries ``value = 0`` (contributes nothing). A chunk
    width trades padding (wide) against segment-sum length (narrow). The
    column side's width is :meth:`default_chunk`, the per-key median
    rounded to a multiple of 8. The row side keeps every row's first chunk
    at the row's own place (``fvals``: those chunk sums are margins, and
    only the chunks a row fills beyond its first go through the
    segment-sum), and takes its two widths from the rows' counts
    (:meth:`row_widths`): the sparse cell's rows, 9 entries at the mean
    after the planes, keep first chunks of 10 and overflow chunks of 2,
    52.5M slots where chunks of 16 stored 80.0M (0.150 s a ``matvec`` where
    it took 0.214 on the chip: PERF.md, section 6, PR 36). 2x memory vs
    CsrDesign — the price of replacing both big scatters. On the chip a
    ``(M, C)`` array lies with ``M``, its long axis, in the lanes and C in
    sublanes padded to a multiple of 8, so the build and the contractions
    walk it slots-major, and first chunks C wide are held ``fold`` rows a
    lane column, ``fold x C`` a multiple of 8: their tiles hold no padding
    (chunks of 10 would fill 16 sublanes; the chip's look-ups took the same
    time an index either way, so the fold saves memory alone).

    A look-up costs the same whatever it fetches (nanoseconds an index), so
    a design whose entries crowd into a few bins (hashed one-hot features: a
    few thousand bins of a million hold four fifths of the entries) keeps
    those out of the chunks: a BUSY bin whose entries all carry one value is
    a bit a row (``hot_*``: bit planes, again stored twice, once with the
    rows and once with the bins in the lanes), and a contraction reads the
    planes in one streaming pass with no look-up at all. Which bins, and
    whether any, the build decides from the counts it sees (:meth:`layout`).
    What the build and an evaluation take at 2 x 10^8 entries, and which
    operations take it: PERF.md, sections 5 and 6 (PR 32). This is the
    counterpart of the reference's executor-local hash-map gradient
    accumulation in ``function/glm/ValueAndGradientAggregator.scala``,
    re-shaped for a machine that hates random writes and loves wide reads.
    """

    rvals: Array  # (Mr, C) f32
    rcols: Array  # (Mr, C) int32
    rrow: Array  # (Mr,) int32 — row id per chunk (non-decreasing)
    cvals: Array  # (Mc, C) f32
    crows: Array  # (Mc, C) int32
    ccol: Array  # (Mc,) int32 — col id per chunk (non-decreasing)
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    n_cols: int = dataclasses.field(metadata=dict(static=True))
    #: every row's first chunk, or None: ``fold`` rows a lane column, row
    #: ``k * m + j`` in slots ``k * C`` to ``(k + 1) * C`` of column ``j``
    #: (``m`` columns), so that ``fold * C`` fills whole tiles of 8
    #: sublanes. Their chunk sums ARE margins; the ``r*`` chunks then hold
    #: only what rows keep beyond their first chunk (:meth:`layout`)
    fvals: Array | None = None  # (m, fold * C) f32
    fcols: Array | None = None  # (m, fold * C) int32
    fold: int = dataclasses.field(default=1, metadata=dict(static=True))
    #: the busy bins, or None: their ids and the one value each one's entries
    #: carry (``(K,)``; unused places hold value 0), and their planes: bit
    #: ``k % 32`` of ``hot_by_row[k // 32, i]`` and bit ``i % 32`` of
    #: ``hot_by_bin[i // 32, k]`` say that row ``i`` has an entry in busy bin
    #: ``k`` (one: a second entry of that row and bin stays in the chunks)
    hot_cols: Array | None = None  # (K,) int32
    hot_vals: Array | None = None  # (K,) f32
    hot_by_row: Array | None = None  # (K / 32, n_rows) uint32
    hot_by_bin: Array | None = None  # (ceil(n_rows / 32), K) uint32

    @property
    def n_samples(self) -> int:
        return self.n_rows

    @property
    def dim(self) -> int:
        return self.n_cols

    @property
    def rows_first(self) -> bool:
        """Every row's first chunk at the row's own place (``fvals``)."""
        return self.fvals is not None

    @staticmethod
    def _chunk_sums(vals: Array, idx: Array, table: Array,
                    fold: int = 1) -> Array:
        """``Σ_slot vals * table[idx]`` per chunk, for ``(M, C)`` chunks;
        with ``fold``, ``fold`` chunks a row, the sums of the row's first
        chunks, then of its second ..."""
        acc = jnp.promote_types(jnp.promote_types(vals.dtype, table.dtype),
                                jnp.float32)
        got = lookup(table, jnp.swapaxes(idx, -1, -2))
        part = (jnp.swapaxes(vals, -1, -2) * got).astype(acc)
        c = part.shape[-2] // fold
        return jnp.concatenate(
            [jnp.sum(part[..., k * c:(k + 1) * c, :], axis=-2)
             for k in range(fold)], axis=-1)

    def matvec(self, w: Array) -> Array:
        with jax.named_scope("design.matvec"):
            out = None
            if self.rows_first:
                out = self._chunk_sums(self.fvals, self.fcols, w,
                                       self.fold)[..., :self.n_rows]
            if out is None or self.rrow.shape[-1]:
                summed = jax.ops.segment_sum(
                    self._chunk_sums(self.rvals, self.rcols, w), self.rrow,
                    num_segments=self.n_rows, indices_are_sorted=True)
                out = summed if out is None else out + summed
            if self.hot_cols is not None:
                coef = self.hot_vals * lookup(w, self.hot_cols[None, :])[0]
                out = out + _planes_dot(self.hot_by_row,
                                        coef.reshape(-1, _WORD))
            return out

    def _transposed(self, vals: Array, g: Array, hot_vals) -> Array:
        with jax.named_scope("design.rmatvec"):
            out = jax.ops.segment_sum(
                self._chunk_sums(vals, self.crows, g), self.ccol,
                num_segments=self.n_cols, indices_are_sorted=True)
            if self.hot_cols is not None:
                words = self.hot_by_bin.shape[0]
                per_row = jnp.pad(g, (0, words * _WORD - g.shape[0]))
                sums = _planes_dot(self.hot_by_bin,
                                   per_row.reshape(words, _WORD))
                out = out.at[self.hot_cols].add(hot_vals * sums)
            return out

    def rmatvec(self, g: Array) -> Array:
        return self._transposed(self.cvals, g, self.hot_vals)

    def rmatvec_squared(self, g: Array) -> Array:
        """``(X²)ᵀ g`` — the Hessian-diagonal contraction (values squared)."""
        hot = None if self.hot_vals is None else jnp.square(self.hot_vals)
        return self._transposed(jnp.square(self.cvals), g, hot)

    @staticmethod
    def default_chunk(counts: np.ndarray) -> int:
        """Median nnz of the non-empty keys, rounded to 8 in [8, 128]: the
        column side's width, and the row side's where rows are stacked in
        blocks (``rows_first`` off). Timed on the chip at 16 (the sparse
        cell's rows before PR 36: 1.80 ns a slot gathered, 0.78 picked, of
        which a third padding) and as the cell's column side (PERF.md,
        section 6, PRs 32 and 36)."""
        nz = counts[counts > 0]
        if not len(nz):
            return 8
        med = int(np.median(nz))
        return int(np.clip(-(-med // 8) * 8, 8, 128))

    @staticmethod
    def row_widths(counts: np.ndarray) -> tuple[int, int]:
        """The row side's widths where every row's first chunk stands at its
        own place: ``(C, O)``, the first chunks' and the overflow chunks'
        (those a row fills beyond its first), each in [1, 128], that cost an
        evaluation least by the chip's rates: ``_SLOT_NS`` a stored slot and
        ``_SUM_NS`` more an overflow chunk (its sum goes through the
        segment-sum). ``O = C`` where no row overflows."""
        k, rows = np.unique(np.asarray(counts, np.int64), return_counts=True)
        widths = np.arange(1, _MAX_CHUNK + 1)
        best = None
        for c in widths:
            over = np.maximum(k - c, 0)
            if not over.any():
                cost, o = _SLOT_NS * c * rows.sum(), c
            else:
                chunks = (-(-over[None, :] // widths[:, None]) * rows).sum(1)
                costs = _SLOT_NS * widths * chunks + _SUM_NS * chunks
                o = int(np.argmin(costs)) + 1
                cost = _SLOT_NS * c * rows.sum() + costs[o - 1]
            if best is None or cost < best[0]:
                best = (cost, int(c), int(o))
        return best[1:]

    @staticmethod
    def layout(rows, cols, vals, n_rows: int, n_cols: int, *,
               row_chunk: int | None = None,
               col_chunk: int | None = None,
               rows_first: bool = True,
               hot_columns: int | None = None) -> dict:
        """THE build: both chunk layouts from COO triplets (host or device
        arrays), made on the device in int32 and float32. Per side: the
        entries ordered by the side's key (one sort, none for entries that
        come in that order), every key's run cut into rows of the chunk
        width. Only per-key counts visit the host (the default widths, the
        number of chunk rows and the busy bins are read from them). Explicit
        zeros are dropped; duplicate ``(row, col)`` entries keep separate
        slots. Every row's first chunk stands at the row's own place
        (``rows_first``: ``fvals``, ``fcols``, ``fold``; the rows' own
        entries beyond it in the ``r*`` chunks), ``row_chunk`` wide and the
        others ``row_chunk`` too where the caller gives it, else as
        :meth:`row_widths` weighs the rows' counts (``row_chunk``,
        ``row_overflow_chunk``). A caller that stacks layouts of several
        blocks turns ``rows_first`` off and gets the chunks of non-empty
        rows alone, :meth:`default_chunk` wide.

        ``hot_columns``: how many of the busiest bins become bit planes
        (:func:`_hot_tier`): 0 for none; by default those that hold an entry
        in one row of ``_HOT_ONE_IN`` or more, ``_HOT_MAX`` at the most, in a
        design of ``_HOT_MIN_ENTRIES`` entries or more. Only a bin whose
        entries all carry one value can be one, and of a row's duplicate
        entries in it the first alone; what the planes hold leaves the
        chunks."""
        rows = jnp.asarray(rows, jnp.int32).reshape(-1)
        cols = jnp.asarray(cols, jnp.int32).reshape(-1)
        vals = jnp.asarray(vals, jnp.float32).reshape(-1)
        limit = np.iinfo(np.int32).max
        if max(n_rows, n_cols) >= limit or vals.shape[0] >= limit - 128:
            raise ValueError(
                f"{vals.shape[0]} entries of a {n_rows} x {n_cols} design "
                f"pass what int32 positions address")
        hot = {"hot_entries": 0}
        if hot_columns != 0 and (hot_columns is not None
                                 or vals.shape[0] >= _HOT_MIN_ENTRIES):
            rows, cols, vals, hot = _hot_tier(
                rows, cols, vals, int(n_rows), int(n_cols), hot_columns)

        def ordered(keys, other, n_keys):
            _, other, v, starts = _entries_in_key_order(keys, other, vals,
                                                        n_keys)
            return other, v, starts, np.diff(np.asarray(starts))

        def chunked(other, v, starts, counts, chunk, skip=0):
            n_chunks = int((-(-np.maximum(counts - skip, 0) // chunk)).sum())
            if n_chunks * chunk >= limit:
                raise ValueError(f"{n_chunks} chunks of {chunk} slots pass "
                                 f"what int32 positions address")
            return _chunk_runs(other, v, starts, chunk=int(chunk),
                               n_chunks=n_chunks, skip=int(skip))

        other, v, starts, counts = ordered(cols, rows, int(n_cols))
        col_chunk = int(col_chunk or ChunkedSparseDesign.default_chunk(
            counts))
        cvals, crows, ccol = chunked(other, v, starts, counts, col_chunk)
        other, v, starts, counts = ordered(rows, cols, int(n_rows))
        lay = dict(cvals=cvals, crows=crows, ccol=ccol, col_chunk=col_chunk,
                   rows_first=bool(rows_first),
                   entries=int(counts.sum()) + hot["hot_entries"], **hot)
        if not rows_first:
            row_chunk = int(row_chunk or ChunkedSparseDesign.default_chunk(
                counts))
            rvals, rcols, rrow = chunked(other, v, starts, counts, row_chunk)
            return dict(rvals=rvals, rcols=rcols, rrow=rrow,
                        row_chunk=row_chunk, **lay)
        first, beyond = (int(row_chunk),) * 2 if row_chunk else \
            ChunkedSparseDesign.row_widths(counts)
        fold = _SUBLANES // math.gcd(first, _SUBLANES)
        fvals, fcols = _first_chunks(other, v, starts, chunk=first, fold=fold)
        rvals, rcols, rrow = chunked(other, v, starts, counts, beyond,
                                     skip=first)
        return dict(rvals=rvals, rcols=rcols, rrow=rrow, fvals=fvals,
                    fcols=fcols, fold=fold, row_chunk=first,
                    row_overflow_chunk=beyond, **lay)

    @staticmethod
    def layout_numpy(rows, cols, vals, n_rows: int, n_cols: int, *,
                     row_chunk: int | None = None,
                     col_chunk: int | None = None) -> dict:
        """:meth:`layout` with its arrays on the host, for callers that pad
        and stack the layouts of several row blocks (chunk rows of non-empty
        keys only, so that padding chunks can follow them; every bin in the
        chunks)."""
        lay = ChunkedSparseDesign.layout(
            rows, cols, vals, n_rows, n_cols, row_chunk=row_chunk,
            col_chunk=col_chunk, rows_first=False, hot_columns=0)
        return {k: np.asarray(v) if isinstance(v, jax.Array) else v
                for k, v in lay.items()}

    @staticmethod
    def from_coo(rows, cols, vals, n_rows: int, n_cols: int,
                 row_chunk: int | None = None, col_chunk: int | None = None,
                 hot_columns: int | None = None) -> "ChunkedSparseDesign":
        """Build both layouts from COO triplets (:meth:`layout`), under a
        ``design.build`` span that carries the sizes. Duplicate (row, col)
        entries accumulate in every contraction, the same semantics as
        CsrDesign."""
        from photon_ml_tpu.telemetry import tracing

        with tracing.span(BUILD_SPAN, rows=int(n_rows),
                          dim=int(n_cols)) as build:
            lay = ChunkedSparseDesign.layout(
                rows, cols, vals, n_rows, n_cols, row_chunk=row_chunk,
                col_chunk=col_chunk, hot_columns=hot_columns)
            lay.pop("rows_first")
            sizes = {k: lay.pop(k) for k in (
                "entries", "row_chunk", "row_overflow_chunk", "col_chunk",
                "hot_entries")}
            design = ChunkedSparseDesign(
                **lay, n_rows=int(n_rows), n_cols=int(n_cols))
            jax.block_until_ready(design)
            build.set(**sizes,
                      row_slots=design.fvals.size + design.rvals.size,
                      col_slots=design.cvals.size,
                      hot_columns=0 if design.hot_cols is None
                      else design.hot_cols.shape[0])
        return design


Design = Union[DenseDesign, CsrDesign, ChunkedSparseDesign]


def design_kind(design) -> str:
    """A design's name in span attributes: ``dense``, ``csr``,
    ``chunked_sparse``; another type's own name, lower-cased."""
    names = {DenseDesign: "dense", CsrDesign: "csr",
             ChunkedSparseDesign: "chunked_sparse"}
    return names.get(type(design), type(design).__name__.lower())
