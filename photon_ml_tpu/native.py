"""Build/load the native ingest library and decode Avro training files.

Pairs with ``native/avro_reader.cc`` (see its header comment for the role).
The module compiles the shared library on first use from the three committed
sources (g++ -O3 -march=native, linked against zlib) into
``native/build/libphoton_native-<key>.so`` and exposes
:func:`decode_training_file` returning flat numpy arrays. ``<key>`` hashes
the sources, the compiler flags and this host's CPU: ``-march=native`` bakes
the build host's instruction set into the artifact, so a library built on
one machine is never loaded on another (a checkout copied between hosts
rebuilds instead of faulting on an unknown instruction), and an edited
source can never pair with a stale binary. :data:`available` is False when
the library cannot be built or loaded — said once, with the reason, at
WARNING — and callers then take their pure-Python paths
(``AvroDataReader`` decodes ~30x slower through :mod:`photon_ml_tpu.io.avro`).
"""

from __future__ import annotations

import ctypes
import dataclasses
import hashlib
import io
import json
import logging
import os
import platform
import subprocess
import threading
from typing import Optional, Sequence

import numpy as np

from photon_ml_tpu.io import avro as avro_mod

logger = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SOURCES = tuple(os.path.join(_REPO_ROOT, "native", name) for name in
                 ("avro_reader.cc", "avro_writer.cc", "bucket_pack.cc"))
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")
#: -march=native measured ~7% on the decode hot loop (figure from before
#: PR 1; not measured on the present hosts)
_FLAGS = ("-std=c++17", "-O3", "-march=native", "-shared", "-fPIC")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_error: Optional[str] = None

#: canonical field order we emit; the file's order is matched against names
_FIELDS = ("uid", "response", "offset", "weight", "features", "metadataMap")


def _host_cpu() -> str:
    """What ``-march=native`` resolves against: the CPU model and its
    instruction-set flags."""
    try:
        with open("/proc/cpuinfo") as f:
            return "".join(sorted({line for line in f if line.startswith(
                ("model name", "flags"))}))
    except OSError:
        return platform.machine() + platform.processor()


def _artifact_path() -> str:
    """``native/build/libphoton_native-<key>.so`` for these sources, these
    flags and this host. Raises OSError when a source is missing."""
    h = hashlib.sha256()
    for src in _SOURCES:
        with open(src, "rb") as f:
            h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_host_cpu().encode())
    return os.path.join(_BUILD_DIR,
                        f"libphoton_native-{h.hexdigest()[:16]}.so")


def _build(lib_path: str) -> None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # compile beside the target and rename: a concurrent process (a
    # supervised fleet's workers start together) never loads a half-written
    # library
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", *_FLAGS, "-o", tmp, *_SOURCES, "-lz"]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=300)
        if proc.returncode != 0:
            raise OSError(f"{' '.join(cmd)} exited {proc.returncode}: "
                          f"{proc.stderr[-2000:]}")
        os.replace(tmp, lib_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_error
    with _lock:
        if _lib is not None or _load_error is not None:
            return _lib
        try:
            lib_path = _artifact_path()
            if not os.path.exists(lib_path):
                _build(lib_path)
            lib = ctypes.CDLL(lib_path)
            _declare(lib)
        except (OSError, subprocess.SubprocessError) as e:
            _load_error = f"{type(e).__name__}: {e}"
            logger.warning(
                "native library unavailable, callers take their pure-Python "
                "paths (Avro decode ~30x slower): %s", _load_error)
            return None
        _lib = lib
        return _lib


def _declare(lib: ctypes.CDLL) -> None:
    """ctypes signatures of every exported function."""
    lib.photon_decode_blocks.restype = ctypes.c_void_p
    lib.photon_decode_blocks.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int), ctypes.c_char_p, ctypes.c_char_p]
    lib.photon_result_error.restype = ctypes.c_char_p
    lib.photon_result_error.argtypes = [ctypes.c_void_p]
    for name, res in (("n_records", ctypes.c_int64),
                      ("nnz", ctypes.c_int64),
                      ("n_feature_keys", ctypes.c_int32),
                      ("feature_bytes_len", ctypes.c_int64)):
        fn = getattr(lib, f"photon_result_{name}")
        fn.restype = res
        fn.argtypes = [ctypes.c_void_p]
    lib.photon_result_copy_core.argtypes = [ctypes.c_void_p] + \
        [np.ctypeslib.ndpointer(dtype=d, flags="C_CONTIGUOUS")
         for d in (np.float64, np.float64, np.float64, np.int64,
                   np.int32, np.float64)]
    lib.photon_result_copy_feature_keys.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")]
    lib.photon_result_id_vocab_size.restype = ctypes.c_int32
    lib.photon_result_id_vocab_size.argtypes = [ctypes.c_void_p,
                                                ctypes.c_int32]
    lib.photon_result_id_vocab_bytes_len.restype = ctypes.c_int64
    lib.photon_result_id_vocab_bytes_len.argtypes = [ctypes.c_void_p,
                                                     ctypes.c_int32]
    lib.photon_result_copy_id_col.argtypes = [
        ctypes.c_void_p, ctypes.c_int32,
        np.ctypeslib.ndpointer(dtype=np.int32, flags="C_CONTIGUOUS"),
        ctypes.c_char_p,
        np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")]
    lib.photon_result_free.argtypes = [ctypes.c_void_p]
    _i64p = np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")
    _i32p = np.ctypeslib.ndpointer(dtype=np.int32, flags="C_CONTIGUOUS")
    _f32p = np.ctypeslib.ndpointer(dtype=np.float32, flags="C_CONTIGUOUS")
    _f64p = np.ctypeslib.ndpointer(dtype=np.float64, flags="C_CONTIGUOUS")
    lib.photon_shard_split_count.restype = None
    lib.photon_shard_split_count.argtypes = [
        _i64p, _i32p, ctypes.c_int64, _i32p, ctypes.c_int32, _i64p]
    lib.photon_shard_split_fill.restype = None
    lib.photon_shard_split_fill.argtypes = [
        _i64p, _i32p, _f64p, ctypes.c_int64, _i32p, ctypes.c_int32,
        _i64p, _i32p, _f32p]
    lib.photon_counting_sort.restype = None
    lib.photon_counting_sort.argtypes = [
        _i64p, ctypes.c_int64, _i64p, _i64p]
    lib.photon_re_feature_counts.restype = None
    lib.photon_re_feature_counts.argtypes = [
        _i64p, _i32p, _i64p, _i64p,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        _i64p, _i64p, _i64p]
    lib.photon_re_bucket_fill.restype = None
    lib.photon_re_bucket_fill.argtypes = [
        _i64p, _i32p, _f32p, _i64p, _i64p, _f32p, _f32p, _i64p,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, _i64p, _i64p, _i64p, _i64p,
        _f32p, _f32p, _f32p, _i64p, _i64p]
    lib.photon_re_bucket_indices.restype = None
    lib.photon_re_bucket_indices.argtypes = [
        _i64p, _i32p, _i64p, _i64p, _i64p,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        _i64p, _i64p, _i64p, _i64p]
    lib.photon_write_scoring_results.restype = ctypes.c_int64
    lib.photon_write_scoring_results.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
        np.ctypeslib.ndpointer(dtype=np.float64, flags="C_CONTIGUOUS"),
        ctypes.c_void_p,  # labels (f64*) or NULL
        ctypes.c_char_p,  # uid bytes or NULL
        ctypes.c_void_p,  # uid offsets (i64*) or NULL
        ctypes.c_int64, ctypes.c_int64]
    _f64p = np.ctypeslib.ndpointer(dtype=np.float64, flags="C_CONTIGUOUS")
    lib.photon_write_re_models.restype = ctypes.c_int64
    lib.photon_write_re_models.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_char_p, _i64p,
        ctypes.c_char_p, ctypes.c_int64,
        _i64p, _i32p, _f64p,
        ctypes.c_void_p,  # variances (f64*) or NULL
        ctypes.c_char_p, _i64p, ctypes.c_char_p, _i64p,
        ctypes.c_int64]


def available() -> bool:
    return _load() is not None


@dataclasses.dataclass
class DecodedFile:
    """Columnar decode of one TrainingExampleAvro container file."""

    response: np.ndarray  # (n,) f64, NaN never (response is required)
    offset: np.ndarray  # (n,) f64, NaN = null
    weight: np.ndarray  # (n,) f64, NaN = null
    feat_indptr: np.ndarray  # (n+1,) i64
    feat_key_id: np.ndarray  # (nnz,) i32 -> feature_keys
    feat_val: np.ndarray  # (nnz,) f64
    feature_keys: list[str]  # interned "name\x01term" strings
    id_cols: dict[str, np.ndarray]  # (n,) i32, -1 missing
    id_vocabs: dict[str, list[str]]

    @property
    def n_records(self) -> int:
        return int(self.response.shape[0])


def _schema_layout(schema) -> Optional[tuple[list[int], bytes]]:
    """Match the file schema against TrainingExampleAvro; return
    (field_order, null_first) or None if incompatible."""
    if not isinstance(schema, dict) or schema.get("type") != "record":
        return None
    fields = schema.get("fields", [])
    if len(fields) != len(_FIELDS):
        return None
    order: list[int] = []
    null_first = bytearray(len(_FIELDS))
    for f in fields:
        name = f.get("name")
        if name not in _FIELDS:
            return None
        idx = _FIELDS.index(name)
        order.append(idx)
        t = f.get("type")
        if name in ("uid", "offset", "weight", "metadataMap"):
            if not (isinstance(t, list) and len(t) == 2 and "null" in t):
                return None
            null_first[idx] = 1 if t[0] == "null" else 0
            other = t[1] if t[0] == "null" else t[0]
            if name == "uid" and other != "string":
                return None
            if name in ("offset", "weight") and other != "double":
                return None
            if name == "metadataMap" and not (
                    isinstance(other, dict) and other.get("type") == "map"
                    and other.get("values") == "string"):
                return None
        elif name == "response":
            if t != "double":
                return None
        else:  # features
            if not (isinstance(t, dict) and t.get("type") == "array"):
                return None
            items = t.get("items")
            if not (isinstance(items, dict) and items.get("type") == "record"):
                return None
            fnames = [x.get("name") for x in items.get("fields", [])]
            ftypes = [x.get("type") for x in items.get("fields", [])]
            if fnames != ["name", "term", "value"] or \
                    ftypes != ["string", "string", "double"]:
                return None
    return order, bytes(null_first)


def _snappy_blocks_to_null(blocks: bytes, sync: bytes, path: str) -> bytes:
    """Rewrite a snappy-codec block stream as a null-codec stream.

    Each container block is ``long(count) long(size) payload sync``; the
    frame decode (decompress + CRC) is :func:`io.avro.snappy_decode_block`.
    CRC mismatches raise — matching the pure-Python reader's behavior rather
    than None-falling-back, since the file is genuinely corrupt.

    Memory note: this materializes the file's full UNCOMPRESSED block stream
    (the native decoder consumes one contiguous buffer); the caller drops the
    compressed blob before invoking the decoder so peak overhead vs the
    deflate path is one uncompressed copy per in-flight decode."""
    src = io.BytesIO(blocks)
    out = io.BytesIO()
    total = len(blocks)
    while src.tell() < total:
        count = avro_mod.read_long(src)
        size = avro_mod.read_long(src)
        data = avro_mod.snappy_decode_block(src.read(size), context=path)
        block_sync = src.read(avro_mod.SYNC_SIZE)
        if block_sync != sync:
            raise ValueError(f"sync marker mismatch in {path!r}")
        avro_mod.write_long(out, count)
        avro_mod.write_long(out, len(data))
        out.write(data)
        out.write(sync)
    return out.getvalue()


def decode_training_file(path: str, id_keys: Sequence[str] = ()
                         ) -> Optional[DecodedFile]:
    """Decode via the native library; None if unavailable/incompatible
    (caller falls back to the pure-Python reader)."""
    lib = _load()
    if lib is None:
        return None
    with open(path, "rb") as f:
        blob = f.read()
    buf = io.BytesIO(blob)
    if buf.read(4) != avro_mod.MAGIC:
        return None
    # header: metadata map + sync (python-side; cheap)
    names: dict = {}
    meta = {}
    while True:
        count = avro_mod.read_long(buf)
        if count == 0:
            break
        if count < 0:
            count = -count
            avro_mod.read_long(buf)
        for _ in range(count):
            k = avro_mod.read_datum(buf, "string", names)
            size = avro_mod.read_long(buf)
            meta[k] = buf.read(size)
    codec = meta.get("avro.codec", b"null").decode()
    if codec not in ("null", "deflate", "snappy"):
        return None
    layout = _schema_layout(json.loads(meta["avro.schema"].decode()))
    if layout is None:
        return None
    field_order, null_first = layout
    sync = buf.read(avro_mod.SYNC_SIZE)
    blocks = blob[buf.tell():]
    if codec == "snappy":
        # the native decoder speaks null/deflate; snappy blocks are small in
        # number (thousands of records each) — decompress them here and hand
        # the decoder an equivalent null-codec block stream, keeping the
        # C++ fast path instead of silently dropping to the Python reader
        blocks = _snappy_blocks_to_null(blocks, sync, path)
        codec = "null"
        del blob, buf  # free the compressed copy before the decode

    order_arr = (ctypes.c_int * len(field_order))(*field_order)
    rp = lib.photon_decode_blocks(
        blocks, len(blocks), sync, int(codec == "deflate"), order_arr,
        null_first, "\n".join(id_keys).encode())
    if not rp:
        return None
    try:
        err = lib.photon_result_error(rp)
        if err:
            raise ValueError(f"native avro decode failed for {path!r}: "
                             f"{err.decode()}")
        n = lib.photon_result_n_records(rp)
        nnz = lib.photon_result_nnz(rp)
        n_keys = lib.photon_result_n_feature_keys(rp)
        key_bytes_len = lib.photon_result_feature_bytes_len(rp)

        response = np.empty(n, np.float64)
        offset = np.empty(n, np.float64)
        weight = np.empty(n, np.float64)
        indptr = np.empty(n + 1, np.int64)
        key_id = np.empty(nnz, np.int32)
        val = np.empty(nnz, np.float64)
        lib.photon_result_copy_core(rp, response, offset, weight, indptr,
                                    key_id, val)

        kb = ctypes.create_string_buffer(max(int(key_bytes_len), 1))
        koff = np.empty(n_keys + 1, np.int64)
        lib.photon_result_copy_feature_keys(rp, kb, koff)
        kraw = kb.raw[:key_bytes_len]
        feature_keys = [kraw[koff[i]:koff[i + 1]].decode()
                        for i in range(n_keys)]

        id_cols = {}
        id_vocabs = {}
        for c, key in enumerate(id_keys):
            vsize = lib.photon_result_id_vocab_size(rp, c)
            vbytes = lib.photon_result_id_vocab_bytes_len(rp, c)
            ids = np.empty(n, np.int32)
            vb = ctypes.create_string_buffer(max(int(vbytes), 1))
            voff = np.empty(vsize + 1, np.int64)
            lib.photon_result_copy_id_col(rp, c, ids, vb, voff)
            vraw = vb.raw[:vbytes]
            id_cols[key] = ids
            id_vocabs[key] = [vraw[voff[i]:voff[i + 1]].decode()
                              for i in range(vsize)]
        return DecodedFile(
            response=response, offset=offset, weight=weight,
            feat_indptr=indptr, feat_key_id=key_id, feat_val=val,
            feature_keys=feature_keys, id_cols=id_cols, id_vocabs=id_vocabs)
    finally:
        lib.photon_result_free(rp)


def write_scoring_results(path: str, scores: np.ndarray,
                          labels: Optional[np.ndarray] = None,
                          uids: Optional[Sequence[str]] = None,
                          block_records: int = 65536) -> bool:
    """Write a ``ScoringResultAvro`` container via the native writer.

    Columns in, container out — the output half of the native IO path
    (measured ~5M rows/s vs ~100k for the pure-Python record encoder —
    ~50x; see ``native/avro_writer.cc``).
    ``uids=None`` writes decimal record indices (what ``score_game``
    emits). Returns False when the native library is unavailable, in which
    case the caller falls back to :func:`photon_ml_tpu.io.avro.write_avro_file`.
    """
    lib = _load()
    if lib is None:
        return False
    from photon_ml_tpu.io.schemas import SCORING_RESULT_AVRO

    schema = json.dumps(SCORING_RESULT_AVRO).encode()
    scores = np.ascontiguousarray(scores, np.float64)
    if scores.ndim != 1:
        raise ValueError(f"scores must be 1-D, got shape {scores.shape}")
    n = scores.shape[0]
    labels_ptr = None
    labels_arr = None
    if labels is not None:
        labels_arr = np.ascontiguousarray(labels, np.float64)
        if labels_arr.shape != (n,):
            raise ValueError(
                f"labels must be shape ({n},), got {labels_arr.shape}")
        labels_ptr = labels_arr.ctypes.data_as(ctypes.c_void_p)
    uid_bytes = None
    uid_off_ptr = None
    uid_off = None
    if uids is not None:
        encoded = [u.encode() for u in uids]
        if len(encoded) != n:
            raise ValueError("uids length mismatch")
        uid_off = np.zeros(n + 1, np.int64)
        np.cumsum([len(b) for b in encoded], out=uid_off[1:])
        uid_bytes = b"".join(encoded)
        uid_off_ptr = uid_off.ctypes.data_as(ctypes.c_void_p)
    wrote = lib.photon_write_scoring_results(
        path.encode(), schema, len(schema), scores, labels_ptr,
        uid_bytes, uid_off_ptr, n, block_records)
    return wrote == n


class BucketPackScratch:
    """Shared dim-sized scratch for one dataset build's packer calls.

    The stamp arrays are -1-initialized once here and shared across every
    pass-A/pass-B call of a single build (the C side stamps with dense
    entity ids, which never repeat across calls — see bucket_pack.cc's
    scratch contract). Pass A and pass B need DISTINCT stamp arrays."""

    def __init__(self, dim: int):
        self.stamp_a = np.full(dim, -1, np.int64)
        self.stamp_b = np.full(dim, -1, np.int64)
        self.kept_stamp = np.full(dim, -1, np.int64)
        self.support = np.empty(dim, np.int64)
        self.local = np.empty(dim, np.int64)


def _concat_strings(strings) -> tuple[bytes, np.ndarray]:
    """Concatenated utf-8 bytes + (n+1,) offsets for a string sequence."""
    encoded = [s.encode() for s in strings]
    offs = np.zeros(len(encoded) + 1, np.int64)
    np.cumsum([len(b) for b in encoded], out=offs[1:])
    return b"".join(encoded), offs


def write_re_models(path: str, model_ids, model_class: str,
                    rec_indptr: np.ndarray, name_ids: np.ndarray,
                    values: np.ndarray, variances: Optional[np.ndarray],
                    names, terms, block_records: int = 4096) -> bool:
    """Write per-entity ``BayesianLinearModelAvro`` records via the native
    writer (``native/avro_writer.cc::photon_write_re_models``).

    ``rec_indptr`` gives each record's [lo, hi) span in the flat
    ``name_ids``/``values``/``variances`` columns; ``name_ids`` index the
    ``names``/``terms`` tables. ``model_class`` is written as both
    modelClass and lossFunction (matching the Python path). Returns False
    when the native library is unavailable; the caller falls back to
    :func:`photon_ml_tpu.io.avro.write_avro_file`."""
    lib = _load()
    if lib is None:
        return False
    from photon_ml_tpu.io.schemas import BAYESIAN_LINEAR_MODEL_AVRO

    schema = json.dumps(BAYESIAN_LINEAR_MODEL_AVRO).encode()
    id_bytes, id_offs = _concat_strings(model_ids)
    name_bytes, name_offs = _concat_strings(names)
    term_bytes, term_offs = _concat_strings(terms)
    rec_indptr = np.ascontiguousarray(rec_indptr, np.int64)
    name_ids = np.ascontiguousarray(name_ids, np.int32)
    values = np.ascontiguousarray(values, np.float64)
    n_models = len(rec_indptr) - 1
    var_ptr = None
    var_arr = None
    if variances is not None:
        var_arr = np.ascontiguousarray(variances, np.float64)
        var_ptr = var_arr.ctypes.data_as(ctypes.c_void_p)
    mc = model_class.encode()
    wrote = lib.photon_write_re_models(
        path.encode(), schema, len(schema), n_models, id_bytes, id_offs,
        mc, len(mc), rec_indptr, name_ids, values, var_ptr,
        name_bytes, name_offs, term_bytes, term_offs, block_records)
    return wrote == n_models


def re_feature_counts(indptr: np.ndarray, cols: np.ndarray,
                      all_active: np.ndarray, ent_starts: np.ndarray,
                      dim: int, max_active_features: Optional[int],
                      scratch: BucketPackScratch) -> Optional[np.ndarray]:
    """Per-entity distinct-feature counts (post-pruning) over entity-grouped
    active rows — pass A of the native bucket packer
    (``native/bucket_pack.cc``). None when the library is unavailable; the
    caller falls back to the numpy formulation. Arrays must be C-contiguous
    with the documented dtypes (ctypes ndpointer enforces this)."""
    lib = _load()
    if lib is None:
        return None
    n_entities = len(ent_starts) - 1
    out = np.empty(n_entities, np.int64)
    lib.photon_re_feature_counts(
        indptr, cols, all_active, ent_starts, n_entities, int(dim),
        -1 if max_active_features is None else int(max_active_features),
        scratch.stamp_a, scratch.support, out)
    return out


def re_bucket_fill(indptr, cols, vals, all_active, ent_starts,
                   labels_all, weights_all, sel, S: int, D: int,
                   dim: int, max_active_features: Optional[int],
                   scratch: BucketPackScratch):
    """Pack one bucket's (E, S, D) tensors — pass B of the native bucket
    packer. Returns ``(x, labels, weights, sample_idx, feature_index)``
    matching the numpy path exactly, or None when unavailable."""
    lib = _load()
    if lib is None:
        return None
    sel = np.ascontiguousarray(sel, np.int64)
    e = len(sel)
    x = np.zeros((e, S, D), np.float32)
    labels = np.zeros((e, S), np.float32)
    weights = np.zeros((e, S), np.float32)
    sample_idx = np.full((e, S), -1, np.int64)
    feature_index = np.full((e, D), -1, np.int64)
    lib.photon_re_bucket_fill(
        indptr, cols, vals, all_active, ent_starts, labels_all, weights_all,
        sel, e, int(S), int(D), int(dim),
        -1 if max_active_features is None else int(max_active_features),
        scratch.stamp_b, scratch.support, scratch.kept_stamp, scratch.local,
        x, labels, weights, sample_idx, feature_index)
    return x, labels, weights, sample_idx, feature_index


def re_bucket_indices(indptr, cols, all_active, ent_starts, sel,
                      S: int, D: int, max_active_features: Optional[int],
                      scratch: BucketPackScratch):
    """Pack one bucket's index maps ONLY (pass B'): the compact device path
    reconstructs the (E, S, D) tensors by on-device gathers, so the host
    fill is skipped. Returns ``(sample_idx, feature_index)`` identical to
    :func:`re_bucket_fill`'s, or None when unavailable."""
    lib = _load()
    if lib is None:
        return None
    sel = np.ascontiguousarray(sel, np.int64)
    e = len(sel)
    sample_idx = np.full((e, S), -1, np.int64)
    feature_index = np.full((e, D), -1, np.int64)
    lib.photon_re_bucket_indices(
        indptr, cols, all_active, ent_starts, sel, e, int(S), int(D),
        -1 if max_active_features is None else int(max_active_features),
        scratch.stamp_b, scratch.support, sample_idx, feature_index)
    return sample_idx, feature_index


def shard_split(feat_indptr, feat_key_id, feat_val, key_to_col,
                intercept_col: int):
    """CSR split of one decoded file's flat feature stream into one shard
    (``avro_reader.cc::photon_shard_split_{count,fill}``): record order
    preserved, values cast to f32 in-pass, optional per-record intercept
    entry appended. Replaces the numpy remap/mask/gather assembly (~1 s on
    a 1M-record file). Returns ``(indptr, cols, vals)`` or None when the
    library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    n = len(feat_indptr) - 1
    counts = np.empty(n, np.int64)
    lib.photon_shard_split_count(feat_indptr, feat_key_id, n, key_to_col,
                                 intercept_col, counts)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    nnz = int(indptr[-1]) if n else 0
    cols = np.empty(nnz, np.int32)
    vals = np.empty(nnz, np.float32)
    lib.photon_shard_split_fill(feat_indptr, feat_key_id, feat_val, n,
                                key_to_col, intercept_col, indptr, cols,
                                vals)
    return indptr, cols, vals


def counting_sort(ids: np.ndarray) -> Optional[np.ndarray]:
    """Stable group-order of dense non-negative int ids — the native O(n)
    counting sort (``bucket_pack.cc::photon_counting_sort``). Returns the
    same permutation as ``np.argsort(ids, kind="stable")``; None when the
    library is unavailable (caller falls back).

    Counting sort allocates O(max(ids)) counter arrays — correct only for
    PRE-INDEXED dense ids. A sparse column (raw 64-bit hashes, say) would
    silently allocate gigabytes, so large-and-sparse inputs take the
    comparison-sort fallback here instead of gambling on the caller."""
    ids = np.ascontiguousarray(ids, np.int64)
    if ids.size == 0:
        return np.zeros(0, np.int64)
    if int(ids.max()) > 4 * ids.size:
        return np.argsort(ids, kind="stable")
    lib = _load()
    if lib is None:
        return None
    cnt = np.bincount(ids)
    cursors = np.zeros(len(cnt), np.int64)
    np.cumsum(cnt[:-1], out=cursors[1:])
    order = np.empty(ids.size, np.int64)
    lib.photon_counting_sort(ids, ids.size, cursors, order)
    return order
