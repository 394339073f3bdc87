"""Native host-ops loader (C++ via ctypes).

The shared object is generated code: it is built from the sources
committed next to this file into `libhostops-<source hash>.so`
(`python -m transferia_tpu.native.build`, or on first use), so a library
on disk always matches the sources beside it — a copied tree has
arbitrary mtimes, a content hash does not.  A build or load failure
raises; `lib()` returns None only when TRANSFERIA_TPU_NO_NATIVE=1 asks
for the numpy paths.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import pathlib
import threading
from typing import Optional

from transferia_tpu.runtime import knobs

logger = logging.getLogger(__name__)

_DIR = pathlib.Path(__file__).parent
# translation units, then the parts they #include (hashed, not compiled)
_SOURCES = ("hostops.cpp", "parquetdec.cpp")
_INCLUDES = ("parquetdec_ba.inc",)
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_failure: Optional["NativeBuildError"] = None  # the first, re-raised


class NativeBuildError(RuntimeError):
    """The host-ops library could not be built or loaded."""


# An uninitialised bytes object for a native writer to fill: the result
# itself (a numpy buffer and tobytes() would copy it once more, holding the
# GIL).  A prototype of our own: ctypes.pythonapi's attribute is the
# process's.
new_bytes = ctypes.PYFUNCTYPE(
    ctypes.py_object, ctypes.c_void_p, ctypes.c_ssize_t,
)(("PyBytes_FromStringAndSize", ctypes.pythonapi))


def so_path() -> pathlib.Path:
    """Where the library for the sources on disk lives (may not exist
    yet): the name carries a hash of every source file's bytes."""
    h = hashlib.sha256()
    for name in _SOURCES + _INCLUDES:
        h.update(name.encode())
        h.update((_DIR / name).read_bytes())
    return _DIR / f"libhostops-{h.hexdigest()[:16]}.so"


def _bind(cdll: ctypes.CDLL) -> ctypes.CDLL:
    import numpy.ctypeslib as npc
    import numpy as np

    u8 = npc.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    i32 = npc.ndpointer(np.int32, flags="C_CONTIGUOUS")
    i64 = npc.ndpointer(np.int64, flags="C_CONTIGUOUS")
    u32 = npc.ndpointer(np.uint32, flags="C_CONTIGUOUS")
    u64 = npc.ndpointer(np.uint64, flags="C_CONTIGUOUS")
    # RowBinary writer: per-column widths, then buffer addresses as u64
    cdll.rowbinary_size.argtypes = [
        ctypes.c_int64, ctypes.c_int32, i32, u64, u64, u8,
    ]
    cdll.rowbinary_size.restype = ctypes.c_int64
    cdll.rowbinary_write.argtypes = [
        ctypes.c_int64, ctypes.c_int32, i32, u64, u64, u64, u8,
        ctypes.c_char_p,  # the bytes object the rows are written into
    ]
    cdll.rowbinary_write.restype = ctypes.c_int64
    # Debezium envelope renderer: per-column kinds and buffer addresses,
    # then a message's slots and the offsets of its constant pieces
    cdll.debezium_render_size.argtypes = [
        ctypes.c_int64, i32, u64, u64, u64, ctypes.c_int32, i32, i64, i64,
    ]
    cdll.debezium_render_size.restype = ctypes.c_int64
    cdll.debezium_render_write.argtypes = [
        ctypes.c_int64, ctypes.c_int64, i32, u64, u64, u64, ctypes.c_int32,
        i32, ctypes.c_char_p, i64,
        ctypes.c_char_p,  # the bytes object the messages are written into
    ]
    cdll.debezium_render_write.restype = ctypes.c_int64
    cdll.gather_varwidth.argtypes = [u8, i32, i64, ctypes.c_int64, u8, i32]
    cdll.gather_varwidth.restype = ctypes.c_int64
    cdll.gather_var_offsets.argtypes = [i32, i64, ctypes.c_int64, i32]
    cdll.gather_var_offsets.restype = ctypes.c_int64
    cdll.gather_var_bytes.argtypes = [
        u8, i32, i64, ctypes.c_int64, i32, u8,
    ]
    cdll.gather_var_bytes.restype = None
    cdll.gather_fixed.argtypes = [
        u8, i64, ctypes.c_int64, ctypes.c_int32, u8,
    ]
    cdll.gather_fixed.restype = None
    cdll.pack_sha_blocks.argtypes = [
        u8, i32, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, u8, i32,
    ]
    cdll.pack_sha_blocks.restype = None
    cdll.hmac_sha256_hex.argtypes = [
        u8, i32, ctypes.c_int64, u32, u32, ctypes.c_void_p, u8,
    ]
    cdll.hmac_sha256_hex.restype = None
    cdll.sha256_block_state.argtypes = [u8, u32]
    cdll.sha256_block_state.restype = None
    cdll.polyhash_varcol.argtypes = [
        u8, i32, ctypes.c_int64, u32, u32, u32, u32,
    ]
    cdll.polyhash_varcol.restype = None
    # fused fingerprint lane kernels
    cdll.rowhash_mix_fixed.argtypes = [
        u32, u32, ctypes.c_int64, ctypes.c_uint32, ctypes.c_uint32,
        u32, u32,
    ]
    cdll.rowhash_mix_fixed.restype = None
    cdll.rowhash_mix_var.argtypes = [
        u32, u32, ctypes.c_int64, ctypes.c_uint32, ctypes.c_uint32,
        u32, u32,
    ]
    cdll.rowhash_mix_var.restype = None
    cdll.rowhash_dict_lanes.argtypes = [
        u32, u32, i32, ctypes.c_int64, ctypes.c_uint32,
        ctypes.c_uint32, u32, u32,
    ]
    cdll.rowhash_dict_lanes.restype = None
    cdll.rowhash_accum.argtypes = [
        u32, u32, ctypes.c_int64, u32, u32,
    ]
    cdll.rowhash_accum.restype = None
    cdll.crc32c_batch.argtypes = [u8, i64, ctypes.c_int64, u32]
    cdll.crc32c_batch.restype = None
    cdll.kafka_scan_records.argtypes = [
        u8, ctypes.c_int64, i64, ctypes.c_int64,
    ]
    cdll.kafka_scan_records.restype = ctypes.c_int64
    cdll.avro_decode_flat.argtypes = [
        u8, i64, ctypes.c_int64, u8, u8, u8, ctypes.c_int64, i64,
    ]
    cdll.avro_decode_flat.restype = ctypes.c_int64
    cdll.crc32c_buf.argtypes = [u8, ctypes.c_int64, ctypes.c_uint32]
    cdll.crc32c_buf.restype = ctypes.c_uint32
    cdll.kafka_encode_records.argtypes = [
        u8, i64, ctypes.c_void_p, u8, i64, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int64, u8, ctypes.c_int64,
    ]
    cdll.kafka_encode_records.restype = ctypes.c_int64
    # the gather framer: keys, their offsets and the null flags may be
    # absent (None); out None asks for the size
    cdll.kafka_frame_rows.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_char_p, i64, ctypes.c_void_p,
        i64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_char_p,  # the bytes object the records are written into
        ctypes.c_int64,
    ]
    cdll.kafka_frame_rows.restype = ctypes.c_int64
    # parquet decoder (parquetdec.cpp)
    cdll.pq_decode_fixed.argtypes = [
        u8, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
        ctypes.c_void_p,
    ]
    cdll.pq_decode_fixed.restype = ctypes.c_int64
    cdll.pq_decode_bytearray.argtypes = [
        u8, ctypes.c_int64, ctypes.c_int32, ctypes.c_int64,
        ctypes.c_int32, u8, ctypes.c_int64, i32, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64),
    ]
    cdll.pq_decode_bytearray.restype = ctypes.c_int64
    cdll.pq_decode_rowgroup.argtypes = [
        u8, ctypes.c_int64, i64, ctypes.c_int64,
    ]
    cdll.pq_decode_rowgroup.restype = ctypes.c_int64
    cdll.pq_codec_supported.argtypes = [ctypes.c_int32]
    cdll.pq_codec_supported.restype = ctypes.c_int32
    cdll.pg_copy_unframe.argtypes = [u8, ctypes.c_int64, u8, i64]
    cdll.pg_copy_unframe.restype = ctypes.c_int64
    return cdll


def build(force: bool = False) -> pathlib.Path:
    """Compile the library for the sources on disk (a no-op when it is
    already there and `force` is off); returns its path.  Raises
    NativeBuildError when there is no compiler or the compile fails."""
    import shutil
    import subprocess

    so = so_path()
    if so.exists() and not force:
        return so
    cxx = shutil.which("g++") or shutil.which("clang++")
    if cxx is None:
        raise NativeBuildError(
            "no C++ compiler (g++/clang++) to build the host-ops "
            "library; set TRANSFERIA_TPU_NO_NATIVE=1 to run the numpy "
            "paths on purpose")
    # compile beside the target, then rename: concurrent builders (part
    # threads are serialized by lib()'s lock, worker PROCESSES are not)
    # never see a half-written library
    tmp = so.with_name(f"{so.name}.{os.getpid()}.tmp")
    # NOTE: -march=native was tried and measured SLOWER on the v5e bench
    # box (AVX-512 codegen/downclocking on the byte-wise hot loops);
    # plain -O3 with the runtime SSE4.2/SHA-NI dispatch stays the build
    try:
        subprocess.run(
            [cxx, "-O3", "-shared", "-fPIC", "-o", str(tmp)]
            + [str(_DIR / s) for s in _SOURCES] + ["-ldl"],
            check=True, capture_output=True, timeout=300,
        )
        os.replace(tmp, so)
    except subprocess.CalledProcessError as e:
        raise NativeBuildError(
            f"host-ops build failed (rc={e.returncode}): "
            f"{e.stderr.decode(errors='replace')[-2000:]}") from e
    except subprocess.TimeoutExpired as e:
        raise NativeBuildError(f"host-ops build timed out: {e}") from e
    finally:
        tmp.unlink(missing_ok=True)
    # libraries of earlier source versions are dead weight
    for old in _DIR.glob("libhostops*.so"):
        if old != so:
            old.unlink(missing_ok=True)
    return so


class _ProfiledLib:
    """CDLL proxy: every exported-function call publishes a "this
    thread is inside native symbol S" marker for the sampling profiler
    (stats/profiler.py native_call) — without it, samples landing in
    the C++ kernels attribute to the CALLER's Python line and profiles
    inflate lines like mask.py's hmac call with pure C++ time.

    Everything else forwards to the wrapped CDLL: `hasattr` probes for
    optional symbols and non-callable attributes behave identically.
    The wrapper costs two dict operations per native CALL (calls are
    per-batch/per-column, never per-row)."""

    __slots__ = ("_cdll", "_wrapped")

    def __init__(self, cdll: ctypes.CDLL):
        self._cdll = cdll
        self._wrapped: dict = {}

    def __getattr__(self, name):
        w = self._wrapped.get(name)
        if w is not None:
            return w
        fn = getattr(self._cdll, name)  # AttributeError propagates
        if not callable(fn):
            return fn
        from transferia_tpu.stats.profiler import native_call

        def call(*args, _fn=fn, _name=name):
            with native_call(_name):
                return _fn(*args)

        self._wrapped[name] = call
        return call


def lib() -> Optional[ctypes.CDLL]:
    """The loaded library (built first when the one for these sources is
    missing).  None only under TRANSFERIA_TPU_NO_NATIVE=1; a failed
    build or load raises NativeBuildError — on every call, so no caller
    drops to its numpy path without a word."""
    global _lib, _failure
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None:
            return _lib
        if knobs.env_str("TRANSFERIA_TPU_NO_NATIVE", "") == "1":
            return None
        if _failure is not None:  # one compile attempt per process
            raise _failure
        try:
            so = build()
            _lib = _ProfiledLib(_bind(ctypes.CDLL(str(so))))
        except NativeBuildError as e:
            _failure = e
            raise
        except (OSError, AttributeError) as e:
            _failure = NativeBuildError(
                f"host-ops library failed to load: {e}")
            raise _failure from e
    return _lib
