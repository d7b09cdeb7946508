"""ctypes bindings for the C++ piece codec (native/src/*.cpp).

The reference is pure Python (SURVEY executive summary: "zero
C++/Rust/CUDA/native components"); this framework's runtime keeps a
native data plane where it pays: content-hashing model-weight pieces.
`hashlib` releases the GIL per call but Python still iterates pieces
serially — the C++ codec hashes all pieces of a checkpoint across cores
in one call.

The shared object is a build product (git-ignored): the first use in a
process runs one quiet `make`, which builds it when it is missing OR
older than its sources — a binary left over from an earlier checkout is
never loaded in place of the code that is there. If the build fails and
no binary exists, every function falls back to hashlib so the framework
never hard-requires the native build.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from pathlib import Path

logger = logging.getLogger("bee2bee_tpu.native")

_SO_PATH = Path(__file__).parent / "_native" / "libbee2bee.so"
_NATIVE_DIR = Path(__file__).parent.parent / "native"
_lib = None
_load_attempted = False


def _try_build() -> bool:
    """`make` the codec (a no-op when it is newer than its sources)."""
    if not (_NATIVE_DIR / "Makefile").exists():
        return False
    try:
        subprocess.run(
            ["make", "-C", str(_NATIVE_DIR)],
            capture_output=True,
            timeout=120,
            check=True,
        )
        return _SO_PATH.exists()
    except (subprocess.SubprocessError, OSError) as e:
        logger.debug("native build failed: %s", e)
        return False


def _load():
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    if os.environ.get("BEE2BEE_DISABLE_NATIVE", "").lower() in ("1", "true", "yes"):
        return None
    if not _try_build() and not _SO_PATH.exists():
        logger.info("native codec unavailable; using hashlib fallback")
        return None
    try:
        lib = ctypes.CDLL(str(_SO_PATH))
        lib.b2b_version.restype = ctypes.c_char_p
        lib.b2b_sha256.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_void_p
        ]
        lib.b2b_hash_many.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint64,
            ctypes.c_void_p,
            ctypes.c_int,
        ]
        lib.b2b_hash_chunks.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.c_void_p, ctypes.c_int,
        ]
        lib.b2b_hash_chunks.restype = ctypes.c_uint64
        lib.b2b_verify_many.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint64,
            ctypes.c_void_p,
            ctypes.c_int,
        ]
        lib.b2b_verify_many.restype = ctypes.c_int64
        lib.b2b_sha256_accelerated.restype = ctypes.c_int
        _lib = lib
    except (OSError, AttributeError) as e:
        # AttributeError = a prebuilt .so missing a newer symbol that
        # could not be rebuilt (no make/g++ here); degrade to hashlib
        # rather than crashing every entry point
        logger.warning(
            "failed to load native codec (%s); falling back to hashlib — "
            "run `make -C native clean all` to rebuild", e
        )
        _lib = None
    return _lib


def available() -> bool:
    return _load() is not None


def version() -> str | None:
    lib = _load()
    return lib.b2b_version().decode() if lib else None


def accelerated() -> bool:
    """True when the codec resolved libcrypto's SHA256 (SHA-NI/AVX2) —
    the fast path that makes multi-GB checkpoint hashing ~10x quicker
    than the portable fallback."""
    lib = _load()
    return bool(lib and lib.b2b_sha256_accelerated())


def _ptr_arrays(blobs: list[bytes]):
    n = len(blobs)
    datas = (ctypes.c_char_p * n)(*blobs)
    lens = (ctypes.c_uint64 * n)(*[len(b) for b in blobs])
    return datas, lens


def sha256_hex(data: bytes) -> str:
    lib = _load()
    if lib is None:
        return hashlib.sha256(data).hexdigest()
    out = (ctypes.c_uint8 * 32)()
    lib.b2b_sha256(data, len(data), out)
    return bytes(out).hex()


def hash_many(blobs: list[bytes], n_threads: int = 0) -> list[str]:
    """Parallel sha256 of many buffers; [] -> []."""
    if not blobs:
        return []
    lib = _load()
    if lib is None:
        return [hashlib.sha256(b).hexdigest() for b in blobs]
    datas, lens = _ptr_arrays(blobs)
    out = (ctypes.c_uint8 * (32 * len(blobs)))()
    lib.b2b_hash_many(datas, lens, len(blobs), out, n_threads)
    raw = bytes(out)
    return [raw[i * 32 : (i + 1) * 32].hex() for i in range(len(blobs))]


def hash_chunks(data: bytes, piece_size: int, n_threads: int = 0) -> list[str]:
    """Hash consecutive piece_size chunks of one buffer without splitting
    it into Python objects first."""
    if not data:
        return []
    lib = _load()
    if lib is None:
        return [
            hashlib.sha256(data[i : i + piece_size]).hexdigest()
            for i in range(0, len(data), piece_size)
        ]
    n = -(-len(data) // piece_size)
    out = (ctypes.c_uint8 * (32 * n))()
    got = lib.b2b_hash_chunks(data, len(data), piece_size, out, n_threads)
    raw = bytes(out)
    return [raw[i * 32 : (i + 1) * 32].hex() for i in range(got)]


def verify_many(blobs: list[bytes], hex_digests: list[str], n_threads: int = 0) -> int:
    """Return -1 if every blob matches its digest, else the lowest
    mismatching index."""
    if len(blobs) != len(hex_digests):
        raise ValueError(f"count mismatch: {len(blobs)} blobs, {len(hex_digests)} digests")
    if not blobs:
        return -1
    lib = _load()
    if lib is None:
        for i, (b, h) in enumerate(zip(blobs, hex_digests)):
            if hashlib.sha256(b).hexdigest() != h:
                return i
        return -1
    datas, lens = _ptr_arrays(blobs)
    expected = bytes.fromhex("".join(hex_digests))
    return lib.b2b_verify_many(datas, lens, len(blobs), expected, n_threads)
