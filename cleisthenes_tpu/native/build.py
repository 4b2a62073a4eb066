"""On-demand compilation + ctypes loading of the native kernels.

Each kernel source compiles to a shared library cached by source hash
(rebuilds on change, races benignly via atomic rename); loading is
attempted once per process and failure degrades to the pure-python /
XLA paths, never to an exception — but the reason is logged once,
because the degradation moves the host floors (ModEngine falls to
HOST_FLOOR_NO_NATIVE) and the CPU reference to python pow().
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
from pathlib import Path
from typing import Callable, Dict, Optional

_DIR = Path(__file__).parent
_LIBS: Dict[str, Optional[ctypes.CDLL]] = {}
_LOG = logging.getLogger("cleisthenes_tpu.native")


def source_path(name: str) -> Path:
    """The committed C++ source of kernel ``name``."""
    return _DIR / f"{name}.cpp"


def _cache_path(src: Path) -> Path:
    """Library path keyed by source hash (rebuilds on source change)."""
    digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    name = f"_{src.stem}-{digest}.so"
    if os.access(src.parent, os.W_OK):
        return src.parent / name
    cache_dir = Path(tempfile.gettempdir()) / "cleisthenes_tpu_native"
    cache_dir.mkdir(parents=True, exist_ok=True)
    return cache_dir / name


def _compile(src: Path, out: Path) -> None:
    # per-process tmp name: concurrent first-time builders must not
    # interleave writes before the atomic rename
    tmp = out.with_suffix(f".tmp{os.getpid()}.so")
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC",
        "-funroll-loops", "-pthread", str(src), "-o", str(tmp),
    ]
    subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    tmp.replace(out)  # atomic: concurrent builders race benignly


def _load(name: str, configure: Callable[[ctypes.CDLL], None]):
    """Compile-if-needed + load + configure + selftest, once per
    process; returns None forever after the first failure, whose
    reason is logged that one time."""
    if name in _LIBS:
        return _LIBS[name]
    try:
        src = source_path(name)
        path = _cache_path(src)
        if not path.exists():
            _compile(src, path)
        lib = ctypes.CDLL(str(path))
        configure(lib)
        _LIBS[name] = lib
    except (
        OSError,  # no g++, unwritable cache dir, dlopen failure
        subprocess.SubprocessError,  # compile error or timeout
        AttributeError,  # library lacks an expected symbol
        RuntimeError,  # selftest mismatch
    ) as exc:
        detail = getattr(exc, "stderr", None) or exc
        if isinstance(detail, bytes):
            detail = detail.decode("utf-8", "replace")
        _LOG.warning(
            "native kernel %r unavailable, using the python/XLA path "
            "(%s: %s)",
            name,
            type(exc).__name__,
            str(detail).strip()[-400:],
        )
        _LIBS[name] = None
    return _LIBS[name]


def _configure_gf256(lib: ctypes.CDLL) -> None:
    lib.gf256_matmul.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
    ]
    lib.gf256_matmul_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
    ]
    lib.gf256_selftest.restype = ctypes.c_int
    rc = lib.gf256_selftest()
    if rc != 0:
        raise RuntimeError(f"gf256 selftest failed: {rc}")


def _configure_modpow(lib: ctypes.CDLL) -> None:
    lib.modpow256_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int,
    ]
    lib.dualpow256_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int,
    ]
    lib.modreduce256_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int,
    ]
    lib.muladdmod256_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
    ]
    lib.modpow256_selftest.restype = ctypes.c_int
    rc = lib.modpow256_selftest()
    if rc != 0:
        raise RuntimeError(f"modpow256 selftest failed: {rc}")


def _configure_sha256(lib: ctypes.CDLL) -> None:
    lib.sha256_rows.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
    ]
    lib.sha256_rows.restype = ctypes.c_int
    lib.sha256_rows_fixed.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_int,
    ]
    lib.sha256_rows_fixed.restype = ctypes.c_int
    lib.sha256_path.argtypes = []
    lib.sha256_path.restype = ctypes.c_int
    lib.sha256_resolve.argtypes = [ctypes.c_int]
    lib.sha256_resolve.restype = ctypes.c_int
    lib.sha256_thread_floor_blocks.argtypes = []
    lib.sha256_thread_floor_blocks.restype = ctypes.c_int64
    lib.sha256_selftest.argtypes = []
    lib.sha256_selftest.restype = ctypes.c_int
    rc = lib.sha256_selftest()
    if rc != 0:
        raise RuntimeError(f"sha256rows selftest failed: {rc}")


def _configure_modpow_wide(lib: ctypes.CDLL) -> None:
    rows = [ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int]
    lib.modpow_wide_batch.argtypes = [ctypes.c_void_p] * 2 + rows
    lib.modpow_wide_batch.restype = ctypes.c_int
    lib.dualpow_wide_batch.argtypes = [ctypes.c_void_p] * 4 + rows
    lib.dualpow_wide_batch.restype = ctypes.c_int
    lib.kronecker_wide_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.kronecker_wide_batch.restype = ctypes.c_int
    lib.modpow_wide_available.argtypes = []
    lib.modpow_wide_available.restype = ctypes.c_int
    lib.modpow_wide_selftest.argtypes = []
    lib.modpow_wide_selftest.restype = ctypes.c_int
    rc = lib.modpow_wide_selftest()
    if rc != 0:
        raise RuntimeError(f"modpow_wide selftest failed: {rc}")
    if not lib.modpow_wide_available():
        raise RuntimeError("modpow_wide: libcrypto's BN calls not found")


def load_sha256() -> Optional[ctypes.CDLL]:
    """The batched SHA-256 library, or None (no toolchain)."""
    return _load("sha256rows", _configure_sha256)


def load_gf256() -> Optional[ctypes.CDLL]:
    """The GF(2^8) RS kernel library, or None (no toolchain)."""
    return _load("gf256", _configure_gf256)


def load_modpow() -> Optional[ctypes.CDLL]:
    """The 256-bit Montgomery modexp library, or None."""
    return _load("modpow256", _configure_modpow)


def load_modpow_wide() -> Optional[ctypes.CDLL]:
    """The OpenSSL-backed modexp library of every width, or None (no
    toolchain, or no libcrypto)."""
    return _load("modpow_wide", _configure_modpow_wide)


def native_available() -> bool:
    return load_gf256() is not None


__all__ = [
    "load_gf256",
    "load_modpow",
    "load_modpow_wide",
    "load_sha256",
    "native_available",
]
