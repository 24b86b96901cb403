"""ctypes bindings + on-demand build for the C++ token loader (csrc/).

The reference's data path gets its native speed from torch's C++ DataLoader
workers; this is our equivalent: ``csrc/token_loader.cpp`` mmaps a flat int32
token file and assembles shuffled batches on C++ threads (no GIL), with a
bounded prefetch queue. The Python side stays a thin iterator.

The shared library is compiled with g++ on first use and cached next to
the source (git ignores it; no commit carries a binary). Asking for the
native loader where it cannot be built is an error, not a quiet switch to
the pure-Python loader (``data/loader.py``), whose shuffle order differs.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from pathlib import Path
from typing import Iterator, Optional

import numpy as np

_CSRC = Path(__file__).parent.parent / "csrc"
_LIB: Optional[ctypes.CDLL] = None


def _build_library() -> Path:
    src = _CSRC / "token_loader.cpp"
    out = _CSRC / "libtokenloader.so"
    if out.exists() and out.stat().st_mtime > src.stat().st_mtime:
        return out
    # build beside the target and rename: several processes (test workers)
    # may build at once, and none may load a half-written library
    tmp = out.with_name(f"{out.name}.{os.getpid()}.tmp")
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
           "-o", str(tmp), str(src), "-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
    except (OSError, subprocess.SubprocessError) as e:
        tmp.unlink(missing_ok=True)
        detail = getattr(e, "stderr", b"") or b""
        raise RuntimeError(
            f"native loader build failed ({e}): "
            f"{detail.decode(errors='replace')[-2000:]}") from e
    return out


def get_library() -> ctypes.CDLL:
    """The loaded library, built on first use; raises ``RuntimeError`` when
    it cannot be built or loaded."""
    global _LIB
    if _LIB is not None:
        return _LIB
    path = _build_library()
    try:
        lib = ctypes.CDLL(str(path))
    except OSError as e:
        raise RuntimeError(f"native loader {path} does not load: {e}") from e
    lib.tl_open.restype = ctypes.c_void_p
    lib.tl_open.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
                            ctypes.c_uint64, ctypes.c_int, ctypes.c_int]
    lib.tl_num_batches.restype = ctypes.c_int64
    lib.tl_num_batches.argtypes = [ctypes.c_void_p]
    lib.tl_num_sequences.restype = ctypes.c_int64
    lib.tl_num_sequences.argtypes = [ctypes.c_void_p]
    lib.tl_start_epoch.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64]
    lib.tl_next_batch.restype = ctypes.c_int
    lib.tl_next_batch.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_int32)]
    lib.tl_close.argtypes = [ctypes.c_void_p]
    _LIB = lib
    return lib


def native_available() -> bool:
    """For the tests' skip mark: whether the library builds and loads here."""
    try:
        get_library()
    except RuntimeError:
        return False
    return True


def write_token_file(dataset: np.ndarray, path: str | Path) -> Path:
    """Flat int32 token file — the native loader's (and mmap-friendly) format."""
    path = Path(path)
    np.ascontiguousarray(dataset, dtype=np.int32).tofile(path)
    return path


class NativeTokenLoader:
    """Iterator over [batch, seq_len] int32 batches assembled in C++.

    Deterministic per (seed, epoch); supports resume via ``start_step`` like
    the python loader (the two use different shuffle orders — pick one backend
    per experiment).
    """

    def __init__(self, token_file: str | Path, seq_len: int, batch: int,
                 seed: int = 0, threads: int = 2, prefetch: int = 4):
        lib = self._lib = get_library()
        self._handle = lib.tl_open(str(token_file).encode(), seq_len, batch,
                                   seed, threads, prefetch)
        if not self._handle:
            raise OSError(f"tl_open failed for {token_file}")
        self.seq_len = seq_len
        self.batch = batch

    def __len__(self) -> int:
        return self._lib.tl_num_batches(self._handle)

    @property
    def num_sequences(self) -> int:
        return self._lib.tl_num_sequences(self._handle)

    def epoch_batches(self, epoch: int = 0, start_step: int = 0) -> Iterator[np.ndarray]:
        self._lib.tl_start_epoch(self._handle, epoch, start_step)
        out = np.empty((self.batch, self.seq_len), dtype=np.int32)
        ptr = out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        while self._lib.tl_next_batch(self._handle, ptr):
            yield out.copy()

    def close(self) -> None:
        if self._handle:
            self._lib.tl_close(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
