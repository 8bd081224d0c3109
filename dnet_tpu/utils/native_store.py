"""ctypes wrapper over the native host store (native/hoststore.cpp).

The disk->host-DRAM half of weight streaming, native like the reference's
(src/dnet/utils/layer_manager.py drives libc madvise; its repack/mmap IO is
the performance-critical native path).  Provides:

- NativeSafetensors: one mmap per .safetensors file with a self-parsed
  header (8-byte LE length + JSON index — the same structure the reference
  parses at src/dnet/utils/model.py:388-417), zero-copy numpy views per
  tensor, and per-tensor-span madvise prefetch/release.
- graceful degradation: if g++ or the platform is unavailable the importers
  fall back to the pure-Python safetensors path (`available()` gates use).

Page-cache streaming protocol (mirrors layer_manager modes):
  prefetch(names, sync=False)  -> WILLNEED + background page-touch, so the
                                  next window's disk reads overlap compute
  release(names)               -> DONTNEED evicted windows' pages
"""

from __future__ import annotations

import ctypes
import json
import struct
import subprocess
import threading
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from dnet_tpu.utils.logger import get_logger

log = get_logger()

_NATIVE_DIR = Path(__file__).resolve().parents[2] / "native"
_SRC = _NATIVE_DIR / "hoststore.cpp"
_LIB = _NATIVE_DIR / "libdnethost.so"
_build_lock = threading.Lock()
_lib = None
_lib_failed = False


def ensure_built(force: bool = False) -> Path:
    """Compile the host-store library if missing/stale (g++ is baked in)."""
    with _build_lock:
        if (
            not force
            and _LIB.is_file()
            and _LIB.stat().st_mtime >= _SRC.stat().st_mtime
        ):
            return _LIB
        cmd = [
            "g++", "-O2", "-shared", "-fPIC", "-std=c++17",
            "-o", str(_LIB), str(_SRC), "-lpthread",
        ]
        log.info("building native host store: %s", " ".join(cmd))
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"native host store build failed:\n{proc.stderr.strip()}"
            )
        return _LIB


def _load():
    lib = ctypes.CDLL(str(ensure_built()))
    lib.hs_open.argtypes = [ctypes.c_char_p]
    lib.hs_open.restype = ctypes.c_int
    lib.hs_close.argtypes = [ctypes.c_int]
    lib.hs_size.argtypes = [ctypes.c_int]
    lib.hs_size.restype = ctypes.c_uint64
    lib.hs_addr.argtypes = [ctypes.c_int]
    lib.hs_addr.restype = ctypes.c_void_p
    for f in (lib.hs_prefetch, lib.hs_prefetch_async, lib.hs_release):
        f.argtypes = [ctypes.c_int, ctypes.c_uint64, ctypes.c_uint64]
        f.restype = ctypes.c_int
    lib.hs_read.argtypes = [
        ctypes.c_int, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_void_p,
    ]
    lib.hs_read.restype = ctypes.c_int
    lib.hs_pending.restype = ctypes.c_int
    return lib


def _get_lib():
    global _lib, _lib_failed
    if _lib is None and not _lib_failed:
        try:
            _lib = _load()
            log.info("native host store serves checkpoint IO: %s", _LIB)
        except Exception as exc:  # missing toolchain / unsupported platform
            _lib_failed = True
            log.warning("native host store unavailable, using python IO: %s", exc)
    return _lib


def available() -> bool:
    return _get_lib() is not None


_ST_DTYPES = {
    "F64": np.dtype(np.float64), "F32": np.dtype(np.float32),
    "F16": np.dtype(np.float16), "I64": np.dtype(np.int64),
    "I32": np.dtype(np.int32), "I16": np.dtype(np.int16),
    "I8": np.dtype(np.int8), "U8": np.dtype(np.uint8),
    "U16": np.dtype(np.uint16), "U32": np.dtype(np.uint32),
    "U64": np.dtype(np.uint64), "BOOL": np.dtype(np.bool_),
}


def _np_dtype(st_dtype: str) -> np.dtype:
    if st_dtype == "BF16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    try:
        return _ST_DTYPES[st_dtype]
    except KeyError:
        raise ValueError(f"unsupported safetensors dtype {st_dtype!r}") from None


class NativeSafetensors:
    """One safetensors file: native mmap + parsed header + zero-copy views."""

    def __init__(self, path: str | Path):
        lib = _get_lib()
        if lib is None:
            raise RuntimeError("native host store unavailable")
        self._lib = lib
        self.path = Path(path)
        self._h = lib.hs_open(str(self.path).encode())
        if self._h < 0:
            raise OSError(f"hs_open failed for {self.path}")
        self.size = int(lib.hs_size(self._h))
        # header: u64 LE json length, then the json index; tensor offsets
        # are relative to the data section that follows the header
        hdr_len_buf = (ctypes.c_char * 8)()
        if lib.hs_read(self._h, 0, 8, hdr_len_buf) != 0:
            raise OSError(f"short read on {self.path}")
        (hdr_len,) = struct.unpack("<Q", hdr_len_buf.raw)
        if 8 + hdr_len > self.size:
            raise ValueError(f"corrupt safetensors header in {self.path}")
        hdr_buf = ctypes.create_string_buffer(hdr_len)
        lib.hs_read(self._h, 8, hdr_len, hdr_buf)
        header = json.loads(hdr_buf.raw.decode("utf-8"))
        header.pop("__metadata__", None)
        self._data0 = 8 + hdr_len
        # name -> (abs_offset, nbytes, dtype, shape)
        self.tensors: Dict[str, Tuple[int, int, np.dtype, Tuple[int, ...]]] = {}
        for name, info in header.items():
            a, b = info["data_offsets"]
            self.tensors[name] = (
                self._data0 + a,
                b - a,
                _np_dtype(info["dtype"]),
                tuple(info["shape"]),
            )
        base = lib.hs_addr(self._h)
        buf = (ctypes.c_char * self.size).from_address(base)
        self._view = np.frombuffer(buf, dtype=np.uint8)
        self._view.flags.writeable = False

    def keys(self) -> List[str]:
        return list(self.tensors)

    def tensor(self, name: str) -> np.ndarray:
        """Zero-copy read-only view into the mapped file."""
        off, nbytes, dtype, shape = self.tensors[name]
        flat = self._view[off : off + nbytes]
        return flat.view(dtype).reshape(shape)

    def span(self, name: str) -> Tuple[int, int]:
        off, nbytes, _, _ = self.tensors[name]
        return off, nbytes

    def _coalesced(self, names: Iterable[str]) -> List[Tuple[int, int]]:
        """Merge tensor spans into maximal runs (the reference coalesces
        per-file spans before madvise, layer_manager.py:160-186)."""
        spans = sorted(self.span(n) for n in names)
        out: List[Tuple[int, int]] = []
        for off, nbytes in spans:
            if out and off <= out[-1][0] + out[-1][1] + 4096:
                prev_off, prev_len = out[-1]
                out[-1] = (prev_off, max(prev_len, off + nbytes - prev_off))
            else:
                out.append((off, nbytes))
        return out

    def prefetch(self, names: Iterable[str], sync: bool = False) -> None:
        fn = self._lib.hs_prefetch if sync else self._lib.hs_prefetch_async
        for off, nbytes in self._coalesced(names):
            fn(self._h, off, nbytes)

    def release(self, names: Iterable[str]) -> None:
        for off, nbytes in self._coalesced(names):
            self._lib.hs_release(self._h, off, nbytes)

    def pending(self) -> int:
        return int(self._lib.hs_pending())

    def close(self) -> None:
        if self._h >= 0:
            # the numpy view aliases the mapping; drop it before munmap
            self._view = None
            self._lib.hs_close(self._h)
            self._h = -1

    def __del__(self):  # best-effort; explicit close preferred
        try:
            self.close()
        except Exception:
            pass
