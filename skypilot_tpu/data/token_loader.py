"""Token data loader: C++ mmap+prefetch core with ctypes binding.

Training input pipeline for the recipe models: binary token shards
(nanoGPT-style .bin of uint16/uint32) → [batch, seq+1] uint32 arrays,
deterministic per (seed, step, rank) so data-parallel hosts draw
disjoint streams. The native core (native/token_loader.cpp) mmaps
shards and prefetches on background threads. The .so is a build
product, not a tracked file: it is built with `make` on first use
(g++ and make are part of the installation); a pure-numpy fallback
keeps everything working where no toolchain exists.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List, Optional, Sequence

import numpy as np

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), 'native')
_SO_PATH = os.path.join(_NATIVE_DIR, 'libtoken_loader.so')

_lib: Optional[ctypes.CDLL] = None
# Why the native core is unusable, when it is (None = usable or not
# yet probed). Tests key skip-with-reason off this instead of failing
# in environments that cannot build or load the .so.
_native_error: Optional[str] = None


def _build_native(force: bool = False) -> bool:
    if not os.path.exists(os.path.join(_NATIVE_DIR, 'token_loader.cpp')):
        return False
    try:
        # Only this target: the Makefile's default also builds the
        # FUSE shims, which need nothing to do with the loader.
        cmd = ['make', '-C', _NATIVE_DIR, 'libtoken_loader.so']
        if force:
            cmd.insert(1, '-B')
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return os.path.exists(_SO_PATH)
    except (subprocess.SubprocessError, OSError):
        return False


def native_unavailable_reason() -> Optional[str]:
    """None when the native loader works here; otherwise why not
    (no toolchain, GLIBC mismatch, ...)."""
    _load_lib()
    return _native_error


def _dlopen_or_rebuild() -> Optional[ctypes.CDLL]:
    """dlopen the .so; on failure (typically a stale binary built
    against another toolchain's GLIBC) force one rebuild and retry."""
    global _native_error
    try:
        return ctypes.CDLL(_SO_PATH)
    except OSError as e:
        first_error = str(e)
    if not _build_native(force=True):
        _native_error = (f'cannot load {_SO_PATH} ({first_error}) and '
                         f'rebuild failed (no usable C++ toolchain?)')
        return None
    try:
        return ctypes.CDLL(_SO_PATH)
    except OSError as e:
        _native_error = f'rebuilt .so still does not load: {e}'
        return None


def _load_lib() -> Optional[ctypes.CDLL]:
    global _lib, _native_error
    if _lib is not None:
        return _lib
    if _native_error is not None:
        return None
    if not os.path.exists(_SO_PATH) and not _build_native():
        _native_error = (f'{_SO_PATH} missing and `make -C '
                         f'{_NATIVE_DIR}` did not produce it')
        return None
    lib = _dlopen_or_rebuild()
    if lib is None:
        return None
    lib.tl_open.restype = ctypes.c_void_p
    lib.tl_open.argtypes = [ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
                            ctypes.c_int]
    lib.tl_total_tokens.restype = ctypes.c_uint64
    lib.tl_total_tokens.argtypes = [ctypes.c_void_p]
    lib.tl_start.restype = ctypes.c_int
    lib.tl_start.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
                             ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
                             ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.tl_next.restype = ctypes.c_int64
    lib.tl_next.argtypes = [ctypes.c_void_p,
                            ctypes.POINTER(ctypes.c_uint32)]
    lib.tl_close.restype = None
    lib.tl_close.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def native_available() -> bool:
    return _load_lib() is not None


class TokenLoader:
    """Iterates [batch, seq+1] uint32 batches from token shard files."""

    def __init__(self, shard_paths: Sequence[str], batch: int, seq: int,
                 *, seed: int = 0, rank: int = 0, world: int = 1,
                 shuffle: bool = True, dtype_bytes: int = 2,
                 prefetch_threads: int = 2, use_native: bool = True) -> None:
        self.paths = [os.path.abspath(os.path.expanduser(p))
                      for p in shard_paths]
        for p in self.paths:
            if not os.path.exists(p):
                raise FileNotFoundError(p)
        self.batch, self.seq = batch, seq
        self.seed, self.rank, self.world = seed, rank, world
        self.shuffle = shuffle
        self.dtype_bytes = dtype_bytes
        self._handle = None
        self._lib = _load_lib() if use_native else None
        if self._lib is not None:
            arr = (ctypes.c_char_p * len(self.paths))(
                *[p.encode() for p in self.paths])
            handle = self._lib.tl_open(arr, len(self.paths), dtype_bytes)
            if not handle:
                raise OSError(f'tl_open failed for {self.paths}')
            self._handle = ctypes.c_void_p(handle)
            rc = self._lib.tl_start(self._handle, batch, seq, seed, rank,
                                    world, int(shuffle), prefetch_threads, 4)
            if rc != 0:
                raise ValueError('dataset smaller than one window')
            self.total_tokens = int(
                self._lib.tl_total_tokens(self._handle))
        else:
            # numpy fallback: concat-mmap the shards.
            dt = np.uint16 if dtype_bytes == 2 else np.uint32
            self._arrays = [np.memmap(p, dtype=dt, mode='r')
                            for p in self.paths]
            self._cum = np.cumsum([0] + [len(a) for a in self._arrays])
            self.total_tokens = int(self._cum[-1])
            if seq + 1 >= self.total_tokens:
                raise ValueError('dataset smaller than one window')
            self._step = 0
            self._rng_base = np.random.SeedSequence(seed)

    # -- numpy fallback helpers --------------------------------------------
    def _window_np(self, start: int, count: int) -> np.ndarray:
        out = np.empty(count, np.uint32)
        filled = 0
        while filled < count:
            shard = int(np.searchsorted(self._cum, start + filled,
                                        side='right')) - 1
            off = start + filled - self._cum[shard]
            take = min(count - filled,
                       len(self._arrays[shard]) - int(off))
            out[filled:filled + take] = self._arrays[shard][off:off + take]
            filled += take
        return out

    def _next_np(self) -> np.ndarray:
        step = self._step
        self._step += 1
        out = np.empty((self.batch, self.seq + 1), np.uint32)
        n_windows = self.total_tokens // self.seq
        for b in range(self.batch):
            if self.shuffle:
                rng = np.random.default_rng(
                    [self.seed, step, self.rank, b])
                start = int(rng.integers(
                    0, self.total_tokens - self.seq - 1))
            else:
                window = (step * self.world + self.rank) * self.batch + b
                start = (window % n_windows) * self.seq
                start = min(start, self.total_tokens - self.seq - 1)
            out[b] = self._window_np(start, self.seq + 1)
        return out

    # -- public --------------------------------------------------------------
    def next_batch(self) -> np.ndarray:
        """[batch, seq+1] uint32; inputs = [:, :-1], targets = [:, 1:]."""
        if self._handle is not None:
            out = np.empty((self.batch, self.seq + 1), np.uint32)
            step = self._lib.tl_next(
                self._handle,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
            if step < 0:
                raise StopIteration
            return out
        return self._next_np()

    def __iter__(self):
        return self

    def __next__(self) -> np.ndarray:
        return self.next_batch()

    def close(self) -> None:
        if self._handle is not None:
            self._lib.tl_close(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # pylint: disable=broad-except
            pass
