"""ctypes bindings for the native runtime components.

Builds ``libkaito_native.so`` on first use when a compiler is
available (make -C kaito_tpu/native).  The vector index has a
pure-Python fallback in its consumer; the prefix cache has none — the
engine warns and serves without prefix reuse, visibly on /health.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libkaito_native.so")
_lib = None
_lib_lock = threading.Lock()
_build_attempted = False


def load_native() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None on failure."""
    global _lib, _build_attempted
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not _build_attempted:
            # always invoke make: dependency-driven, a no-op when fresh,
            # and it rebuilds a stale .so missing newer symbols
            _build_attempted = True
            try:
                subprocess.run(["make", "-C", _DIR, "-s"], check=True,
                               capture_output=True, timeout=120)
            except Exception as e:
                logger.warning("native build failed (%s); using python fallbacks", e)
        if not os.path.exists(_LIB_PATH):
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
            _declare(lib)
        except (OSError, AttributeError) as e:
            logger.warning("cannot load %s: %s", _LIB_PATH, e)
            return None
        _lib = lib
        return _lib


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.kprefix_new.restype = c.c_void_p
    lib.kprefix_new.argtypes = [c.c_int32, c.c_int32]
    lib.kprefix_free.argtypes = [c.c_void_p]
    lib.kprefix_acquire.restype = c.c_int32
    lib.kprefix_acquire.argtypes = [
        c.c_void_p, c.POINTER(c.c_int32), c.c_int32, c.c_int32,
        c.POINTER(c.c_int32), c.POINTER(c.c_int32)]
    lib.kprefix_release.argtypes = [
        c.c_void_p, c.POINTER(c.c_int32), c.c_int32,
        c.POINTER(c.c_int32), c.c_int32]
    lib.kprefix_alloc_raw.restype = c.c_int32
    lib.kprefix_alloc_raw.argtypes = [
        c.c_void_p, c.c_int32, c.POINTER(c.c_int32)]
    lib.kprefix_release_uncommitted.argtypes = [
        c.c_void_p, c.POINTER(c.c_int32), c.c_int32,
        c.POINTER(c.c_int32), c.c_int32]
    lib.kprefix_available.restype = c.c_int32
    lib.kprefix_available.argtypes = [c.c_void_p]
    lib.kprefix_stats.argtypes = [c.c_void_p] + [c.POINTER(c.c_int64)] * 4

    lib.kvec_new.restype = c.c_void_p
    lib.kvec_new.argtypes = [c.c_int32]
    lib.kvec_free.argtypes = [c.c_void_p]
    lib.kvec_size.restype = c.c_int64
    lib.kvec_size.argtypes = [c.c_void_p]
    lib.kvec_add.argtypes = [c.c_void_p, c.c_int64, c.POINTER(c.c_float)]
    lib.kvec_remove.restype = c.c_int32
    lib.kvec_remove.argtypes = [c.c_void_p, c.c_int64]
    lib.kvec_search.restype = c.c_int32
    lib.kvec_search.argtypes = [
        c.c_void_p, c.POINTER(c.c_float), c.c_int32,
        c.POINTER(c.c_int64), c.POINTER(c.c_float)]
    lib.kvec_export.argtypes = [
        c.c_void_p, c.POINTER(c.c_int64), c.POINTER(c.c_float)]


def _i32ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


class NativePrefixCache:
    """Prefix-caching page allocator (radix tree over token chunks)."""

    def __init__(self, num_pages: int, page_size: int):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.kprefix_new(num_pages, page_size)
        if not self._h:
            raise ValueError("bad prefix cache parameters")
        self.num_pages = num_pages
        self.page_size = page_size

    def acquire(self, tokens: list[int], max_total_tokens: int
                ) -> Optional[tuple[list[int], int]]:
        """Returns (pages, cached_tokens) or None on OOM."""
        toks = np.asarray(tokens, np.int32)
        max_pages = -(-max_total_tokens // self.page_size)
        out = np.zeros(max_pages + 1, np.int32)
        cached = ctypes.c_int32(0)
        n = self._lib.kprefix_acquire(
            self._h, _i32ptr(toks), len(toks), max_total_tokens,
            _i32ptr(out), ctypes.byref(cached))
        if n < 0:
            return None
        return list(out[:n]), int(cached.value)

    def release(self, tokens: list[int], pages: list[int]) -> None:
        toks = np.asarray(tokens, np.int32)
        pg = np.asarray(pages, np.int32)
        self._lib.kprefix_release(self._h, _i32ptr(toks), len(toks),
                                  _i32ptr(pg), len(pg))

    def alloc_raw(self, n: int) -> Optional[list[int]]:
        """Plain page allocation for on-demand sequence growth; the pages
        return through release()/release_uncommitted() with the rest."""
        out = np.zeros(max(n, 1), np.int32)
        got = self._lib.kprefix_alloc_raw(self._h, n, _i32ptr(out))
        if got < 0:
            return None
        return list(out[:got])

    def release_uncommitted(self, tokens: list[int], pages: list[int]) -> None:
        """Return shared refs and free exclusive pages WITHOUT committing
        anything into the radix tree (failure / unvalidated-KV paths)."""
        toks = np.asarray(tokens, np.int32)
        pg = np.asarray(pages, np.int32)
        self._lib.kprefix_release_uncommitted(
            self._h, _i32ptr(toks), len(toks), _i32ptr(pg), len(pg))

    @property
    def available(self) -> int:
        return int(self._lib.kprefix_available(self._h))

    def stats(self) -> dict:
        vals = [ctypes.c_int64(0) for _ in range(4)]
        self._lib.kprefix_stats(self._h, *[ctypes.byref(v) for v in vals])
        return {"hits": vals[0].value, "misses": vals[1].value,
                "evictions": vals[2].value, "cached_pages": vals[3].value}

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.kprefix_free(self._h)
            self._h = None


class NativeFlatIndex:
    """Flat inner-product index backed by the C++ implementation;
    interface-compatible with rag.vector_store.FlatDenseIndex."""

    def __init__(self, dim: int):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.kvec_new(dim)
        self.dim = dim
        self._key_to_int: dict[str, int] = {}
        self._int_to_key: dict[int, str] = {}
        self._next = 1

    def _intern(self, doc_id: str) -> int:
        i = self._key_to_int.get(doc_id)
        if i is None:
            i = self._next
            self._next += 1
            self._key_to_int[doc_id] = i
            self._int_to_key[i] = doc_id
        return i

    def add(self, doc_id: str, vec: np.ndarray) -> None:
        v = np.ascontiguousarray(vec, np.float32)
        self._lib.kvec_add(self._h, self._intern(doc_id),
                           v.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))

    def remove(self, doc_id: str) -> None:
        i = self._key_to_int.pop(doc_id, None)
        if i is not None:
            self._int_to_key.pop(i, None)
            self._lib.kvec_remove(self._h, i)

    def search(self, query_vec: np.ndarray, top_k: int) -> list[tuple[str, float]]:
        q = np.ascontiguousarray(query_vec, np.float32)
        ids = np.zeros(top_k, np.int64)
        scores = np.zeros(top_k, np.float32)
        n = self._lib.kvec_search(
            self._h, q.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), top_k,
            ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            scores.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        return [(self._int_to_key[int(ids[i])], float(scores[i]))
                for i in range(n) if int(ids[i]) in self._int_to_key]

    def state(self) -> dict:
        n = int(self._lib.kvec_size(self._h))
        ids = np.zeros(n, np.int64)
        vecs = np.zeros((n, self.dim), np.float32)
        if n:
            self._lib.kvec_export(
                self._h, ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                vecs.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        return {"ids": [self._int_to_key.get(int(i), str(i)) for i in ids],
                "vecs": vecs}

    def load_state(self, state: dict) -> None:
        for doc_id, vec in zip(state["ids"], np.asarray(state["vecs"])):
            self.add(str(doc_id), vec)

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.kvec_free(self._h)
            self._h = None
