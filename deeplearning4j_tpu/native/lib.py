"""Build + bind the native library; pure-python fallbacks when unavailable."""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings
from typing import Optional, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libdl4j_native.so")

_lib = None
_lib_lock = threading.Lock()
_build_attempted = False


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.dl4j_last_error.restype = ctypes.c_char_p
    lib.dl4j_csv_load.restype = ctypes.POINTER(ctypes.c_float)
    lib.dl4j_csv_load.argtypes = [
        ctypes.c_char_p, ctypes.c_char, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
    ]
    lib.dl4j_free.argtypes = [ctypes.c_void_p]
    lib.dl4j_pool_create.restype = ctypes.c_void_p
    lib.dl4j_pool_create.argtypes = [ctypes.c_size_t, ctypes.c_int]
    lib.dl4j_pool_acquire.restype = ctypes.c_void_p
    lib.dl4j_pool_acquire.argtypes = [ctypes.c_void_p]
    lib.dl4j_pool_release.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.dl4j_pool_available.restype = ctypes.c_int
    lib.dl4j_pool_available.argtypes = [ctypes.c_void_p]
    lib.dl4j_pool_destroy.argtypes = [ctypes.c_void_p]
    lib.dl4j_loader_open.restype = ctypes.c_void_p
    lib.dl4j_loader_open.argtypes = [
        ctypes.c_char_p, ctypes.c_char, ctypes.c_int, ctypes.c_int64,
        ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
    ]
    lib.dl4j_loader_cols.restype = ctypes.c_int64
    lib.dl4j_loader_cols.argtypes = [ctypes.c_void_p]
    lib.dl4j_loader_rows.restype = ctypes.c_int64
    lib.dl4j_loader_rows.argtypes = [ctypes.c_void_p]
    lib.dl4j_loader_next.restype = ctypes.c_int64
    lib.dl4j_loader_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
    ]
    lib.dl4j_loader_close.argtypes = [ctypes.c_void_p]
    lib.dl4j_corpus_index.restype = ctypes.c_void_p
    lib.dl4j_corpus_index.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
    ]
    lib.dl4j_corpus_vocab_size.restype = ctypes.c_int64
    lib.dl4j_corpus_vocab_size.argtypes = [ctypes.c_void_p]
    lib.dl4j_corpus_words_bytes.restype = ctypes.c_int64
    lib.dl4j_corpus_words_bytes.argtypes = [ctypes.c_void_p]
    lib.dl4j_corpus_export_vocab.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
    ]
    lib.dl4j_corpus_n_tokens.restype = ctypes.c_int64
    lib.dl4j_corpus_n_tokens.argtypes = [ctypes.c_void_p]
    lib.dl4j_corpus_n_sentences.restype = ctypes.c_int64
    lib.dl4j_corpus_n_sentences.argtypes = [ctypes.c_void_p]
    lib.dl4j_corpus_export_index.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
    ]
    lib.dl4j_corpus_free.argtypes = [ctypes.c_void_p]
    return lib


def _get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _build_attempted
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not os.path.exists(_SO_PATH) and not _build_attempted:
            _build_attempted = True
            try:
                subprocess.run(  # graftlint: allow[blocking-under-lock] build-once seam: the lock must serialize the first-use make (bounded by timeout=120) so N threads never race the compiler
                    ["make", "-C", _NATIVE_DIR],
                    check=True, capture_output=True, timeout=120,
                )
            except (subprocess.SubprocessError, OSError) as exc:
                warnings.warn(f"native library build failed ({exc}); using "
                              "the pure-python fallbacks", RuntimeWarning)
                return None
        if not os.path.exists(_SO_PATH):
            return None
        try:
            _lib = _bind(ctypes.CDLL(_SO_PATH))
        except OSError:
            return None
        return _lib


def native_available() -> bool:
    return _get_lib() is not None


def load_csv(path: str, delimiter: str = ",", skip_lines: int = 0) -> np.ndarray:
    """Parse a numeric CSV to a (rows, cols) float32 array. Native mmap
    parser when available, numpy fallback otherwise."""
    lib = _get_lib()
    if lib is None:
        return np.loadtxt(path, delimiter=delimiter, skiprows=skip_lines,
                          dtype=np.float32, ndmin=2)
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    ptr = lib.dl4j_csv_load(path.encode(), delimiter.encode(), skip_lines,
                            ctypes.byref(rows), ctypes.byref(cols))
    if not ptr:
        raise ValueError(
            f"native csv parse failed for {path!r}: "
            f"{lib.dl4j_last_error().decode()}"
        )
    try:
        n = rows.value * cols.value
        arr = np.ctypeslib.as_array(ptr, shape=(n,)).copy()
    finally:
        lib.dl4j_free(ptr)
    return arr.reshape(rows.value, cols.value)


def corpus_index(text: bytes, min_count: int = 1
                 ) -> Optional[Tuple[list, np.ndarray, np.ndarray, np.ndarray]]:
    """Native corpus tokenize+count+index (native/text.cpp).

    ``text``: newline-separated ASCII sentences. Returns
    (words, counts int64, flat int32, sentence_ids int32) with the exact
    semantics of VocabCache.finish + word2vec build_vocab indexing
    (vocab by (-count, word); sentences with <2 kept tokens dropped),
    or None when the native library is unavailable or the input is not
    ASCII (byte-wise tokenizing would diverge from Python str.split on
    unicode whitespace — the caller keeps its Python path)."""
    lib = _get_lib()
    if lib is None or not text.isascii():
        return None
    handle = lib.dl4j_corpus_index(text, len(text), min_count)
    if not handle:
        return None
    try:
        n_vocab = lib.dl4j_corpus_vocab_size(handle)
        counts = np.zeros(n_vocab, np.int64)
        words_buf = ctypes.create_string_buffer(
            int(lib.dl4j_corpus_words_bytes(handle)))
        if n_vocab:
            lib.dl4j_corpus_export_vocab(
                handle, words_buf,
                counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        words = words_buf.raw.decode("ascii").split("\n")[:-1] if n_vocab else []
        n_tok = lib.dl4j_corpus_n_tokens(handle)
        flat = np.zeros(n_tok, np.int32)
        sids = np.zeros(n_tok, np.int32)
        if n_tok:
            lib.dl4j_corpus_export_index(
                handle,
                flat.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                sids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        return words, counts, flat, sids
    finally:
        lib.dl4j_corpus_free(handle)


class PooledBuffer:
    """float32 view over one pooled native buffer (or plain numpy in
    fallback mode). ``array`` is the usable view."""

    __slots__ = ("array", "_ptr")

    def __init__(self, array: np.ndarray, ptr=None):
        self.array = array
        self._ptr = ptr


class BufferPool:
    """Reusable page-aligned host staging buffers (native), or plain numpy
    allocation when the library is unavailable."""

    def __init__(self, buffer_bytes: int, count: int):
        self.buffer_bytes = buffer_bytes
        self.count = count
        self._lib = _get_lib()
        self._handle = None
        if self._lib is not None:
            self._handle = self._lib.dl4j_pool_create(buffer_bytes, count)

    @property
    def native(self) -> bool:
        return self._handle is not None

    def acquire(self) -> Optional[PooledBuffer]:
        """A pooled buffer, or None when the pool is exhausted."""
        if self._handle is None:
            return PooledBuffer(np.empty(self.buffer_bytes // 4, np.float32))
        ptr = self._lib.dl4j_pool_acquire(self._handle)
        if not ptr:
            return None
        arr = np.ctypeslib.as_array(
            ctypes.cast(ptr, ctypes.POINTER(ctypes.c_float)),
            shape=(self.buffer_bytes // 4,),
        )
        return PooledBuffer(arr, ptr)

    def release(self, buf: PooledBuffer) -> None:
        if self._handle is not None and buf._ptr is not None:
            self._lib.dl4j_pool_release(self._handle, buf._ptr)
            buf._ptr = None

    def available(self) -> int:
        if self._handle is None:
            return self.count
        return self._lib.dl4j_pool_available(self._handle)

    def close(self) -> None:
        if self._handle is not None:
            self._lib.dl4j_pool_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeCSVLoader:
    """Background-prefetching batch loader over a numeric CSV.

    Iterates (batch_rows, cols) float32 arrays; the native producer thread
    stays `queue_capacity` batches ahead. Falls back to a synchronous numpy
    implementation without the library.
    """

    def __init__(self, path: str, batch: int, delimiter: str = ",",
                 skip_lines: int = 0, queue_capacity: int = 4,
                 drop_last: bool = False, shuffle_seed: int = 0):
        self.path = path
        self.batch = batch
        self.delimiter = delimiter
        self.skip_lines = skip_lines
        self.queue_capacity = queue_capacity
        self.drop_last = drop_last
        self.shuffle_seed = shuffle_seed
        self._lib = _get_lib()
        self._handle = None
        self._fallback: Optional[np.ndarray] = None
        self._cursor = 0
        self._open()

    def _open(self) -> None:
        if self._lib is not None:
            self._handle = self._lib.dl4j_loader_open(
                self.path.encode(), self.delimiter.encode(), self.skip_lines,
                self.batch, self.queue_capacity, int(self.drop_last),
                self.shuffle_seed,
            )
            if self._handle:
                self.rows = self._lib.dl4j_loader_rows(self._handle)
                self.cols = self._lib.dl4j_loader_cols(self._handle)
                return
            raise ValueError(
                f"native loader failed for {self.path!r}: "
                f"{self._lib.dl4j_last_error().decode()}"
            )
        data = np.loadtxt(self.path, delimiter=self.delimiter,
                          skiprows=self.skip_lines, dtype=np.float32, ndmin=2)
        if self.shuffle_seed:
            rng = np.random.default_rng(self.shuffle_seed)
            data = data[rng.permutation(len(data))]
        self._fallback = data
        self.rows, self.cols = data.shape

    @property
    def native(self) -> bool:
        return self._handle is not None

    def __iter__(self):
        if self._handle is not None:
            buf = np.empty(self.batch * self.cols, np.float32)
            while True:
                n = self._lib.dl4j_loader_next(
                    self._handle,
                    buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                    buf.size,
                )
                if n == 0:  # clean end-of-epoch
                    return
                if n < 0:  # error (e.g. out_capacity too small) — never EOF
                    raise RuntimeError(
                        f"native loader error for {self.path!r}: "
                        f"{self._lib.dl4j_last_error().decode()}"
                    )
                yield buf[: n * self.cols].reshape(n, self.cols).copy()
        else:
            data = self._fallback
            for start in range(0, self.rows, self.batch):
                chunk = data[start : start + self.batch]
                if len(chunk) < self.batch and self.drop_last:
                    return
                yield chunk.copy()

    def close(self) -> None:
        if self._handle is not None:
            self._lib.dl4j_loader_close(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
