"""ctypes bridge to the C++ worker-pool dataloader (native/dataloader.cpp).

Python builds a producer callback (collate into a flat byte buffer); C++
threads run it concurrently and keep an ordered ring of ready batches. For
pure-C++ producers (pt_lm_window_producer) the whole pipeline runs without
the GIL. The .so is never committed: the first loader of a process runs
``make`` (a no-op when the library is newer than its source), so the
library is always built from native/dataloader.cpp on the machine that
runs it.
"""
import ctypes
import os
import pickle
import subprocess
import threading

import numpy as np

from .. import observability as _obs

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), '..', '..', 'native')
_LIB_PATH = os.path.join(_NATIVE_DIR, 'libpaddle_tpu_native.so')
_lib = None
_lib_lock = threading.Lock()

_PRODUCE_FN = ctypes.CFUNCTYPE(ctypes.c_int64, ctypes.c_int64,
                               ctypes.POINTER(ctypes.c_uint8),
                               ctypes.c_int64, ctypes.c_void_p)


def get_lib():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        # build-once-under-lock is intentional: concurrent callers must
        # block until the shared library exists, and no device work can
        # be in flight before the first loader is constructed
        # pt-lint: disable=lock-blocking-call
        build = subprocess.run(['make', '-C', _NATIVE_DIR],
                               capture_output=True, text=True)
        if build.returncode != 0:
            raise RuntimeError(
                f'building {_LIB_PATH} failed (needs make and a C++17 '
                f'compiler):\n{build.stdout}{build.stderr}')
        lib = ctypes.CDLL(_LIB_PATH)
        lib.pt_pool_create.restype = ctypes.c_void_p
        lib.pt_pool_create.argtypes = [ctypes.c_int, ctypes.c_int,
                                       ctypes.c_int64, _PRODUCE_FN,
                                       ctypes.c_void_p]
        lib.pt_pool_submit.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.pt_pool_next.restype = ctypes.c_int64
        lib.pt_pool_next.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_uint8)]
        lib.pt_pool_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


class WorkerPool:
    """Generic pool: producer(index) -> bytes (pickled batch)."""

    def __init__(self, produce_py, n_workers=2, ring_cap=4,
                 batch_bytes=32 << 20):
        lib = get_lib()
        self.batch_bytes = batch_bytes

        def produce(index, dest, capacity, ctx):
            try:
                payload = produce_py(index)
                n = len(payload)
                if n > capacity:
                    return -1
                ctypes.memmove(dest, payload, n)
                return n
            except Exception:
                return -1

        self._cb = _PRODUCE_FN(produce)          # keep alive
        self._pool = lib.pt_pool_create(n_workers, ring_cap, batch_bytes,
                                        self._cb, None)
        self._buf = (ctypes.c_uint8 * batch_bytes)()
        self._lib = lib
        self._closed = False

    def submit(self, index):
        self._lib.pt_pool_submit(self._pool, index)

    def next(self):
        n = self._lib.pt_pool_next(self._pool, self._buf)
        if n < 0:
            return None
        return bytes(self._buf[:n])

    def close(self):
        if not self._closed:
            self._lib.pt_pool_destroy(self._pool)
            self._closed = True

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeWorkerIterator:
    """DataLoader iterator backed by the C++ pool: collation runs on worker
    threads, Python just unpickles ready batches in order."""

    def __init__(self, loader):
        self.loader = loader
        if loader.batch_sampler is None:
            raise RuntimeError('native loader needs a batch_sampler dataset')
        self.batches = list(loader.batch_sampler)
        dataset = loader.dataset
        collate = loader.collate_fn
        batches = self.batches

        def produce(i):
            items = [dataset[j] for j in batches[i]]
            out = collate(items)
            return pickle.dumps(_to_numpy(out), protocol=4)

        self.pool = WorkerPool(produce, n_workers=max(loader.num_workers, 1),
                               ring_cap=loader.prefetch_factor *
                               max(loader.num_workers, 1))
        self.n = len(self.batches)
        self.submitted = 0
        self.consumed = 0
        prefill = min(2 * max(loader.num_workers, 1), self.n)
        for _ in range(prefill):
            self.pool.submit(self.submitted)
            self.submitted += 1

    def __iter__(self):
        return self

    def __next__(self):
        if self.consumed >= self.n:
            self.pool.close()
            raise StopIteration
        if self.submitted < self.n:
            self.pool.submit(self.submitted)
            self.submitted += 1
        payload = self.pool.next()
        self.consumed += 1
        if not payload:
            self.pool.close()
            raise StopIteration
        return _from_numpy(pickle.loads(payload))


def _to_numpy(obj):
    from ..core.tensor import Tensor
    if isinstance(obj, Tensor):
        return np.asarray(obj._value)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_to_numpy(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _to_numpy(v) for k, v in obj.items()}
    return obj


def _from_numpy(obj):
    from ..core.tensor import Tensor
    if isinstance(obj, np.ndarray):
        return Tensor(obj)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_from_numpy(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _from_numpy(v) for k, v in obj.items()}
    return obj


class LMTokenLoader:
    """Pure-C++ LM batcher: windows over a flat int32 token stream (no GIL)."""

    def __init__(self, tokens, batch_size, seq_len, stride=None, n_workers=2,
                 ring_cap=4):
        lib = get_lib()
        self.tokens = np.ascontiguousarray(np.asarray(tokens, np.int32))
        self.batch_size = batch_size
        self.seq_len = seq_len
        stride = stride or seq_len

        class LmCtx(ctypes.Structure):
            _fields_ = [('stream', ctypes.c_void_p),
                        ('n_tokens', ctypes.c_int64),
                        ('seq_len', ctypes.c_int64),
                        ('stride', ctypes.c_int64),
                        ('batch', ctypes.c_int64)]

        self._ctx = LmCtx(self.tokens.ctypes.data, len(self.tokens),
                          seq_len, stride, batch_size)
        producer = ctypes.cast(lib.pt_lm_window_producer, _PRODUCE_FN)
        nbytes = batch_size * seq_len * 4
        self._pool = lib.pt_pool_create(n_workers, ring_cap, nbytes, producer,
                                        ctypes.byref(self._ctx))
        self._buf = (ctypes.c_uint8 * nbytes)()
        self._lib = lib
        self._nbytes = nbytes
        self._next_submit = 0
        for _ in range(ring_cap):
            self._lib.pt_pool_submit(self._pool, self._next_submit)
            self._next_submit += 1

    def next_batch(self):
        # the wait for the native pool, on the profiler's clock, timed
        # where it happens
        with _obs.span('data.next_batch', n_bytes=self._nbytes):
            self._lib.pt_pool_submit(self._pool, self._next_submit)
            self._next_submit += 1
            n = self._lib.pt_pool_next(self._pool, self._buf)
        assert n == self._nbytes
        arr = np.frombuffer(bytes(self._buf[:n]), np.int32).reshape(
            self.batch_size, self.seq_len)
        return arr

    def close(self):
        self._lib.pt_pool_destroy(self._pool)
