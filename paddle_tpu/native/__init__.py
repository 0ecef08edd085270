"""ctypes bindings to the native (C++) runtime core: graph IR + execution
planner, host staging allocator, and the prefetch byte-queue.

Reference parity: this plays the role of the `core_avx` pybind module
(pybind/pybind.cc:469) for the subsystems that stay native in the TPU build —
graph topology/scheduling (framework/executor_gc_helper, ir memory passes),
host memory (memory/allocation/auto_growth_best_fit_allocator.cc) and reader
prefetch (operators/reader/buffered_reader.h:36).  Per-op fast paths
(op_function_generator.cc) are NOT reproduced: jax already is the fused fast
path; only whole-graph calls cross the boundary.

The shared library is built on demand with g++ (no pybind11 in the image; the
ABI is plain C consumed via ctypes).  If the build fails the static executor
plans in pure Python (`available()` -> False); the failure is logged once,
with the compiler's words, and kept in `build_error()`.
"""
import ctypes
import logging
import os
import subprocess
import threading

_log = logging.getLogger("ptn.native")

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_BUILD_DIR = os.path.join(_NATIVE_DIR, "_build")

_lib = None
_lib_err = None
_lock = threading.Lock()


def _build_and_load():
    """Build via native/Makefile (single source of truth for sources/flags);
    make's own mtime tracking decides whether a rebuild is needed."""
    global _lib, _lib_err
    so_path = os.path.join(_BUILD_DIR, "libptn.so")
    try:
        os.makedirs(_BUILD_DIR, exist_ok=True)
        subprocess.run(
            ["make", "-C", _NATIVE_DIR, f"OUT={so_path}"],
            check=True, capture_output=True, text=True)
        lib = ctypes.CDLL(so_path)
    except (OSError, ValueError, subprocess.CalledProcessError) as e:
        _lib_err = e
        _log.warning("native runtime not built (%s): %s", so_path,
                     build_error())
        return None
    _declare(lib)
    return lib


def build_error():
    """Why the native library is unavailable (make's stderr when the
    build itself failed), or None."""
    if _lib_err is None:
        return None
    stderr = getattr(_lib_err, "stderr", None)
    return f"{_lib_err}" + (f"\n{stderr.strip()}" if stderr else "")


def _declare(lib):
    c = ctypes
    i32, u32, u64, i64 = c.c_int32, c.c_uint32, c.c_uint64, c.c_int64
    p, cp = c.c_void_p, c.c_char_p
    sigs = {
        "ptn_program_new": (p, []),
        "ptn_program_free": (None, [p]),
        "ptn_program_add_block": (i32, [p, i32]),
        "ptn_block_add_var": (i32, [p, i32, cp, i32]),
        "ptn_block_find_var": (i32, [p, i32, cp]),
        "ptn_block_add_op": (i32, [p, i32, cp, c.POINTER(i32), i32,
                                   c.POINTER(i32), i32, i32]),
        "ptn_block_num_ops": (i32, [p, i32]),
        "ptn_block_num_vars": (i32, [p, i32]),
        "ptn_plan_build": (p, [p, i32, c.POINTER(i32), i32,
                               c.POINTER(i32), i32]),
        "ptn_plan_free": (None, [p]),
        "ptn_plan_num_ops": (i32, [p]),
        "ptn_plan_op_at": (i32, [p, i32]),
        "ptn_plan_has_cycle": (i32, [p]),
        "ptn_plan_num_slots": (i32, [p]),
        "ptn_plan_slot_of": (i32, [p, i32]),
        "ptn_plan_dead_after": (i32, [p, i32, c.POINTER(i32), i32]),
        "ptn_plan_num_waves": (i32, [p]),
        "ptn_plan_wave_size": (i32, [p, i32]),
        "ptn_plan_donatable": (i32, [p, c.POINTER(i32), i32]),
        "ptn_alloc_create": (p, [u64]),
        "ptn_alloc_malloc": (p, [p, u64]),
        "ptn_alloc_free": (None, [p, p]),
        "ptn_alloc_stats": (None, [p, c.POINTER(u64)]),
        "ptn_alloc_destroy": (None, [p]),
        "ptn_queue_create": (p, [u32]),
        "ptn_queue_push": (c.c_int, [p, p, u64, i64]),
        "ptn_queue_pop": (p, [p, c.POINTER(u64), i64]),
        "ptn_queue_close": (None, [p]),
        "ptn_queue_size": (u64, [p]),
        "ptn_queue_bytes": (u64, [p]),
        "ptn_queue_destroy": (None, [p]),
        "ptn_bytes_free": (None, [p]),
        "ptn_feed_create": (p, [c.POINTER(cp), i32, i32, i32, i32, i32,
                                i32]),
        "ptn_feed_next_batch": (c.c_int, [p, c.POINTER(c.POINTER(c.c_float)),
                                          c.POINTER(c.POINTER(i64)),
                                          c.POINTER(i32), c.POINTER(i32)]),
        "ptn_feed_destroy": (None, [p]),
        "ptn_version": (cp, []),
    }
    for name, (restype, argtypes) in sigs.items():
        fn = getattr(lib, name)
        fn.restype = restype
        fn.argtypes = argtypes


def get_lib():
    global _lib
    if _lib is None:
        with _lock:
            if _lib is None and _lib_err is None:
                _lib = _build_and_load()
    return _lib


def available():
    return get_lib() is not None


def _i32_array(values):
    arr = (ctypes.c_int32 * len(values))(*values)
    return arr, len(values)


class NativeProgram:
    """Topology mirror of a static Program (framework.proto:202 role)."""

    def __init__(self):
        self._lib = get_lib()
        if self._lib is None:
            raise RuntimeError(f"native runtime unavailable: {_lib_err}")
        self._h = self._lib.ptn_program_new()

    def close(self):
        if getattr(self, "_h", None):
            self._lib.ptn_program_free(self._h)
            self._h = None

    __del__ = close

    def add_var(self, name, persistable=False, block=0):
        return self._lib.ptn_block_add_var(
            self._h, block, name.encode(), int(bool(persistable)))

    def find_var(self, name, block=0):
        return self._lib.ptn_block_find_var(self._h, block, name.encode())

    def add_op(self, op_type, input_ids, output_ids, side_effect=False, block=0):
        ins, n_in = _i32_array(list(input_ids))
        outs, n_out = _i32_array(list(output_ids))
        return self._lib.ptn_block_add_op(
            self._h, block, op_type.encode(), ins, n_in, outs, n_out,
            int(bool(side_effect)))

    def num_ops(self, block=0):
        return self._lib.ptn_block_num_ops(self._h, block)

    def num_vars(self, block=0):
        return self._lib.ptn_block_num_vars(self._h, block)

    def build_plan(self, feed_ids, fetch_ids, block=0):
        feeds, n_f = _i32_array(list(feed_ids))
        fetches, n_t = _i32_array(list(fetch_ids))
        h = self._lib.ptn_plan_build(self._h, block, feeds, n_f, fetches, n_t)
        return NativePlan(self._lib, h)


class NativePlan:
    """Pruned + scheduled + liveness-annotated execution plan."""

    def __init__(self, lib, handle):
        self._lib = lib
        self._h = handle

    def close(self):
        if getattr(self, "_h", None):
            self._lib.ptn_plan_free(self._h)
            self._h = None

    __del__ = close

    @property
    def order(self):
        n = self._lib.ptn_plan_num_ops(self._h)
        return [self._lib.ptn_plan_op_at(self._h, i) for i in range(n)]

    @property
    def has_cycle(self):
        return bool(self._lib.ptn_plan_has_cycle(self._h))

    @property
    def num_slots(self):
        return self._lib.ptn_plan_num_slots(self._h)

    def slot_of(self, var_id):
        return self._lib.ptn_plan_slot_of(self._h, var_id)

    def dead_after(self, step):
        buf = (ctypes.c_int32 * 256)()
        n = self._lib.ptn_plan_dead_after(self._h, step, buf, 256)
        if n > 256:
            buf = (ctypes.c_int32 * n)()
            n = self._lib.ptn_plan_dead_after(self._h, step, buf, n)
        return list(buf[:n])

    @property
    def wave_sizes(self):
        n = self._lib.ptn_plan_num_waves(self._h)
        return [self._lib.ptn_plan_wave_size(self._h, i) for i in range(n)]

    @property
    def donatable_feeds(self):
        buf = (ctypes.c_int32 * 256)()
        n = self._lib.ptn_plan_donatable(self._h, buf, 256)
        if n > 256:
            buf = (ctypes.c_int32 * n)()
            n = self._lib.ptn_plan_donatable(self._h, buf, n)
        return list(buf[:n])


class HostAllocator:
    """Chunked best-fit host arena (auto_growth_best_fit_allocator.cc role)."""

    def __init__(self, chunk_size=64 << 20):
        self._lib = get_lib()
        if self._lib is None:
            raise RuntimeError(f"native runtime unavailable: {_lib_err}")
        self._h = self._lib.ptn_alloc_create(chunk_size)

    def close(self):
        if getattr(self, "_h", None):
            self._lib.ptn_alloc_destroy(self._h)
            self._h = None

    __del__ = close

    def alloc(self, size):
        p = self._lib.ptn_alloc_malloc(self._h, size)
        if not p:
            raise MemoryError(f"native host allocator failed for {size} bytes")
        return p

    def free(self, ptr):
        self._lib.ptn_alloc_free(self._h, ptr)

    def stats(self):
        buf = (ctypes.c_uint64 * 5)()
        self._lib.ptn_alloc_stats(self._h, buf)
        return {"in_use": buf[0], "reserved": buf[1], "peak": buf[2],
                "alloc_count": buf[3], "chunks": buf[4]}


class PrefetchQueue:
    """Bounded blocking byte-batch queue (BufferedReader / blocking-queue
    role). push/pop move pickled batches; blocking calls release the GIL."""

    def __init__(self, capacity=2):
        self._lib = get_lib()
        if self._lib is None:
            raise RuntimeError(f"native runtime unavailable: {_lib_err}")
        self._h = self._lib.ptn_queue_create(capacity)

    def close(self):
        h, self._h = getattr(self, "_h", None), None
        if h:
            self._lib.ptn_queue_close(h)
            self._lib.ptn_queue_destroy(h)

    def push(self, data: bytes, timeout_ms=-1) -> bool:
        if self._h is None:
            return False
        rc = self._lib.ptn_queue_push(self._h, data, len(data), timeout_ms)
        if rc == -3:
            raise MemoryError("prefetch queue allocation failed")
        return rc == 0

    def pop(self, timeout_ms=-1):
        """bytes, or None on timeout, or EOFError raised when closed+drained."""
        if self._h is None:
            raise EOFError("queue closed")
        size = ctypes.c_uint64()
        p = self._lib.ptn_queue_pop(self._h, ctypes.byref(size), timeout_ms)
        if not p:
            if size.value == ctypes.c_uint64(-1).value:
                raise EOFError("queue closed")
            return None
        try:
            return ctypes.string_at(p, size.value)
        finally:
            self._lib.ptn_bytes_free(p)

    def shutdown(self):
        if self._h is not None:
            self._lib.ptn_queue_close(self._h)

    def qsize(self):
        return self._lib.ptn_queue_size(self._h) if self._h else 0


class NativeDataFeed:
    """Threaded C++ file reader/parser (framework/data_feed.cc parity).

    Iterates (features float32 [rows, cols], labels int64 [rows]) batches
    parsed off the GIL on C++ worker threads.  CSV (`label_col` selects the
    int label column) or the reference's MultiSlot text format
    (`multislot=True`, slots concatenated into the feature row).
    """

    def __init__(self, files, batch_size, num_threads=2, label_col=-1,
                 queue_cap=8, multislot=False):
        self._lib = get_lib()
        if self._lib is None:
            raise RuntimeError(f"native runtime unavailable: {_lib_err}")
        self._files = [os.fsencode(f) for f in files]
        arr = (ctypes.c_char_p * len(self._files))(*self._files)
        self._h = self._lib.ptn_feed_create(
            arr, len(self._files), int(batch_size), int(num_threads),
            int(label_col), int(queue_cap), 1 if multislot else 0)

    def __iter__(self):
        return self

    def __next__(self):
        import numpy as np

        if self._h is None:
            raise StopIteration
        vals = ctypes.POINTER(ctypes.c_float)()
        labs = ctypes.POINTER(ctypes.c_int64)()
        rows = ctypes.c_int32()
        cols = ctypes.c_int32()
        ok = self._lib.ptn_feed_next_batch(
            self._h, ctypes.byref(vals), ctypes.byref(labs),
            ctypes.byref(rows), ctypes.byref(cols))
        if not ok:
            self.close()
            raise StopIteration
        r, c = rows.value, cols.value
        try:
            feats = np.ctypeslib.as_array(vals, shape=(r, c)).copy()
            labels = np.ctypeslib.as_array(labs, shape=(r,)).copy()
        finally:
            self._lib.ptn_bytes_free(
                ctypes.cast(vals, ctypes.c_void_p))
            self._lib.ptn_bytes_free(
                ctypes.cast(labs, ctypes.c_void_p))
        return feats, labels

    def close(self):
        if self._h is not None:
            self._lib.ptn_feed_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
