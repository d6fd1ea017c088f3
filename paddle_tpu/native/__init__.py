"""Native runtime components, built on first import with the system g++
and bound through ctypes (the image has no pybind11; reference-parity
components that are C++ in the reference stay C++ here — SURVEY.md §2.11).

``lib()`` returns the loaded CDLL or None when no toolchain is available;
callers fall back to pure-Python implementations in that case.
"""

import contextlib
import ctypes
import glob
import hashlib
import os
import subprocess
import tempfile
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ["recordio.cc", "blocking_queue.cc", "multislot.cc"]
_BUILD = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC"]

_lock = threading.Lock()
_lib = None
_build_failed = False


def _so_path():
    """The library's file name carries a hash of the sources' content and
    the build command, so a binary is trusted only if it was built from
    exactly these files — never by mtime, which a copied tree does not
    keep. A tree that arrives with another tree's binary rebuilds."""
    h = hashlib.sha256(" ".join(_BUILD).encode())
    for s in _SOURCES:
        with open(os.path.join(_DIR, s), "rb") as f:
            h.update(f.read())
    return os.path.join(
        _DIR, "libpaddle_tpu_native.%s.so" % h.hexdigest()[:16])


def _build(so_path):
    """Compile beside the target and rename into place: several test
    workers may build at once, and each must load a whole file."""
    srcs = [os.path.join(_DIR, s) for s in _SOURCES]
    fd, tmp = tempfile.mkstemp(dir=_DIR, suffix=".so.tmp")
    os.close(fd)
    try:
        subprocess.run([*_BUILD, "-o", tmp, *srcs, "-lpthread"],
                       check=True, capture_output=True)
        os.replace(tmp, so_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for stale in glob.glob(os.path.join(_DIR, "libpaddle_tpu_native*.so")):
        if stale != so_path:
            with contextlib.suppress(OSError):  # another worker got there
                os.unlink(stale)


def _bind(lib):
    c = ctypes
    lib.rio_writer_open.restype = c.c_void_p
    lib.rio_writer_open.argtypes = [c.c_char_p, c.c_uint32, c.c_uint64]
    lib.rio_writer_write.restype = c.c_int
    lib.rio_writer_write.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64]
    lib.rio_writer_close.restype = c.c_int
    lib.rio_writer_close.argtypes = [c.c_void_p]
    lib.rio_reader_open.restype = c.c_void_p
    lib.rio_reader_open.argtypes = [c.c_char_p]
    lib.rio_reader_next.restype = c.c_int64
    lib.rio_reader_next.argtypes = [c.c_void_p, c.POINTER(c.c_char_p)]
    lib.msf_parse_file.restype = c.c_void_p
    lib.msf_parse_file.argtypes = [c.c_char_p, c.c_int,
                                   c.POINTER(c.c_uint8)]
    lib.msf_num_rows.restype = c.c_int64
    lib.msf_num_rows.argtypes = [c.c_void_p]
    lib.msf_slot_total.restype = c.c_int64
    lib.msf_slot_total.argtypes = [c.c_void_p, c.c_int]
    lib.msf_slot_counts.restype = None
    lib.msf_slot_counts.argtypes = [c.c_void_p, c.c_int,
                                    c.POINTER(c.c_int64)]
    lib.msf_slot_values_f.restype = None
    lib.msf_slot_values_f.argtypes = [c.c_void_p, c.c_int,
                                      c.POINTER(c.c_float)]
    lib.msf_slot_values_i.restype = None
    lib.msf_slot_values_i.argtypes = [c.c_void_p, c.c_int,
                                      c.POINTER(c.c_int64)]
    lib.msf_free.restype = None
    lib.msf_free.argtypes = [c.c_void_p]
    lib.msf_range_total.restype = c.c_int64
    lib.msf_range_total.argtypes = [c.c_void_p, c.c_int, c.c_int64,
                                    c.c_int64]
    lib.msf_counts_range.restype = None
    lib.msf_counts_range.argtypes = [c.c_void_p, c.c_int, c.c_int64,
                                     c.c_int64, c.POINTER(c.c_int64)]
    lib.msf_values_f_range.restype = None
    lib.msf_values_f_range.argtypes = [c.c_void_p, c.c_int, c.c_int64,
                                       c.c_int64, c.POINTER(c.c_float)]
    lib.msf_values_i_range.restype = None
    lib.msf_values_i_range.argtypes = [c.c_void_p, c.c_int, c.c_int64,
                                       c.c_int64,
                                       c.POINTER(c.c_int64)]
    lib.rio_reader_close.argtypes = [c.c_void_p]

    lib.btq_create.restype = c.c_void_p
    lib.btq_create.argtypes = [c.c_uint64]
    lib.btq_push.restype = c.c_int
    lib.btq_push.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64]
    lib.btq_pop.restype = c.c_int64
    lib.btq_pop.argtypes = [c.c_void_p, c.POINTER(c.POINTER(c.c_char))]
    lib.btq_free_buf.argtypes = [c.POINTER(c.c_char)]
    lib.btq_size.restype = c.c_uint64
    lib.btq_size.argtypes = [c.c_void_p]
    lib.btq_close.argtypes = [c.c_void_p]
    lib.btq_reset.argtypes = [c.c_void_p]
    lib.btq_destroy.argtypes = [c.c_void_p]
    return lib


def lib():
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        try:
            so_path = _so_path()
            if not os.path.exists(so_path):
                _build(so_path)
            _lib = _bind(ctypes.CDLL(so_path))
        except Exception:
            _build_failed = True
            _lib = None
    return _lib


class BlockingQueue:
    """Bounded byte-buffer queue (native when available). The capacity
    bound gives backpressure; ``close`` lets poppers drain then signals
    end-of-stream — the LoDTensorBlockingQueue contract."""

    def __init__(self, capacity=64):
        self._native = lib()
        if self._native is not None:
            self._q = self._native.btq_create(capacity)
        else:
            import queue

            self._q = queue.Queue(maxsize=capacity)
            self._closed = False

    def push(self, data: bytes) -> bool:
        if self._native is not None:
            return self._native.btq_push(self._q, data, len(data)) == 0
        import queue

        # Re-check _closed between bounded put attempts so close() can
        # unblock a producer stuck on a full queue (mirrors the native
        # btq_push close semantics; a plain blocking put would hang the
        # producer thread forever if the consumer stops early).
        while not self._closed:
            try:
                self._q.put(data, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def pop(self):
        """bytes, or None at end-of-stream."""
        if self._native is not None:
            out = ctypes.POINTER(ctypes.c_char)()
            n = self._native.btq_pop(self._q, ctypes.byref(out))
            if n < 0:
                return None
            data = ctypes.string_at(out, n)
            self._native.btq_free_buf(out)
            return data
        import queue

        while True:
            try:
                return self._q.get(timeout=0.05)
            except queue.Empty:
                if self._closed:
                    return None

    def size(self):
        if self._native is not None:
            return int(self._native.btq_size(self._q))
        return self._q.qsize()

    def close(self):
        if self._native is not None:
            self._native.btq_close(self._q)
        else:
            self._closed = True

    def reset(self):
        if self._native is not None:
            self._native.btq_reset(self._q)
        else:
            import queue

            self._q = queue.Queue(maxsize=self._q.maxsize)
            self._closed = False

    def __del__(self):
        try:
            if getattr(self, "_native", None) is not None:
                self._native.btq_destroy(self._q)
        except Exception:
            pass


def parse_multislot_file(path, slot_is_float):
    """Whole-file convenience over open_multislot_file (tests): returns
    (num_rows, [(counts, values) per slot]) or None."""
    mf = open_multislot_file(path, slot_is_float)
    if mf is None:
        return None
    with mf:
        return mf.rows, [mf.slot_batch(j, 0, mf.rows)
                         for j in range(len(slot_is_float))]


class MultiSlotFile:
    """Handle over a natively-parsed slot file; batches are copied out
    one row-range at a time (the parsed data lives once in the C++
    vectors — no whole-file numpy duplicate). Use as a context manager
    or call close()."""

    def __init__(self, handle, n_slots, slot_is_float):
        self._h = handle
        self._n = n_slots
        self._is_float = list(slot_is_float)
        self.rows = lib().msf_num_rows(handle)

    def slot_batch(self, j, r0, r1):
        """(counts int64[r1-r0], values np[range total]) for slot j."""
        import ctypes

        import numpy as np

        l = lib()
        counts = np.empty(r1 - r0, np.int64)
        if r1 > r0:
            l.msf_counts_range(
                self._h, j, r0, r1,
                counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        total = l.msf_range_total(self._h, j, r0, r1)
        if self._is_float[j]:
            vals = np.empty(total, np.float32)
            if total:
                l.msf_values_f_range(
                    self._h, j, r0, r1,
                    vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        else:
            vals = np.empty(total, np.int64)
            if total:
                l.msf_values_i_range(
                    self._h, j, r0, r1,
                    vals.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        return counts, vals

    def close(self):
        if self._h is not None:
            lib().msf_free(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def open_multislot_file(path, slot_is_float):
    """Parse a MultiSlotDataFeed file natively; returns a MultiSlotFile
    handle or None (no toolchain / parse error -> Python fallback)."""
    import ctypes

    l = lib()
    if l is None:
        return None
    n = len(slot_is_float)
    mask = (ctypes.c_uint8 * n)(*[1 if f else 0 for f in slot_is_float])
    h = l.msf_parse_file(path.encode(), n, mask)
    if not h:
        return None
    return MultiSlotFile(h, n, slot_is_float)
