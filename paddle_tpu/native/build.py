"""Lazy g++ build + ctypes loader for the native host runtime.

The built library is named after a hash of ``dataloader.cpp``
(``libpaddle_tpu_native-<sha>.so``), so a library left on disk by another
version of the source, or carried along by a copy that reorders mtimes,
is never loaded: a checkout that holds only what git commits builds its
own on first use.
"""
import ctypes
import glob
import hashlib
import os
import shutil
import subprocess
import threading
import warnings

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "dataloader.cpp")
_lock = threading.Lock()
_lib = None
_status = None   # "built" | "loaded" | "absent" once load_native has run


def _lib_path(tag=""):
    with open(_SRC, "rb") as f:
        sha = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_HERE, "libpaddle_tpu_native%s-%s.so" % (tag, sha))


def _compile(lib_path, extra_flags=()):
    cmd = [
        "g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-pthread",
        *extra_flags, _SRC, "-o", lib_path + ".tmp%d" % os.getpid(),
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode:
        raise RuntimeError(
            "native runtime build failed (%s):\n%s"
            % (" ".join(cmd), proc.stderr[-2000:]))
    os.replace(cmd[-1], lib_path)   # concurrent builders race benignly
    for stale in glob.glob(lib_path.rsplit("-", 1)[0] + "-*.so"):
        if stale != lib_path:
            os.remove(stale)


def build_tsan():
    """Race-detection build of the native runtime (aux subsystem: the
    reference's CI runs its C++ under sanitizers; here
    -fsanitize=thread covers the slot ring + worker pool). Returns the
    .so path; load it in a TSAN_OPTIONS-configured process to check for
    data races in the pipe/queue/arena paths."""
    path = _lib_path("_tsan")
    if not os.path.exists(path):
        _compile(path, ("-fsanitize=thread", "-O1", "-g"))
    return path


def status():
    """How the last :func:`load_native` got the runtime: ``"built"``
    (compiled by this process), ``"loaded"`` (the library for this
    source hash was already on disk), ``"absent"`` (no g++; the
    pure-python queue serves), or None before the first call."""
    return _status


def load_native():
    """Return the ctypes lib, building it on first call. None — with a
    warning — only where there is no g++ to build it with; a build or
    load that fails raises."""
    global _lib, _status
    with _lock:
        if _status is not None:
            return _lib
        path = _lib_path()
        how = "loaded"
        if not os.path.exists(path):
            if shutil.which("g++") is None:
                _status = "absent"
                warnings.warn(
                    "paddle_tpu native runtime absent: no g++ on PATH to "
                    "build %s; data pipelines use the pure-python queue"
                    % _SRC, RuntimeWarning)
                return None
            _compile(path)
            how = "built"
        lib = ctypes.CDLL(path)
        lib.ptq_create.restype = ctypes.c_void_p
        lib.ptq_create.argtypes = [ctypes.c_int]
        lib.ptq_put.argtypes = [ctypes.c_void_p, ctypes.c_long]
        lib.ptq_get.restype = ctypes.c_long
        lib.ptq_get.argtypes = [ctypes.c_void_p]
        lib.ptq_destroy.argtypes = [ctypes.c_void_p]
        lib.arena_create.restype = ctypes.c_void_p
        lib.arena_create.argtypes = [ctypes.c_size_t]
        lib.arena_is_locked.restype = ctypes.c_int
        lib.arena_is_locked.argtypes = [ctypes.c_void_p]
        lib.arena_alloc.restype = ctypes.c_void_p
        lib.arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
        lib.arena_reset.argtypes = [ctypes.c_void_p]
        lib.arena_destroy.argtypes = [ctypes.c_void_p]
        lib.pipe_create.restype = ctypes.c_void_p
        lib.pipe_create.argtypes = [
            ctypes.c_int, ctypes.c_size_t, ctypes.c_int,
        ]
        lib.pipe_is_pinned.restype = ctypes.c_int
        lib.pipe_is_pinned.argtypes = [ctypes.c_void_p]
        lib.pipe_acquire_write.restype = ctypes.c_int
        lib.pipe_acquire_write.argtypes = [ctypes.c_void_p]
        lib.pipe_slot_ptr.restype = ctypes.c_void_p
        lib.pipe_slot_ptr.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.pipe_write.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_size_t,
        ]
        lib.pipe_submit_write.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_size_t,
        ]
        lib.pipe_wait_writes.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.pipe_commit.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.pipe_acquire_read.restype = ctypes.c_int
        lib.pipe_acquire_read.argtypes = [ctypes.c_void_p]
        lib.pipe_release.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.pipe_abort.argtypes = [ctypes.c_void_p]
        lib.pipe_reset.argtypes = [ctypes.c_void_p]
        lib.pipe_destroy.argtypes = [ctypes.c_void_p]
        _lib, _status = lib, how
        return _lib
