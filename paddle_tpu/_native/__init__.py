"""Native (C++) runtime components, ctypes-bound, with build-on-demand.

Parity: the reference's C++ runtime around the compute path — reader
BlockingQueues/buffered readers and data_feed text processing
(paddle/fluid/operators/reader/, paddle/fluid/framework/data_feed.cc).
The library is compiled from csrc/ on first use (g++, cached as
libpaddle_tpu_native.so next to this file, never committed); every consumer
has a pure-Python fallback so the framework works without a toolchain — but
a build that was tried and failed says so (RuntimeWarning with the
compiler's output), once.
"""
import ctypes
import os
import subprocess
import threading
import warnings

_HERE = os.path.dirname(os.path.abspath(__file__))
_CSRC = os.path.normpath(os.path.join(_HERE, '..', '..', 'csrc'))
_LIB_PATH = os.path.join(_HERE, 'libpaddle_tpu_native.so')

_lock = threading.Lock()
_lib = None
_tried = False


def _build():
    srcs = [os.path.join(_CSRC, f)
            for f in ('prefetch.cpp', 'tokenizer.cpp',
                      'multislot.cpp')]
    if not all(os.path.exists(s) for s in srcs):
        return False
    cmd = ['g++', '-O2', '-std=c++17', '-fPIC', '-Wall', '-pthread',
           '-shared', '-o', _LIB_PATH] + srcs
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, 'stderr', b'') or b''
        warnings.warn(
            "paddle_tpu._native: building %s from csrc/ failed (%r); the "
            "pure-Python paths are used instead.\n%s"
            % (os.path.basename(_LIB_PATH), e,
               detail.decode('utf-8', 'replace')[-2000:]),
            RuntimeWarning)
        return False


def load():
    """Returns the loaded CDLL or None (no toolchain / build failure)."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_LIB_PATH):
            stale = True
        else:
            stale = any(
                os.path.getmtime(os.path.join(_CSRC, f)) >
                os.path.getmtime(_LIB_PATH)
                for f in ('prefetch.cpp', 'tokenizer.cpp', 'multislot.cpp')
                if os.path.exists(os.path.join(_CSRC, f)))
        # graftlint: disable=GC003 — holding _lock through the g++ build
        # is the point: concurrent first-callers must wait for the one
        # shared artifact rather than race a second compile, and there is
        # nothing useful to do after releasing early.
        if stale and not _build():
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            return None
        # tokenizer
        lib.vocab_create.restype = ctypes.c_void_p
        lib.vocab_destroy.argtypes = [ctypes.c_void_p]
        lib.vocab_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_int]
        lib.vocab_set_unk.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.vocab_size.restype = ctypes.c_int
        lib.vocab_size.argtypes = [ctypes.c_void_p]
        lib.vocab_lookup.restype = ctypes.c_int
        lib.vocab_lookup.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.tokenize_ids.restype = ctypes.c_int
        lib.tokenize_ids.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_int,
                                     ctypes.POINTER(ctypes.c_int32),
                                     ctypes.c_int]
        lib.wordpiece_ids.restype = ctypes.c_int
        lib.wordpiece_ids.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_int,
                                      ctypes.POINTER(ctypes.c_int32),
                                      ctypes.c_int, ctypes.c_char_p,
                                      ctypes.c_int]
        _lib = lib
        return _lib


def available():
    return load() is not None
