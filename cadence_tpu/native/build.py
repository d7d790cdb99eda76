"""Build + load the native libraries (ctypes, g++, cached by source hash).

No pip/pybind11 in this environment — the C ABI via ctypes is the binding
layer. A shared object is rebuilt only when its sources change. The
build directory is git-ignored, so a fresh checkout compiles each
library once, on first use, and many processes may reach that first use
together (test workers, the service hosts of one wire cluster): each
compiles to a temporary name of its own and renames it into place, so
no process ever loads a half-written file.

Loading returns None only where there is no toolchain at all (no `g++`
on PATH) — callers then use the pure-Python packer. With a toolchain, a
compile or load that fails is raised: a host must not lose its native
encoder silently.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "packer.cc")
_SRC_GEN = os.path.join(_DIR, "generator.cc")
_SRC_WIREC = os.path.join(_DIR, "wirec.cc")
_BUILD_DIR = os.path.join(_DIR, "_build")

_lock = threading.Lock()
_cached: dict = {}


def _so_path(src: str, stem: str, deps: tuple = ()) -> str:
    """Cache key: the .so name carries a hash of the source AND every
    #include'd sibling, so editing either triggers exactly one rebuild
    and an unchanged tree never recompiles across test sessions."""
    h = hashlib.sha256()
    for path in (src,) + deps:
        with open(path, "rb") as f:
            h.update(f.read())
    return os.path.join(_BUILD_DIR, f"lib{stem}_{h.hexdigest()[:16]}.so")


def _build_src(src: str, stem: str, verbose: bool = False,
               deps: tuple = ()) -> str:
    """Compile one source if needed; returns the .so path. Safe under
    concurrent first use across processes and threads: the compiler
    writes a name only this caller uses, and the atomic rename
    publishes a complete file (every racer's output is identical, so
    whichever rename lands last changes nothing)."""
    so = _so_path(src, stem, deps)
    if os.path.exists(so):
        return so
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{so}.{os.getpid()}.{threading.get_ident()}.tmp"
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        "-o", tmp, src,
    ]
    if verbose:
        print("+", " ".join(cmd))
    try:
        proc = subprocess.run(cmd, capture_output=not verbose, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"g++ failed (rc={proc.returncode}) building {stem} from "
                f"{src}:\n{proc.stderr or ''}")
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so


def build(verbose: bool = False) -> str:
    return _build_src(_SRC, "cadence_packer", verbose)


def _load_lib(src: str, stem: str, configure,
              deps: tuple = ()) -> Optional[ctypes.CDLL]:
    with _lock:
        if stem in _cached:
            return _cached[stem]
        so = _so_path(src, stem, deps)
        if not os.path.exists(so) and shutil.which("g++") is None:
            return None  # genuinely no toolchain: pure-Python callers
        lib = ctypes.CDLL(_build_src(src, stem, deps=deps))
        configure(lib)
        _cached[stem] = lib
        return lib


def load() -> Optional[ctypes.CDLL]:
    """Load the packer (building if necessary); None without a toolchain."""
    def configure(lib):
        lib.cadence_pack_corpus.restype = ctypes.c_int64
        lib.cadence_pack_corpus.argtypes = [
            ctypes.c_char_p,                  # blob
            ctypes.POINTER(ctypes.c_int64),   # offsets
            ctypes.c_int64,                   # num_workflows
            ctypes.c_int64,                   # max_events
            ctypes.c_int64,                   # num_lanes
            ctypes.POINTER(ctypes.c_int64),   # out
            ctypes.c_int64,                   # num_threads
        ]
    return _load_lib(_SRC, "cadence_packer", configure)


def wirec_cached() -> bool:
    """True when the native wirec .so is ALREADY BUILT for the current
    sources — a file-hash probe that never shells out to the compiler,
    so boot paths (ServiceHost gauge pre-registration) can report
    availability without blocking startup on a g++ run."""
    try:
        return os.path.exists(_so_path(_SRC_WIREC, "cadence_wirec",
                                       deps=(_SRC,)))
    except OSError:
        return False


def load_wirec() -> Optional[ctypes.CDLL]:
    """Load the native wirec encoder (wirec.cc includes packer.cc, so
    the cache digest spans both); None without a toolchain."""
    I64P = ctypes.POINTER(ctypes.c_int64)
    U8P = ctypes.POINTER(ctypes.c_uint8)
    I32P = ctypes.POINTER(ctypes.c_int32)

    def configure(lib):
        # packer.cc rides inside wirec.cc, so its corpus entry point is
        # exported from this .so too — declare the 64-bit ABI here as
        # well (ctypes defaults would truncate the int64 args/return)
        lib.cadence_pack_corpus.restype = ctypes.c_int64
        lib.cadence_pack_corpus.argtypes = [
            ctypes.c_char_p, I64P,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            I64P, ctypes.c_int64,
        ]
        lib.cadence_wirec_measure.restype = ctypes.c_int64
        lib.cadence_wirec_measure.argtypes = [
            I64P,                             # lanes [W, E, L]
            ctypes.c_int64,                   # W
            ctypes.c_int64,                   # E
            ctypes.c_int64,                   # L
            I64P, I64P, I64P, I64P,           # kinds/widths/scales/consts
            ctypes.c_int64,                   # num_threads
        ]
        lib.cadence_wirec_measure_blobs.restype = ctypes.c_int64
        lib.cadence_wirec_measure_blobs.argtypes = [
            ctypes.c_char_p,                  # blob
            I64P,                             # offsets [W + 1]
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,  # W, E, L
            I64P, I64P, I64P, I64P,           # kinds/widths/scales/consts
            ctypes.c_int64,                   # num_threads
        ]
        lib.cadence_wirec_emit.restype = ctypes.c_int64
        lib.cadence_wirec_emit.argtypes = [
            I64P,                             # lanes
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,  # W, E, L
            I64P, I64P, I64P, I64P, I64P, I64P, I64P,  # profile columns
            ctypes.c_int64,                   # P
            ctypes.c_int64, ctypes.c_int64,   # B, K
            U8P,                              # slab [W, E, B]
            I64P,                             # bases [W, K]
            I32P,                             # n_events [W]
            ctypes.c_int64,                   # num_threads
        ]
        lib.cadence_wirec_pack_fused.restype = ctypes.c_int64
        lib.cadence_wirec_pack_fused.argtypes = [
            ctypes.c_char_p,                  # blob
            I64P,                             # offsets [W + 1]
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,  # W, E, L
            I64P, I64P, I64P, I64P, I64P, I64P, I64P,  # profile columns
            ctypes.c_int64,                   # P
            ctypes.c_int64, ctypes.c_int64,   # B, K
            U8P,                              # slab
            I64P,                             # bases
            I32P,                             # n_events
            I64P,                             # misfit_out [1]
            ctypes.c_int64,                   # num_threads
        ]
    return _load_lib(_SRC_WIREC, "cadence_wirec", configure, deps=(_SRC,))


def load_generator() -> Optional[ctypes.CDLL]:
    """Load the native corpus generator; None without a toolchain."""
    def configure(lib):
        lib.cadence_generate_corpus.restype = ctypes.c_int64
        lib.cadence_generate_corpus.argtypes = [
            ctypes.c_uint64,                  # seed
            ctypes.c_int64,                   # first_index
            ctypes.c_int64,                   # num_workflows
            ctypes.c_int64,                   # max_events
            ctypes.c_int64,                   # num_lanes
            ctypes.POINTER(ctypes.c_int64),   # out
            ctypes.c_int64,                   # num_threads
        ]
    return _load_lib(_SRC_GEN, "cadence_generator", configure)
