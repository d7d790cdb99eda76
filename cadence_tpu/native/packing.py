"""High-level native packing API: serialized histories → lane tensors."""
from __future__ import annotations

import ctypes
from typing import Optional, Sequence

import numpy as np

from ..ops.encode import NUM_LANES
from ..utils.concurrency import pack_threads
from . import build as _build


def native_available() -> bool:
    return _build.load() is not None


def blob_offsets(blobs: Sequence[bytes]):
    """Join W serialized histories into the (blob, offsets[W + 1]) call
    frame every native corpus entry point takes — ONE implementation so
    the packer ABI has a single Python-side counterpart."""
    blob = b"".join(blobs)
    offsets = np.zeros(len(blobs) + 1, dtype=np.int64)
    np.cumsum([len(b) for b in blobs], out=offsets[1:])
    return blob, offsets


def raise_pack_error(rc: int) -> None:
    """Decode a native packer failure (-(workflow+1)*1000 - err) into
    the typed ValueError — shared by every caller of the corpus entry
    points so the error-code table can't drift per call site."""
    workflow = (-rc) // 1000 - 1
    err = (-rc) % 1000
    raise ValueError(
        f"native packer failed on workflow {workflow} (code {err}: "
        f"1=truncated, 2=unknown attr, 3=history exceeds max_events)")


def pack_serialized(blobs: Sequence[bytes], max_events: int,
                    num_threads: Optional[int] = None,
                    out: Optional[np.ndarray] = None) -> np.ndarray:
    """Pack W serialized histories (core/codec.py wire bytes) into
    [W, max_events, NUM_LANES] int64 with the native packer.

    Pass a preallocated `out` to amortize page-fault cost in streaming
    pipelines (the packer fully overwrites it — real rows and padding)."""
    lib = _build.load()
    if lib is None:
        raise RuntimeError("native packer unavailable (no C++ toolchain)")
    num_threads = pack_threads(num_threads, cap=max(1, len(blobs)))
    W = len(blobs)
    blob, offsets = blob_offsets(blobs)
    if out is None:
        out = np.empty((W, max_events, NUM_LANES), dtype=np.int64)
    else:
        assert out.shape == (W, max_events, NUM_LANES) and out.dtype == np.int64
    rc = lib.cadence_pack_corpus(
        blob,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        W, max_events, NUM_LANES,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        num_threads,
    )
    if rc < 0:
        raise_pack_error(rc)
    return out
