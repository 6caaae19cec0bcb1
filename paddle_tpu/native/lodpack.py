"""ctypes binding for liblodpack.so — the padded-dense LoD layout
conversion (per-step host hot path for every sequence feed).

Caller: core/lod.py LoDTensor.to_padded (pack). unpack() is the reverse
conversion for host-side consumers of padded results (currently exercised
by tests; kept next to pack so the two contracts evolve together). Both
return False/None when the native library is unavailable or the arrays
aren't native-packable, and the caller falls back to numpy.
"""
import ctypes

import numpy as np

from . import load_library

__all__ = ["available", "pack_into", "unpack"]


def _lib():
    lib = load_library("lodpack")
    if lib is None:
        return None
    if not getattr(lib, "_lodpack_ready", False):
        i64p = ctypes.POINTER(ctypes.c_int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.ptpu_lod_pack.argtypes = [
            ctypes.c_char_p, i64p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_char_p]
        lib.ptpu_lod_pack.restype = ctypes.c_int
        lib.ptpu_lod_unpack.argtypes = [
            ctypes.c_char_p, i32p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_char_p]
        lib.ptpu_lod_unpack.restype = ctypes.c_int64
        lib._lodpack_ready = True
    return lib


def available():
    return _lib() is not None


def pack_into(data, offs, out):
    """Pack flat ragged `data` (row offsets `offs`, len n_seqs+1) into the
    pre-zeroed padded array `out` [n_seqs, max_len, *feat]. Returns True
    when the native path ran; False -> caller must use its fallback."""
    lib = _lib()
    if lib is None:
        return False
    data = np.ascontiguousarray(data)
    if not out.flags["C_CONTIGUOUS"] or data.dtype != out.dtype \
            or out.dtype.hasobject:
        return False  # object dtypes hold PyObject*; memcpy would corrupt
    n_seqs, max_len = out.shape[0], out.shape[1]
    row_bytes = int(np.prod(out.shape[2:], dtype=np.int64)) * out.itemsize
    offs_arr = np.ascontiguousarray(np.asarray(offs, dtype=np.int64))
    if offs_arr.shape != (n_seqs + 1,):
        return False  # C loop indexes offs[0..n_seqs]; never read past it
    rc = lib.ptpu_lod_pack(
        data.ctypes.data_as(ctypes.c_char_p),
        offs_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int64(n_seqs), ctypes.c_int64(data.shape[0]),
        ctypes.c_int64(max_len),
        ctypes.c_int64(row_bytes), out.ctypes.data_as(ctypes.c_char_p))
    return rc == 0


def unpack(padded, lengths):
    """Padded [n_seqs, max_len, *feat] + lengths -> flat ragged
    [sum(lengths), *feat] array, or None when native is unavailable."""
    lib = _lib()
    if lib is None:
        return None
    padded = np.ascontiguousarray(padded)
    if padded.dtype.hasobject:
        return None
    lengths = np.ascontiguousarray(np.asarray(lengths, dtype=np.int32))
    n_seqs, max_len = padded.shape[0], padded.shape[1]
    if lengths.shape != (n_seqs,):
        return None  # C writes one block per seq; out is sized from lengths
    if len(lengths) and (lengths.min() < 0 or int(lengths.max()) > max_len):
        return None  # a bad length must never reach memcpy: out is sized
                     # from sum(lengths), so one oversized/negative entry
                     # would overflow it before the C-side check fires
    feat = padded.shape[2:]
    row_bytes = int(np.prod(feat, dtype=np.int64)) * padded.itemsize
    total = int(lengths.sum())
    out = np.empty((total,) + feat, dtype=padded.dtype)
    rows = lib.ptpu_lod_unpack(
        padded.ctypes.data_as(ctypes.c_char_p),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_int64(n_seqs), ctypes.c_int64(max_len),
        ctypes.c_int64(row_bytes), out.ctypes.data_as(ctypes.c_char_p))
    if rows != total:
        return None
    return out
