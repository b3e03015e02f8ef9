"""Native host-side data plane (the analog of the reference's src/main/cpp
tier, loaded there via System.loadLibrary — utils/external/VLFeat.scala:4).

The C++ sources here are built on demand with g++ into a shared library inside
the package directory and bound via ctypes. The build is keyed on the
sources' CONTENT: a SHA-256 of the sources is recorded beside the library and
a library whose recorded digest differs (or is missing) is rebuilt — file
times mean nothing in a copied checkout. With no compiler the pure-NumPy/PIL
paths serve instead, and :func:`status` says which side is serving.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libkeystone_native.so")
_DIGEST_PATH = _LIB_PATH + ".sha256"
_SOURCES = [
    os.path.join(_DIR, "csv_loader.cpp"),
    os.path.join(_DIR, "data_plane.cpp"),
]

_lib: Optional[ctypes.CDLL] = None
_tried = False


def _sources_digest() -> str:
    h = hashlib.sha256()
    for src in _SOURCES:
        with open(src, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _recorded_digest() -> Optional[str]:
    try:
        with open(_DIGEST_PATH) as f:
            return f.read().strip()
    except FileNotFoundError:
        return None


def _build(digest: str) -> bool:
    """Compile to a private name, then rename into place and record the
    digest — concurrent first users (fleet planes, prefetch readers) each
    publish a complete library, never a half-written one."""
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-o", tmp] + _SOURCES
    try:
        res = subprocess.run(cmd, capture_output=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning("native data plane not built (%s)", e)
        return False
    if res.returncode != 0:
        logger.warning(
            "native data plane not built (g++ exit %d): %s",
            res.returncode, res.stderr.decode("utf-8", "replace")[-500:],
        )
        return False
    os.replace(tmp, _LIB_PATH)
    with open(_DIGEST_PATH, "w") as f:
        f.write(digest + "\n")
    return True


def status() -> str:
    """``"native"`` when the C++ data plane is serving, else ``"numpy"``."""
    return "native" if get_lib() is not None else "numpy"


def get_lib() -> Optional[ctypes.CDLL]:
    """The native library, building it on first use; None if unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    try:
        digest = _sources_digest()
        if not os.path.exists(_LIB_PATH) or _recorded_digest() != digest:
            if not _build(digest):
                return None
        lib = ctypes.CDLL(_LIB_PATH)
        lib.ks_parse_csv.restype = ctypes.c_long
        lib.ks_parse_csv.argtypes = [
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_double),
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long),
        ]
        lib.ks_split_records.restype = None
        lib.ks_split_records.argtypes = [
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_float),
        ]
        lib.ks_parse_csv_many.restype = None
        lib.ks_parse_csv_many.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_long),
            ctypes.c_long,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long),
        ]
        lib.ks_decode_pnm_many.restype = None
        lib.ks_decode_pnm_many.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_long),
            ctypes.c_long,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long),
        ]
        lib.ks_decode_pnm.restype = ctypes.c_int
        lib.ks_decode_pnm.argtypes = [
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long),
        ]
        _lib = lib
    except OSError as e:
        logger.warning("native data plane not loaded (%s)", e)
        _lib = None
    return _lib


def _csv_max_vals(text: bytes) -> int:
    """Upper bound on the value count of a CSV buffer: every value is
    preceded by a separator (incl. CR, which the parser skips) or starts the
    buffer."""
    return (
        text.count(b",")
        + text.count(b"\n")
        + text.count(b" ")
        + text.count(b"\t")
        + text.count(b"\r")
        + 2
    )


def parse_csv_floats(text: bytes) -> Tuple[np.ndarray, int, int]:
    """Parse a CSV byte buffer into (flat float64 values, num_columns,
    num_rows). Uses the native parser when available, else a NumPy fallback.
    Callers should validate values.size == num_rows * num_columns to reject
    ragged input."""
    lib = get_lib()
    if lib is not None:
        max_vals = _csv_max_vals(text)
        out = np.empty(max_vals, dtype=np.float64)
        ncols = ctypes.c_long(0)
        nrows = ctypes.c_long(0)
        n = lib.ks_parse_csv(
            text,
            len(text),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            max_vals,
            ctypes.byref(ncols),
            ctypes.byref(nrows),
        )
        return out[:n].copy(), int(ncols.value), int(nrows.value)
    # Fallback
    rows = [r for r in text.decode("utf-8", "ignore").splitlines() if r.strip()]
    vals = []
    ncols = 0
    for r in rows:
        parts = [p for p in r.replace(",", " ").split() if p]
        if not ncols:
            ncols = len(parts)
        vals.extend(float(p) for p in parts)
    return np.asarray(vals, dtype=np.float64), ncols, len(rows)


def decode_pnm(data: bytes) -> Optional[np.ndarray]:
    """Decode binary PPM/PGM bytes to a float32 (x, y, c) array via the
    native decoder; None if the library is unavailable or decoding fails."""
    lib = get_lib()
    if lib is None:
        return None
    max_vals = len(data) * 3
    out = np.empty(max_vals, dtype=np.float32)
    x = ctypes.c_long(0)
    y = ctypes.c_long(0)
    c = ctypes.c_long(0)
    rc = lib.ks_decode_pnm(
        data,
        len(data),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        max_vals,
        ctypes.byref(x),
        ctypes.byref(y),
        ctypes.byref(c),
    )
    if rc != 0:
        return None
    n = x.value * y.value * c.value
    return out[:n].copy().reshape(x.value, y.value, c.value)


def split_records(
    buf: bytes,
    label_bytes: int,
    channels: int,
    height: int,
    width: int,
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Deinterleave CIFAR-style fixed records [label_bytes | planar pixels]
    into (int64 labels, float32 HWC images) with a threaded native loop;
    None when the native library is unavailable. The last label byte is used
    (CIFAR-10's only byte; CIFAR-100's fine label)."""
    if label_bytes < 1:
        raise ValueError("label_bytes must be >= 1")
    lib = get_lib()
    if lib is None:
        return None
    img_bytes = channels * height * width
    rec = label_bytes + img_bytes
    if len(buf) % rec != 0:
        raise ValueError(f"buffer not a multiple of record size {rec}")
    n = len(buf) // rec
    labels = np.empty(n, dtype=np.int64)
    images = np.empty((n, height, width, channels), dtype=np.float32)
    lib.ks_split_records(
        buf,
        n,
        label_bytes,
        channels,
        height,
        width,
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        images.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    return labels, images


def parse_csv_floats_many(texts) -> Optional[list]:
    """Parse many CSV byte buffers concurrently via the native thread pool.
    Returns a list of (flat values, num_columns, num_rows) or None when the
    native library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(texts)
    if n == 0:
        return []
    bufs = (ctypes.c_char_p * n)(*texts)
    lens = (ctypes.c_long * n)(*[len(t) for t in texts])
    max_vals_list = [_csv_max_vals(t) for t in texts]
    outs_np = [np.empty(m, dtype=np.float64) for m in max_vals_list]
    outs = (ctypes.POINTER(ctypes.c_double) * n)(
        *[o.ctypes.data_as(ctypes.POINTER(ctypes.c_double)) for o in outs_np]
    )
    max_vals = (ctypes.c_long * n)(*max_vals_list)
    counts = (ctypes.c_long * n)()
    ncols = (ctypes.c_long * n)()
    nrows = (ctypes.c_long * n)()
    lib.ks_parse_csv_many(bufs, lens, n, outs, max_vals, counts, ncols, nrows)
    return [
        (outs_np[i][: counts[i]].copy(), int(ncols[i]), int(nrows[i]))
        for i in range(n)
    ]


def decode_pnm_many(datas) -> Optional[list]:
    """Decode many binary PNM buffers concurrently via the native thread
    pool. Returns a list of float32 (h, w, c) arrays (None per item that
    failed to decode), or None when the native library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(datas)
    if n == 0:
        return []
    bufs = (ctypes.c_char_p * n)(*datas)
    lens = (ctypes.c_long * n)(*[len(d) for d in datas])
    max_vals_list = [len(d) * 3 for d in datas]
    outs_np = [np.empty(m, dtype=np.float32) for m in max_vals_list]
    outs = (ctypes.POINTER(ctypes.c_float) * n)(
        *[o.ctypes.data_as(ctypes.POINTER(ctypes.c_float)) for o in outs_np]
    )
    max_vals = (ctypes.c_long * n)(*max_vals_list)
    xs = (ctypes.c_long * n)()
    ys = (ctypes.c_long * n)()
    cs = (ctypes.c_long * n)()
    rcs = (ctypes.c_long * n)()
    lib.ks_decode_pnm_many(bufs, lens, n, outs, max_vals, xs, ys, cs, rcs)
    results = []
    for i in range(n):
        if rcs[i] != 0:
            results.append(None)
            continue
        count = xs[i] * ys[i] * cs[i]
        results.append(outs_np[i][:count].copy().reshape(xs[i], ys[i], cs[i]))
    return results
