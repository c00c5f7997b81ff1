"""Native wire codec: lazy g++ build + ctypes binding, Python fallback.

The C++ side (fastcodec.cpp) parses/serializes the ndarray number matrix —
the dominant CPU cost of a REST prediction once the graph runs in-process.
This module compiles it on first use (cached .so next to the source,
rebuilt whenever the .cpp's content hash differs from the one the .so was
built from) and exposes:

    find_ndarray_span(raw: bytes) -> (start, end) | None
    parse_ndarray(raw: bytes) -> np.ndarray (float32, 1D or 2D) | None
    encode_ndarray(arr) -> bytes | None
    pad_rows(arr, bucket) -> np.ndarray

Every entry returns None (or falls back to numpy) when the library is
unavailable or the payload isn't a rectangular numeric array — callers keep
the pure-Python path as the semantic source of truth.
"""

from __future__ import annotations

import contextlib
import ctypes
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

log = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "fastcodec.cpp")
_SO = os.path.join(_HERE, "_fastcodec.so")
# sha256 of the .cpp the .so was built from. File times say nothing in a
# copied or checked-out tree (a stale .so can be newer than an edited
# source), so freshness is decided by content.
_SO_STAMP = _SO + ".src-sha256"

_lib = None
_lib_lock = threading.Lock()
_build_failed = False


def _src_digest() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _stamped_digest() -> str | None:
    try:
        with open(_SO_STAMP) as f:
            return f.read().strip()
    except OSError:
        return None


def _build() -> str | None:
    try:
        digest = _src_digest()
        if os.path.exists(_SO) and _stamped_digest() == digest:
            return _SO
        # pid-unique temp name: concurrent processes (platform + microservice
        # on one host) may both build; a shared .tmp path would interleave
        # writes and os.replace could install a corrupt .so
        tmp = f"{_SO}.tmp.{os.getpid()}"
        try:
            res = subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC],
                capture_output=True,
                timeout=120,
            )
            if res.returncode != 0:
                log.warning("fastcodec build failed: %s", res.stderr.decode()[:500])
                return None
            # stamp AFTER the .so is in place, via its own atomic rename: a
            # crash between the two leaves a stamp-less (= rebuilt) .so,
            # never a stamp that vouches for an older binary
            with contextlib.suppress(OSError):
                os.unlink(_SO_STAMP)
            os.replace(tmp, _SO)
            with open(tmp, "w") as f:
                f.write(digest + "\n")
            os.replace(tmp, _SO_STAMP)
        finally:
            # failed/timed-out builds must not strand pid-unique temp files
            # in the package dir (they are never overwritten by later pids)
            with contextlib.suppress(OSError):
                os.unlink(tmp)
        return _SO
    except Exception as e:  # noqa: BLE001 - no compiler / RO filesystem
        log.warning("fastcodec build unavailable: %s", e)
        return None


def get_lib():
    """The loaded library or None. Thread-safe, builds at most once."""
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        path = _build()
        if path is None:
            _build_failed = True
            return None
        lib = ctypes.CDLL(path)
        lib.ndarray_find.restype = ctypes.c_int
        lib.ndarray_find.argtypes = [
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long),
        ]
        lib.ndarray_probe.restype = ctypes.c_int
        lib.ndarray_probe.argtypes = [
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.ndarray_parse.restype = ctypes.c_int
        lib.ndarray_parse.argtypes = [
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_long,
            ctypes.c_long,
        ]
        lib.ndarray_encode.restype = ctypes.c_long
        lib.ndarray_encode.argtypes = [
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_char_p,
            ctypes.c_long,
        ]
        lib.pad_rows_f32.restype = ctypes.c_int
        lib.pad_rows_f32.argtypes = [
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_float),
        ]
        lib.http_parse_head.restype = ctypes.c_long
        lib.http_parse_head.argtypes = [
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_long),  # method_len
            ctypes.POINTER(ctypes.c_long),  # path_off
            ctypes.POINTER(ctypes.c_long),  # path_len
            ctypes.POINTER(ctypes.c_longlong),  # content_length
            ctypes.POINTER(ctypes.c_long),  # flags
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_long),  # ctype
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_long),  # auth
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None


def find_ndarray_span(raw: bytes) -> tuple[int, int] | None:
    lib = get_lib()
    if lib is None:
        return None
    start, end = ctypes.c_long(), ctypes.c_long()
    rc = lib.ndarray_find(raw, len(raw), ctypes.byref(start), ctypes.byref(end))
    if rc != 0:
        return None
    return start.value, end.value


def parse_ndarray(raw: bytes) -> np.ndarray | None:
    """Parse a JSON 1D/2D numeric array (bytes) to float32. None on any
    deviation (ragged, strings, nesting >2) — caller falls back to json."""
    lib = get_lib()
    if lib is None:
        return None
    rows, cols = ctypes.c_long(), ctypes.c_long()
    is2d = ctypes.c_int()
    rc = lib.ndarray_probe(
        raw, len(raw), ctypes.byref(rows), ctypes.byref(cols), ctypes.byref(is2d)
    )
    if rc != 0:
        return None
    r, c = rows.value, cols.value
    out = np.empty(r * c, dtype=np.float32)
    if r * c:
        rc = lib.ndarray_parse(
            raw, len(raw), out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), r, c
        )
        if rc != 0:
            return None
    return out.reshape(r, c) if is2d.value else out.reshape(c)


def encode_ndarray(arr: np.ndarray) -> bytes | None:
    """float32 2D matrix -> JSON bytes ('[[...],[...]]'). None if lib absent
    or array not 2D float-convertible."""
    lib = get_lib()
    if lib is None or arr.ndim != 2:
        return None
    a = np.ascontiguousarray(arr, dtype=np.float32)
    cap = a.size * 32 + a.shape[0] * 2 + 16
    buf = ctypes.create_string_buffer(cap)
    n = lib.ndarray_encode(
        a.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        a.shape[0],
        a.shape[1],
        buf,
        cap,
    )
    if n < 0:
        return None
    return buf.raw[:n]


def pad_rows(arr: np.ndarray, bucket: int) -> np.ndarray:
    """Zero-pad the batch axis to ``bucket`` (C memcpy when available)."""
    a = np.ascontiguousarray(arr, dtype=np.float32)
    n, feat = a.shape[0], int(np.prod(a.shape[1:], initial=1))
    lib = get_lib()
    if lib is None:
        out = np.zeros((bucket, *a.shape[1:]), dtype=np.float32)
        out[:n] = a
        return out
    out = np.empty((bucket, *a.shape[1:]), dtype=np.float32)
    rc = lib.pad_rows_f32(
        a.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n,
        feat,
        bucket,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    if rc != 0:
        raise ValueError(f"pad_rows: batch {n} exceeds bucket {bucket}")
    return out


# HTTP head-parse flag bits (mirror fastcodec.cpp)
HDRF_HAS_CTYPE = 1
HDRF_CONN_CLOSE = 2
HDRF_HAS_TE = 4  # Transfer-Encoding header present (any value)
HDRF_HAS_CLEN = 8


class ParsedHead:
    """One parsed HTTP/1.1 request head (C fast path)."""

    __slots__ = ("body_start", "method", "path", "content_length", "flags",
                 "content_type", "authorization")

    def __init__(self, body_start, method, path, content_length, flags,
                 content_type, authorization):
        self.body_start = body_start
        self.method = method
        self.path = path
        self.content_length = content_length  # -1 when header absent
        self.flags = flags
        self.content_type = content_type  # raw value or None
        self.authorization = authorization  # raw value or None


# single source of truth for the head-parse out-buffer capacities: the
# scratch allocation, the caps passed to C, and the truncation checks must
# move together (a cap raised past the allocation would make the C memcpy a
# heap overflow)
_CTYPE_CAP = 512
_AUTH_CAP = 4096

_parse_tls = threading.local()


def _parse_scratch():
    """Per-thread reusable ctypes out-params for parse_http_head: the hot
    path calls it once per request, and allocating two string buffers plus
    eight ctypes scalars each time measured ~25 us/request of pure wrapper
    overhead on the serving profile."""
    s = getattr(_parse_tls, "scratch", None)
    if s is None:
        s = (
            ctypes.c_long(),  # method_len
            ctypes.c_long(),  # path_off
            ctypes.c_long(),  # path_len
            ctypes.c_longlong(),  # clen
            ctypes.c_long(),  # flags
            ctypes.create_string_buffer(_CTYPE_CAP),
            ctypes.c_long(),  # ctype_len
            ctypes.create_string_buffer(_AUTH_CAP),
            ctypes.c_long(),  # auth_len
        )
        _parse_tls.scratch = s
    return s


def parse_http_head(buf) -> "ParsedHead | int | None":
    """Parse an HTTP/1.1 request head in one C pass.

    Returns a ParsedHead, 0 when the head is incomplete (read more), -1
    when malformed, or None when the native library is unavailable (caller
    uses its Python parse)."""
    lib = get_lib()
    if lib is None:
        return None
    raw = bytes(buf)
    (
        method_len,
        path_off,
        path_len,
        clen,
        flags,
        ctype_buf,
        ctype_len,
        auth_buf,
        auth_len,
    ) = _parse_scratch()
    rc = lib.http_parse_head(
        raw, len(raw),
        ctypes.byref(method_len),
        ctypes.byref(path_off), ctypes.byref(path_len),
        ctypes.byref(clen), ctypes.byref(flags),
        ctype_buf, _CTYPE_CAP, ctypes.byref(ctype_len),
        auth_buf, _AUTH_CAP, ctypes.byref(auth_len),
    )
    if rc <= 0:
        # incomplete/malformed heads can still have memcpy'd an
        # Authorization value before the parse stopped (e.g. auth header
        # followed by a bad Content-Length) — the reused per-thread scratch
        # must not retain it on ANY exit path, same invariant as below
        ctypes.memset(auth_buf, 0, _AUTH_CAP)
        return 0 if rc == 0 else -1
    if ctype_len.value >= _CTYPE_CAP or auth_len.value >= _AUTH_CAP:
        # possible truncation (oversized JWTs etc.): a clipped credential
        # would 401 on this path but pass the Python parse — hand the
        # request to the uncapped Python parser instead
        ctypes.memset(auth_buf, 0, _AUTH_CAP)
        return None
    head = ParsedHead(
        body_start=int(rc),
        method=raw[: method_len.value].decode("latin-1"),
        path=raw[path_off.value : path_off.value + path_len.value].decode("latin-1"),
        content_length=int(clen.value),
        flags=int(flags.value),
        content_type=(
            ctype_buf.raw[: ctype_len.value].decode("latin-1")
            if ctype_len.value >= 0
            else None
        ),
        authorization=(
            auth_buf.raw[: auth_len.value].decode("latin-1")
            if auth_len.value >= 0
            else None
        ),
    )
    if auth_len.value > 0:
        # the reused scratch must not retain the client's credential past
        # the request (a core dump would otherwise hold the latest JWT per
        # thread at a stable address)
        ctypes.memset(auth_buf, 0, auth_len.value)
    return head
