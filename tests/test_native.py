"""Native C++ wire codec: build, parse/encode round trips, fallbacks, and
equivalence with the pure-Python codec (which stays the semantic oracle).
"""

import json

import numpy as np
import pytest

from seldon_core_tpu import native
from seldon_core_tpu.core.codec_json import (
    message_from_json,
    message_from_json_fast,
    message_to_dict,
    message_to_json_fast,
)
from seldon_core_tpu.core.message import DataKind


def test_library_builds():
    # g++ is baked into the image; the codec must compile and load
    assert native.available()


def test_find_span_simple():
    raw = b'{"data": {"names": ["a"], "ndarray": [[1.0, 2.0]]}}'
    s, e = native.find_ndarray_span(raw)
    assert raw[s:e] == b"[[1.0, 2.0]]"


def test_find_span_ignores_key_inside_string_value():
    raw = b'{"note": "the \\"ndarray\\" key", "data": {"ndarray": [[3]]}}'
    s, e = native.find_ndarray_span(raw)
    assert raw[s:e] == b"[[3]]"


def test_parse_2d():
    arr = native.parse_ndarray(b"[[1.5, -2e3, 3], [4, 5.25, 6]]")
    np.testing.assert_array_equal(
        arr, np.asarray([[1.5, -2000.0, 3.0], [4.0, 5.25, 6.0]], np.float32)
    )


def test_parse_1d():
    arr = native.parse_ndarray(b"[1, 2, 3.5]")
    assert arr.shape == (3,)
    assert arr[2] == 3.5


def test_parse_rejects_ragged_and_strings():
    assert native.parse_ndarray(b"[[1, 2], [3]]") is None
    assert native.parse_ndarray(b'[["a", "b"]]') is None
    assert native.parse_ndarray(b"[[[1]]]") is None  # 3D: python path handles


def test_encode_roundtrips_float32_exactly():
    rng = np.random.default_rng(0)
    arr = rng.standard_normal((7, 5)).astype(np.float32)
    body = native.encode_ndarray(arr)
    back = np.asarray(json.loads(body), np.float32)
    np.testing.assert_array_equal(back, arr)  # %.9g round-trips f32 exactly


def test_pad_rows():
    arr = np.arange(6, dtype=np.float32).reshape(2, 3)
    out = native.pad_rows(arr, 4)
    assert out.shape == (4, 3)
    np.testing.assert_array_equal(out[:2], arr)
    assert out[2:].sum() == 0
    with pytest.raises(ValueError):
        native.pad_rows(arr, 1)


def test_fast_decode_matches_python_decode():
    raw = json.dumps(
        {
            "meta": {"puid": "p1", "tags": {"k": "v"}, "routing": {"r": 1}},
            "data": {"names": ["x", "y"], "ndarray": [[1.0, 2.0], [3.0, 4.0]]},
        }
    ).encode()
    fast = message_from_json_fast(raw)
    slow = message_from_json(raw)
    np.testing.assert_array_equal(fast.array, slow.array)
    assert fast.names == slow.names
    assert fast.meta.puid == slow.meta.puid
    assert fast.meta.routing == slow.meta.routing
    assert fast.data.kind == DataKind.NDARRAY


def test_fast_decode_falls_back_on_nested_request():
    # feedback-style body where data.ndarray is NOT the first ndarray key
    raw = json.dumps(
        {
            "request": {"data": {"ndarray": [[9.0]]}},
            "data": {"ndarray": [[1.0]]},
        }
    ).encode()
    msg = message_from_json_fast(raw)
    # whatever path it took, semantics must match the python codec
    slow = message_from_json(raw)
    np.testing.assert_array_equal(msg.array, slow.array)


def test_fast_decode_falls_back_on_string_categories():
    raw = json.dumps({"data": {"ndarray": [["red", 1.0]]}}).encode()
    msg = message_from_json_fast(raw)
    assert msg.array.shape == (1, 2)


def test_fast_encode_matches_python_encode():
    from seldon_core_tpu.core.codec_json import message_from_dict

    msg = message_from_dict(
        {
            "meta": {"puid": "q"},
            "data": {"names": ["a"], "ndarray": [[0.5, 1.25], [2.0, 3.0]]},
        }
    )
    fast = json.loads(message_to_json_fast(msg))
    slow = message_to_dict(msg)
    assert fast["meta"]["puid"] == slow["meta"]["puid"]
    assert fast["data"]["names"] == slow["data"]["names"]
    np.testing.assert_array_equal(
        np.asarray(fast["data"]["ndarray"], np.float32),
        np.asarray(slow["data"]["ndarray"], np.float32),
    )


def test_fast_decode_malformed_json_raises_api_exception():
    from seldon_core_tpu.core.errors import APIException

    with pytest.raises(APIException):
        message_from_json_fast(b'{"data": {"ndarray": [[1.0]}')


def test_parse_rejects_malformed_number_tokens():
    # each of these diverged from the Python oracle before the grammar fix
    assert native.parse_ndarray(b"[[.5]]") is None
    assert native.parse_ndarray(b"[[1-2]]") is None
    assert native.parse_ndarray(b"[[1.2.3]]") is None
    assert native.parse_ndarray(b"[[5.]]") is None
    assert native.parse_ndarray(b"[[+1]]") is None
    # valid JSON numbers still parse
    arr = native.parse_ndarray(b"[[-1.5e-3, 0.5, 2E4]]")
    np.testing.assert_allclose(arr, [[-0.0015, 0.5, 20000.0]], rtol=1e-6)


def test_fast_encode_survives_forged_sentinel_in_tags():
    from seldon_core_tpu.core.codec_json import message_from_dict

    msg = message_from_dict(
        {
            "meta": {"puid": "p", "tags": {"t": "\x00NDARRAY\x00"}},
            "data": {"ndarray": [[1.0, 2.0]]},
        }
    )
    out = json.loads(message_to_json_fast(msg))
    assert out["meta"]["tags"]["t"] == "\x00NDARRAY\x00"  # tag untouched
    np.testing.assert_array_equal(
        np.asarray(out["data"]["ndarray"], np.float32), [[1.0, 2.0]]
    )


def test_fast_encode_leaves_float64_to_python_path():
    from seldon_core_tpu.core.codec_json import message_to_dict
    from seldon_core_tpu.core.message import DefaultData, Meta, SeldonMessage

    precise = 123456789.12345679
    msg = SeldonMessage(
        data=DefaultData(
            names=(), array=np.asarray([[precise]], np.float64), kind=DataKind.NDARRAY
        ),
        meta=Meta(puid="p"),
    )
    out = json.loads(message_to_json_fast(msg))
    assert out["data"]["ndarray"][0][0] == precise  # no f32 downcast


def test_fast_decode_prefers_tensor_like_oracle():
    raw = json.dumps(
        {
            "data": {
                "tensor": {"shape": [1, 2], "values": [9.0, 9.0]},
                "ndarray": [[1.0, 2.0]],
            }
        }
    ).encode()
    fast = message_from_json_fast(raw)
    slow = message_from_json(raw)
    np.testing.assert_array_equal(fast.array, slow.array)
    assert fast.data.kind == slow.data.kind == DataKind.TENSOR


def test_http_parse_head_fields_and_edges():
    """C HTTP head parser: fields, flags, incomplete/malformed signals."""
    from seldon_core_tpu import native

    if not native.available():
        import pytest

        pytest.skip("no native lib")
    req = (
        b"POST /api/v0.1/predictions?x=1 HTTP/1.1\r\n"
        b"Host: h\r\n"
        b"Content-Type: multipart/form-data; boundary=abc\r\n"
        b"AUTHORIZATION: Bearer tok\r\n"
        b"Connection: close\r\n"
        b"Content-Length: 3\r\n\r\nxyz"
    )
    h = native.parse_http_head(req)
    assert h.method == "POST" and h.path == "/api/v0.1/predictions?x=1"
    assert h.content_length == 3
    assert h.content_type == "multipart/form-data; boundary=abc"  # raw, params kept
    assert h.authorization == "Bearer tok"  # case-insensitive header name
    assert h.flags & native.HDRF_HAS_CTYPE
    assert h.flags & native.HDRF_CONN_CLOSE
    assert h.flags & native.HDRF_HAS_CLEN
    assert req[h.body_start:] == b"xyz"

    assert native.parse_http_head(req[:25]) == 0  # incomplete
    assert native.parse_http_head(b"NOSPACES\r\n\r\n") == -1  # malformed
    assert native.parse_http_head(b"GET /p HTTP/1.1\r\nContent-Length: 1x\r\n\r\n") == -1

    # no content-length header: HAS_CLEN unset, length reported -1
    h2 = native.parse_http_head(b"GET /ready HTTP/1.1\r\nHost: h\r\n\r\n")
    assert not (h2.flags & native.HDRF_HAS_CLEN) and h2.content_length == -1

    # transfer-encoding flag: set on ANY TE value, not just exact "chunked"
    # ("gzip, chunked" with a Content-Length is the TE.CL smuggling shape)
    h3 = native.parse_http_head(
        b"POST /p HTTP/1.1\r\nTransfer-Encoding: chunked\r\n\r\n"
    )
    assert h3.flags & native.HDRF_HAS_TE
    h4 = native.parse_http_head(
        b"POST /p HTTP/1.1\r\nTransfer-Encoding: gzip, chunked\r\n"
        b"Content-Length: 4\r\n\r\nbody"
    )
    assert h4.flags & native.HDRF_HAS_TE and h4.flags & native.HDRF_HAS_CLEN

    # whitespace before the colon: MUST reject (RFC 7230 3.2.4) — a lenient
    # parse would mis-file "Transfer-Encoding : chunked" as an unknown header
    assert (
        native.parse_http_head(
            b"POST /p HTTP/1.1\r\nTransfer-Encoding : chunked\r\n"
            b"Content-Length: 4\r\n\r\nbody"
        )
        == -1
    )
    # differing duplicate Content-Length: MUST reject (RFC 7230 3.3.2)
    assert (
        native.parse_http_head(
            b"POST /p HTTP/1.1\r\nContent-Length: 4\r\nContent-Length: 10\r\n\r\n"
        )
        == -1
    )
    # equal duplicates tolerated
    h5 = native.parse_http_head(
        b"POST /p HTTP/1.1\r\nContent-Length: 4\r\nContent-Length: 4\r\n\r\nbody"
    )
    assert h5.content_length == 4
    # leading whitespace on a header line (obs-fold): MUST reject — a proxy
    # trimming it would see " Transfer-Encoding: chunked" as TE while a
    # lenient parse here would skip it
    assert (
        native.parse_http_head(
            b"POST /p HTTP/1.1\r\n Transfer-Encoding: chunked\r\n"
            b"Content-Length: 4\r\n\r\nbody"
        )
        == -1
    )
    # bare LF inside a header line: reject — an LF-tolerant proxy would see
    # the hidden Transfer-Encoding as its own header and frame by chunked
    assert (
        native.parse_http_head(
            b"POST /p HTTP/1.1\r\nX-A: a\nTransfer-Encoding: chunked\r\n"
            b"Content-Length: 4\r\n\r\nbody"
        )
        == -1
    )
    # bare CR likewise
    assert (
        native.parse_http_head(
            b"POST /p HTTP/1.1\r\nX-A: a\rX-B: b\r\nContent-Length: 4\r\n\r\nbody"
        )
        == -1
    )


def test_http_parse_head_hardening():
    """Code-review r3 security findings: content-length overflow rejected,
    missing-version request line rejected, embedded-NUL header names safe,
    oversized auth values defer to the Python parser."""
    from seldon_core_tpu import native

    if not native.available():
        import pytest

        pytest.skip("no native lib")
    # 20-digit length would wrap int64 and smuggle body bytes
    assert (
        native.parse_http_head(
            b"POST /p HTTP/1.1\r\nContent-Length: 18446744073709551620\r\n\r\n"
        )
        == -1
    )
    # request line without an HTTP version must not swallow header bytes
    assert native.parse_http_head(b"GET /p\r\nContent-Length: 5\r\n\r\nhello") == -1
    # embedded NUL in a header name: non-token field-names are rejected
    # outright (RFC 7230 3.2.6) — mis-filing them as "unknown header" left
    # lenient-proxy smuggling variants open (code-review r4)
    assert (
        native.parse_http_head(b"GET /p HTTP/1.1\r\ncontent-length\x00x: 3\r\n\r\n")
        == -1
    )
    # form-feed before the colon: same family, must reject not mis-file
    assert (
        native.parse_http_head(
            b"POST /p HTTP/1.1\r\nTransfer-Encoding\x0c: chunked\r\n"
            b"Content-Length: 4\r\n\r\nbody"
        )
        == -1
    )
    # equal-value duplicate CL with different spellings tolerated numerically
    h6 = native.parse_http_head(
        b"POST /p HTTP/1.1\r\nContent-Length: 4\r\nContent-Length: 04\r\n\r\nbody"
    )
    assert h6.content_length == 4
    # >4KB authorization: C path declines (None) so Python handles it uncapped
    big = b"Bearer " + b"a" * 5000
    req = b"GET /p HTTP/1.1\r\nAuthorization: " + big + b"\r\n\r\n"
    assert native.parse_http_head(req) is None


def test_stale_library_is_rebuilt_by_content_not_by_file_time(tmp_path, monkeypatch):
    """A copied or checked-out tree guarantees nothing about file times: a
    stale .so NEWER than an edited .cpp must not be picked up. Freshness is
    the .cpp's content hash, stamped beside the .so."""
    import os
    import shutil

    src = shutil.copy(native._SRC, tmp_path / "fastcodec.cpp")
    so = tmp_path / "_fastcodec.so"
    stamp = tmp_path / "_fastcodec.so.src-sha256"
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_SO", str(so))
    monkeypatch.setattr(native, "_SO_STAMP", str(stamp))

    so.write_bytes(b"stale build of some older source")  # newer than the .cpp
    assert os.path.getmtime(so) >= os.path.getmtime(src)
    assert native._build() == str(so)
    assert so.read_bytes()[:4] == b"\x7fELF"  # rebuilt, not trusted
    assert stamp.read_text().strip() == native._src_digest()

    built = so.read_bytes()
    so.write_bytes(built + b"\0")  # a marker a rebuild would erase
    assert native._build() == str(so)
    assert so.read_bytes() == built + b"\0"  # same source: no rebuild

    with open(src, "a") as f:
        f.write("\n// edited\n")
    os.utime(src, (1, 1))  # the edit even LOOKS older than the .so
    assert native._build() == str(so)
    assert so.read_bytes()[:4] == b"\x7fELF" and so.read_bytes() != built + b"\0"
    assert stamp.read_text().strip() == native._src_digest()
