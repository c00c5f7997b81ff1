"""Binary tensor codec: npy bytes <-> numpy arrays.

TPU-native wire fast path. The reference moves every tensor as JSON text
(engine form-encoded ``json=`` hops, ~8-18 bytes per value) and treats the
proto ``binData`` arm as opaque passthrough bytes (prediction.proto:12-21 —
no codec anywhere consumes it). For image-scale payloads the text encoding
is the bottleneck: a 224x224x3 float32 image is ~1.2 MB as JSON but 588 KB
as npy float32 and 147 KB as npy uint8.

Format: the standard npy container (numpy.lib.format) — self-describing
dtype/shape/order header + raw buffer. Chosen over a bespoke header because
every numpy/jax client can produce it with ``np.save`` and it decodes
zero-copy for C-contiguous arrays.

Ingress rule (serving/service.py): a request whose ``binData`` arm starts
with the npy magic is decoded into the tensor ``data`` arm before the
micro-batcher, and the response tensor is encoded back to npy ``binData``
(mirrored kind). Non-npy binData stays opaque passthrough, preserving the
reference semantics. REST also accepts the raw body directly with
``Content-Type: application/x-npy`` (serving/rest.py) — no JSON envelope,
no base64 inflation.
"""

from __future__ import annotations

import io

import numpy as np

from seldon_core_tpu.core.errors import APIException, ErrorCode

NPY_MAGIC = b"\x93NUMPY"


def is_npy(raw: bytes | None) -> bool:
    return raw is not None and raw[: len(NPY_MAGIC)] == NPY_MAGIC


def array_from_npy(raw: bytes) -> np.ndarray:
    """Decode npy bytes. allow_pickle stays False: object-dtype payloads
    would otherwise be arbitrary-code-execution on the serving path."""
    try:
        arr = np.load(io.BytesIO(raw), allow_pickle=False)
    except Exception as e:  # noqa: BLE001 - wire input, map to the error codes
        raise APIException(
            ErrorCode.ENGINE_INVALID_JSON, f"bad npy payload: {e}"
        ) from e
    if arr.dtype == object:  # defense in depth; np.load refuses already
        raise APIException(ErrorCode.ENGINE_INVALID_JSON, "object npy refused")
    return arr


def npy_from_array(array) -> bytes:
    arr = np.asarray(array)
    if arr.dtype.kind == "V" or not arr.dtype.isnative or arr.dtype.hasobject:
        # ml_dtypes (bfloat16 etc.) serialize as opaque void in npy — no
        # client could decode them; float32 is the interoperable form
        arr = arr.astype(np.float32)
    buf = io.BytesIO()
    np.save(buf, np.ascontiguousarray(arr), allow_pickle=False)
    return buf.getvalue()
