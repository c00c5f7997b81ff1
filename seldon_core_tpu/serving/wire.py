"""Transport-neutral hot-path handlers (the wire core).

One implementation of the engine and gateway data-plane semantics, consumed
by BOTH transports: the aiohttp apps (serving/rest.py, gateway/app.py) and
the fast asyncio.Protocol ingress (serving/fast_http.py). aiohttp's
per-request machinery costs ~150 us of a serving core; the reference embeds
Tomcat and pays the same class of overhead (SURVEY C8/C13) — owning the
data-plane HTTP layer is where a serving framework's ingress budget goes.
Keeping the semantics HERE means the fast path can never drift from the
general one.
"""

from __future__ import annotations

import json
import logging
from dataclasses import dataclass, field
from urllib.parse import parse_qs

from seldon_core_tpu.core.codec_json import (
    feedback_from_dict,
    message_from_dict,
    message_from_json_fast,
    message_to_json_fast,
    meta_to_dict,
)
from seldon_core_tpu.core.codec_npy import is_npy
from seldon_core_tpu.core.errors import APIException, ErrorCode
from seldon_core_tpu.core.message import SeldonMessage
from seldon_core_tpu.telemetry.flight import Ingress

log = logging.getLogger(__name__)


@dataclass
class WireRequest:
    """The request shape every transport reduces to: method, path, LOWERCASE
    header dict, raw body bytes. ``declared_ctype`` distinguishes a client
    that actually sent Content-Type from transports that synthesize a
    default (classify_binary_bytes needs this: header-less bodies must fall
    through to the JSON parser)."""

    method: str
    path: str
    headers: dict[str, str]
    body: bytes
    declared_ctype: bool = True

    @property
    def content_type(self) -> str:
        ctype = self.headers.get("content-type", "")
        return ctype.split(";", 1)[0].strip().lower()


@dataclass
class WireResponse:
    status: int = 200
    body: bytes = b""
    content_type: str = "application/json"
    headers: dict[str, str] = field(default_factory=dict)

    @staticmethod
    def json_obj(obj, status: int = 200) -> "WireResponse":
        return WireResponse(status=status, body=json.dumps(obj).encode())

    @staticmethod
    def text(text: str, status: int = 200) -> "WireResponse":
        return WireResponse(
            status=status, body=text.encode(), content_type="text/plain"
        )


@dataclass
class WireStreamResponse:
    """A streaming response: ``events`` is an async iterator of ready-to-
    write bytes chunks (SSE frames). Transports write the head, then each
    chunk as it arrives (the fast ingress uses Transfer-Encoding: chunked).
    Only produced once the request validated — handler errors BEFORE the
    first event come back as a plain WireResponse instead."""

    events: object  # AsyncIterator[bytes]
    status: int = 200
    content_type: str = "text/event-stream"
    headers: dict = field(default_factory=dict)


def sse_frame(obj) -> bytes:
    """One server-sent-events data frame."""
    return b"data: " + json.dumps(obj, separators=(",", ":")).encode() + b"\n\n"


NPY_CONTENT_TYPES = ("application/x-npy", "application/octet-stream")


def classify_binary_bytes(
    ctype: str, declared: bool, raw: bytes, sniff_npy: bool = True
) -> str:
    """Byte-level twin of http_util.classify_binary_body: ``"npy"``,
    ``"bin"`` or ``"json"`` (see that docstring for the full contract —
    x-npy is an explicit opt-in honored regardless of sniffing; octet-stream
    sniffs the magic only when the deployment allows; header-less bodies
    fall to the JSON parser)."""
    if ctype not in NPY_CONTENT_TYPES:
        return "json"
    if ctype == "application/x-npy" or (sniff_npy and is_npy(raw)):
        return "npy"
    if declared:
        return "bin"
    return "json"


def _multipart_field(req: WireRequest, field_name: str) -> str | None:
    """Extract one text field from a multipart/form-data body (the reference
    wire quirk accepts the ``json=`` field from either form encoding)."""
    import re

    full_ctype = req.headers.get("content-type", "")
    m = re.search(r'boundary="?([^";]+)"?', full_ctype)
    if not m:
        return None
    delim = b"--" + m.group(1).encode()
    needle = f'name="{field_name}"'.encode()
    for part in req.body.split(delim):
        head, sep, payload = part.partition(b"\r\n\r\n")
        if sep and needle in head:
            return payload.rstrip(b"\r\n").decode("utf-8", errors="replace")
    return None


def payload_obj(req: WireRequest, invalid_code: ErrorCode) -> dict:
    """JSON body, or form field ``json=`` in urlencoded OR multipart form
    (reference wire compat — wrappers/python/microservice.py:44-52)."""
    ctype = req.content_type
    if ctype in ("application/x-www-form-urlencoded", "multipart/form-data"):
        if ctype.startswith("multipart"):
            raw = _multipart_field(req, "json")
        else:
            fields = parse_qs(req.body.decode("utf-8", errors="replace"))
            raw = (fields.get("json") or [None])[0]
        if raw is None:
            raise APIException(invalid_code, "missing 'json' form field")
        try:
            return json.loads(raw)
        except json.JSONDecodeError as e:
            raise APIException(invalid_code, str(e)) from e
    try:
        return json.loads(req.body)
    except Exception as e:  # noqa: BLE001
        raise APIException(invalid_code, str(e)) from e


def failure_response(
    e: BaseException, *, fallback_code: ErrorCode, op: str, metrics_error
) -> WireResponse:
    """Wire-boundary invariant as a WireResponse (http_util.wire_failure's
    transport-neutral twin): status-JSON body, never an HTML 500."""
    if not isinstance(e, APIException):
        log.exception("unhandled error serving %s", op)
        e = APIException(fallback_code, str(e))
    if metrics_error is not None:
        metrics_error(e.error.code)
    headers = {}
    retry_after = e.retry_after_header()
    if retry_after is not None:
        # open circuit breaker: clients should back off until the breaker's
        # next half-open probe window instead of hammering the endpoint
        headers["Retry-After"] = retry_after
    return WireResponse(
        status=e.error.http_status,
        body=json.dumps(e.to_status_json()).encode(),
        headers=headers,
    )


def npy_wire_response(out: SeldonMessage) -> WireResponse:
    """Raw npy body + meta in the Seldon-Meta header (http_util.npy_response
    semantics, incl. the header-size truncation rule)."""
    meta_json = json.dumps(meta_to_dict(out.meta))
    if len(meta_json) > 6144:
        meta_json = json.dumps(
            {"puid": out.meta.puid, "routing": dict(out.meta.routing), "truncated": True}
        )
    return WireResponse(
        body=out.bin_data,
        content_type="application/x-npy",
        headers={"Seldon-Meta": meta_json},
    )


# --------------------------------------------------------------- engine core
async def engine_predictions(service, req: WireRequest) -> WireResponse:
    """POST /api/v0.1/predictions against one PredictionService — the engine
    data plane (reference RestClientController.predictions:102)."""
    try:
        ctype = req.content_type
        kind = classify_binary_bytes(
            ctype, req.declared_ctype, req.body, sniff_npy=service.decode_npy
        )
        # W3C trace propagation: a remote engine's RemoteUnit (or any
        # tracing client) sends traceparent; the service continues that
        # trace so multi-pod graph walks stitch into one tree
        tp = req.headers.get("traceparent")
        if kind != "json":
            out = await service.predict(
                SeldonMessage(bin_data=req.body),
                wire_npy=kind == "npy",
                traceparent=tp,
            )
            if kind == "npy" and is_npy(out.bin_data):
                return npy_wire_response(out)
            return WireResponse(body=message_to_json_fast(out))
        if ctype == "application/json" or not req.declared_ctype:
            msg = message_from_json_fast(req.body)
        else:
            msg = message_from_dict(payload_obj(req, ErrorCode.ENGINE_INVALID_JSON))
        out = await service.predict(msg, traceparent=tp)
        return WireResponse(body=message_to_json_fast(out))
    except Exception as e:  # noqa: BLE001 - wire boundary
        return failure_response(
            e,
            fallback_code=ErrorCode.ENGINE_MICROSERVICE_ERROR,
            op="predict",
            metrics_error=lambda c: service.metrics.ingress_error(
                service.deployment_name, "predict", c
            ),
        )


async def engine_predictions_stream(service, req: WireRequest):
    """POST /api/v0.1/predictions/stream — per-token SSE streaming for the
    generative tier (service.predict_stream). The buffered /predictions
    surface is untouched: existing clients see no change, streaming is a
    separate opt-in route on the fast ingress.

    Events: ``data: {"row": r, "index": i, "token": t}`` per generated
    token, then ``data: {"done": true, "ids": [[...]], ...}``. Request
    parsing (JSON envelope or npy body) matches /predictions; per-request
    sampling rides meta.tags (temperature / top_k / max_new_tokens).

    The FIRST event is awaited before the response head is committed, so
    validation errors still come back as ordinary status-JSON failures;
    errors after streaming began are sent as a terminal error event."""
    ingress = Ingress()  # the body is still bytes: its parse is ingress
    try:
        ctype = req.content_type
        kind = classify_binary_bytes(
            ctype, req.declared_ctype, req.body, sniff_npy=service.decode_npy
        )
        if kind != "json":
            msg = SeldonMessage(bin_data=req.body)
        elif ctype == "application/json" or not req.declared_ctype:
            msg = message_from_json_fast(req.body)
        else:
            msg = message_from_dict(payload_obj(req, ErrorCode.ENGINE_INVALID_JSON))
        gen = service.predict_stream(
            msg, wire_npy=kind == "npy", traceparent=req.headers.get("traceparent"),
            ingress=ingress,
        )
        first = await gen.__anext__()
    except StopAsyncIteration:
        return WireResponse(status=500, body=b'{"status":"FAILURE"}')
    except Exception as e:  # noqa: BLE001 - wire boundary
        ingress.done()  # refused before a submit
        return failure_response(
            e,
            fallback_code=ErrorCode.ENGINE_MICROSERVICE_ERROR,
            op="predict_stream",
            metrics_error=lambda c: service.metrics.ingress_error(
                service.deployment_name, "predict_stream", c
            ),
        )

    async def events():
        try:
            yield sse_frame(first)
            try:
                async for ev in gen:
                    yield sse_frame(ev)
            except Exception as e:  # noqa: BLE001 - head already committed
                log.exception("stream failed mid-flight")
                err = e.to_status_json() if isinstance(e, APIException) else {"status": "FAILURE"}
                yield sse_frame({"error": err})
        finally:
            # transport-initiated close (client disconnect) must reach the
            # service generator so its finally cancels in-flight generation
            await gen.aclose()

    return WireStreamResponse(events=events())


async def engine_feedback(service, req: WireRequest) -> WireResponse:
    try:
        fb = feedback_from_dict(payload_obj(req, ErrorCode.ENGINE_INVALID_JSON))
        out = await service.send_feedback(
            fb, traceparent=req.headers.get("traceparent")
        )
        return WireResponse(body=message_to_json_fast(out))
    except Exception as e:  # noqa: BLE001 - wire boundary
        return failure_response(
            e,
            fallback_code=ErrorCode.ENGINE_MICROSERVICE_ERROR,
            op="feedback",
            metrics_error=lambda c: service.metrics.ingress_error(
                service.deployment_name, "feedback", c
            ),
        )


# ------------------------------------------------- internal microservice API
async def engine_unit_method(service, req: WireRequest, method: str) -> WireResponse:
    """The reference's INTERNAL microservice API over REST
    (docs/reference/internal-api.md:14-120; wrappers/python/microservice.py
    routes): /predict /route /send-feedback /transform-input
    /transform-output /aggregate on a wrapped single-unit service — the
    endpoints the engine's RemoteUnit client dispatches to. Payloads accept
    raw JSON or the form-encoded ``json=`` field; semantics mirror the gRPC
    services (serving/grpc_server.py) exactly."""
    import numpy as np

    if method == "predict":
        # /predict is the engine predictions surface under the internal-API
        # path name: full semantics incl. the raw application/x-npy fast
        # path and binData classification, not just the JSON envelope
        return await engine_predictions(service, req)
    try:
        unit = service.executor.root.unit
        if method == "send-feedback":
            fb = feedback_from_dict(payload_obj(req, ErrorCode.ENGINE_INVALID_JSON))
            out = await service.send_feedback(
                fb, traceparent=req.headers.get("traceparent")
            )
            return WireResponse(body=message_to_json_fast(out))
        # server-side trace continuation for the internal API: a remote
        # engine's RemoteUnit sends traceparent on transform/route/aggregate
        # hops exactly like /predict — this span is the hop's server half
        with service.tracer.request_trace(
            f"ingress:{method}",
            parent=req.headers.get("traceparent"),
            attrs={"deployment": service.deployment_name, "method": method},
        ):
            if method == "transform-input":
                out = await unit.transform_input(
                    message_from_dict(payload_obj(req, ErrorCode.ENGINE_INVALID_JSON))
                )
            elif method == "transform-output":
                out = await unit.transform_output(
                    message_from_dict(payload_obj(req, ErrorCode.ENGINE_INVALID_JSON))
                )
            elif method == "route":
                branch = await unit.route(
                    message_from_dict(payload_obj(req, ErrorCode.ENGINE_INVALID_JSON))
                )
                out = SeldonMessage.from_array(np.asarray([[branch]], dtype=np.float32))
            elif method == "aggregate":
                obj = payload_obj(req, ErrorCode.ENGINE_INVALID_JSON)
                msgs = [
                    message_from_dict(m) for m in obj.get("seldonMessages", [])
                ]
                out = await unit.aggregate(msgs)
            else:  # pragma: no cover - route tables only register the above
                raise APIException(
                    ErrorCode.ENGINE_INVALID_JSON, f"unknown method {method}"
                )
        return WireResponse(body=message_to_json_fast(out))
    except Exception as e:  # noqa: BLE001 - wire boundary
        return failure_response(
            e,
            fallback_code=ErrorCode.ENGINE_MICROSERVICE_ERROR,
            op=method,
            metrics_error=lambda c: service.metrics.ingress_error(
                service.deployment_name, method, c
            ),
        )


INTERNAL_API_METHODS = (
    "predict",
    "route",
    "send-feedback",
    "transform-input",
    "transform-output",
    "aggregate",
)


# -------------------------------------------------------------- gateway core
async def gateway_predictions(gw, req: WireRequest) -> WireResponse:
    """POST /api/v0.1/predictions through the OAuth gateway — the external
    hot path (reference apife RestClientController.prediction:127)."""
    import time as _time

    start = _time.perf_counter()
    try:
        principal = gw.principal_from_auth(req.headers.get("authorization", ""))
        dep = gw._deployment(principal)
        # predictors of one deployment share wire semantics (validated), so
        # the first predictor's toggle speaks for the deployment
        sniff = dep.predictors[0].tpu.decode_npy_bindata if dep.predictors else True
        ctype = req.content_type
        kind = classify_binary_bytes(ctype, req.declared_ctype, req.body, sniff_npy=sniff)
        npy = kind == "npy"
        if kind != "json":
            # npy: wire_npy carries the explicit declaration to the backend
            # (in-process: service decode; remote: raw x-npy forward).
            # bin: opaque binData passthrough.
            msg = SeldonMessage(bin_data=req.body)
        elif ctype == "application/json" or not req.declared_ctype:
            msg = message_from_json_fast(req.body)
        else:
            msg = message_from_dict(payload_obj(req, ErrorCode.APIFE_INVALID_JSON))
        out = await gw.backend.predict(
            dep, msg, wire_npy=npy, traceparent=req.headers.get("traceparent")
        )
        gw.audit.send(principal, msg, out)  # RestClientController.java:164
        if gw.metrics is not None:
            gw.metrics.ingress_request(dep.name, "predict", _time.perf_counter() - start)
        if npy:
            # mirror the request kind; the is_npy guard keeps opaque
            # bytes-out responses in the JSON envelope
            from seldon_core_tpu.serving.service import mirror_npy_kind

            out = mirror_npy_kind(out)
            if is_npy(out.bin_data):
                return npy_wire_response(out)
        return WireResponse(body=message_to_json_fast(out))
    except Exception as e:  # noqa: BLE001 - wire boundary
        return failure_response(
            e,
            fallback_code=ErrorCode.APIFE_MICROSERVICE_ERROR,
            op="gateway predict",
            metrics_error=lambda c: gw.metrics is not None
            and gw.metrics.ingress_error("", "predict", c),
        )


async def gateway_feedback(gw, req: WireRequest) -> WireResponse:
    import time as _time

    start = _time.perf_counter()
    try:
        principal = gw.principal_from_auth(req.headers.get("authorization", ""))
        dep = gw._deployment(principal)
        fb = feedback_from_dict(payload_obj(req, ErrorCode.APIFE_INVALID_JSON))
        out = await gw.backend.feedback(dep, fb)
        if gw.metrics is not None:
            gw.metrics.ingress_request(dep.name, "feedback", _time.perf_counter() - start)
            gw.metrics.feedback(dep.name, "", "", fb.reward)
        return WireResponse(body=message_to_json_fast(out))
    except Exception as e:  # noqa: BLE001 - wire boundary
        return failure_response(
            e,
            fallback_code=ErrorCode.APIFE_MICROSERVICE_ERROR,
            op="gateway feedback",
            metrics_error=lambda c: gw.metrics is not None
            and gw.metrics.ingress_error("", "feedback", c),
        )


async def gateway_token(gw, req: WireRequest) -> WireResponse:
    """POST /oauth/token — client_credentials via Basic auth or form."""
    import base64

    client_id = client_secret = ""
    auth = req.headers.get("authorization", "")
    if auth.lower().startswith("basic "):
        try:
            decoded = base64.b64decode(auth[6:]).decode()
            client_id, _, client_secret = decoded.partition(":")
        except Exception:  # noqa: BLE001
            pass
    if not client_id:
        if req.content_type == "multipart/form-data":
            client_id = _multipart_field(req, "client_id") or ""
            client_secret = _multipart_field(req, "client_secret") or ""
        else:
            fields = parse_qs(req.body.decode("utf-8", errors="replace"))
            client_id = (fields.get("client_id") or [""])[0]
            client_secret = (fields.get("client_secret") or [""])[0]
    try:
        return WireResponse.json_obj(gw.oauth.issue_token(client_id, client_secret))
    except PermissionError:
        return WireResponse.json_obj(
            {"error": "invalid_client", "error_description": "Bad client credentials"},
            status=401,
        )


# ------------------------------------------------------------- gRPC-Web
# The HTTP/1.1-compatible gRPC wire (unary): each message is framed as
# 1 flags byte + u32 big-endian length + payload; trailers travel as a
# final frame with the 0x80 flag. Serving it on the fast ingress gives
# gRPC-ecosystem clients (browsers, envoy grpc_web filters, generated
# stubs) the asyncio.Protocol + C-head-parser data plane instead of the
# Python HTTP/2 stack — the measured floor behind the native-gRPC gap
# (docs/reference/external-api.md §5).

GRPC_WEB_CTYPE = "application/grpc-web+proto"

# CORS surface for browser gRPC-Web clients: the content type and the
# metadata headers are non-simple, so cross-origin browsers preflight.
# grpc-status rides HTTP trailers-in-body frames, but grpc-web JS also
# reads response HEADERS — expose them.
GRPC_WEB_CORS_HEADERS = {
    "Access-Control-Allow-Origin": "*",
    "Access-Control-Allow-Methods": "POST, OPTIONS",
    "Access-Control-Allow-Headers": (
        "content-type, oauth_token, authorization, x-grpc-web, x-user-agent"
    ),
    "Access-Control-Expose-Headers": "grpc-status, grpc-message",
    "Access-Control-Max-Age": "86400",
}


def grpc_web_frame(flags: int, payload: bytes) -> bytes:
    return bytes([flags]) + len(payload).to_bytes(4, "big") + payload


def grpc_web_first_message(body: bytes) -> bytes:
    """Payload of the single DATA frame a unary request carries. Trailing
    bytes (a second frame / attempted client streaming) are rejected —
    native gRPC errors extra messages on a unary RPC, and silently serving
    half a payload would be the hardest client bug to debug."""
    if len(body) < 5:
        raise ValueError("grpc-web frame truncated")
    if body[0] & 0x80:
        raise ValueError("grpc-web request began with a trailer frame")
    if body[0] & 0x01:
        raise ValueError("compressed grpc-web frames not supported")
    n = int.from_bytes(body[1:5], "big")
    if len(body) < 5 + n:
        raise ValueError("grpc-web frame length exceeds body")
    if len(body) > 5 + n:
        raise ValueError("trailing bytes after the unary request frame")
    return body[5 : 5 + n]


# one route table consumed by BOTH transports (gateway/app.py and
# fast_http.gateway_routes) — the parity the docs promise must have a
# single source, not two loops with matching comments
GRPC_WEB_ROUTES: tuple[tuple[str, str], ...] = tuple(
    (f"/{pkg}.Seldon/{method}", method)
    for pkg in ("seldon.tpu", "seldon.protos")
    for method in ("Predict", "SendFeedback")
)


def _grpc_web_response(message_pb: bytes, status: int = 0) -> "WireResponse":
    body = grpc_web_frame(0, message_pb) + grpc_web_frame(
        0x80, f"grpc-status:{status}\r\n".encode()
    )
    return WireResponse(
        body=body,
        content_type=GRPC_WEB_CTYPE,
        headers=dict(GRPC_WEB_CORS_HEADERS),
    )


def _grpc_web_error(code: int, message: str) -> "WireResponse":
    """Trailers-only response (no DATA frame): transport-level failure,
    e.g. malformed framing. HTTP status stays 200 per the grpc-web spec;
    the grpc-status trailer carries the error. The message is
    percent-encoded per the gRPC spec — raw exception text can carry
    CR/LF/non-ASCII that would corrupt the trailer block."""
    from urllib.parse import quote

    safe_msg = quote(message, safe=" ()[]{}<>=,.:;!?/'~@#$^&*+-_|")
    trailer = f"grpc-status:{code}\r\ngrpc-message:{safe_msg}\r\n".encode()
    return WireResponse(
        body=grpc_web_frame(0x80, trailer),
        content_type=GRPC_WEB_CTYPE,
        headers=dict(GRPC_WEB_CORS_HEADERS),
    )


def _grpc_web_principal(gw, req: "WireRequest") -> str:
    """gRPC metadata maps to HTTP headers under grpc-web: accept the
    gateway's ``oauth_token`` metadata key (HeaderServerInterceptor
    parity) or a standard Authorization bearer."""
    token = req.headers.get("oauth_token", "")
    if token:
        principal = gw.oauth.principal(token)
        if not principal:
            raise APIException(
                ErrorCode.APIFE_GRPC_NO_PRINCIPAL_FOUND, "oauth_token"
            )
        return principal
    return gw.principal_from_auth(req.headers.get("authorization", ""))


async def gateway_grpc_web_predict(gw, req: "WireRequest") -> "WireResponse":
    """POST /seldon.*.Seldon/Predict with application/grpc-web+proto."""
    import time as _time

    from seldon_core_tpu.core.codec_proto import (
        message_from_proto,
        message_to_proto,
    )
    from seldon_core_tpu.proto import prediction_pb2 as pb

    start = _time.perf_counter()
    try:
        pbmsg = pb.SeldonMessage.FromString(grpc_web_first_message(req.body))
    except Exception as e:  # noqa: BLE001 - malformed framing/proto
        return _grpc_web_error(3, f"invalid grpc-web request: {e}")  # 3=INVALID_ARGUMENT
    try:
        principal = _grpc_web_principal(gw, req)
        dep = gw._deployment(principal)
        msg = message_from_proto(pbmsg)
        out = await gw.backend.predict(dep, msg)
        gw.audit.send(principal, msg, out)
        if gw.metrics is not None:
            gw.metrics.ingress_request(
                dep.name, "predict", _time.perf_counter() - start
            )
        return _grpc_web_response(message_to_proto(out).SerializeToString())
    except APIException as e:
        # application-level failure rides a SUCCESS grpc-status with the
        # failure inside the SeldonMessage — byte-for-byte the native gRPC
        # gateway's behavior (gateway/grpc_gateway.py), so a client sees
        # identical semantics on either transport
        if gw.metrics is not None:
            gw.metrics.ingress_error("", "predict", e.error.code)
        failure = SeldonMessage.failure(e.error.code, e.error.message, e.info)
        return _grpc_web_response(message_to_proto(failure).SerializeToString())
    except Exception as e:  # noqa: BLE001 - wire boundary
        log.exception("grpc-web predict failed")
        if gw.metrics is not None:
            gw.metrics.ingress_error("", "predict", ErrorCode.APIFE_MICROSERVICE_ERROR.code)
        return _grpc_web_error(13, str(e))  # 13=INTERNAL


async def gateway_grpc_web_feedback(gw, req: "WireRequest") -> "WireResponse":
    """POST /seldon.*.Seldon/SendFeedback with application/grpc-web+proto."""
    import time as _time

    from seldon_core_tpu.core.codec_proto import (
        feedback_from_proto,
        message_to_proto,
    )
    from seldon_core_tpu.proto import prediction_pb2 as pb

    start = _time.perf_counter()
    try:
        fb_pb = pb.Feedback.FromString(grpc_web_first_message(req.body))
    except Exception as e:  # noqa: BLE001
        return _grpc_web_error(3, f"invalid grpc-web request: {e}")
    try:
        principal = _grpc_web_principal(gw, req)
        dep = gw._deployment(principal)
        fb = feedback_from_proto(fb_pb)
        out = await gw.backend.feedback(dep, fb)
        # same instrumentation as the REST feedback path: dashboards must
        # see grpc-web traffic (latency + the bandit reward gauge)
        if gw.metrics is not None:
            gw.metrics.ingress_request(
                dep.name, "feedback", _time.perf_counter() - start
            )
            gw.metrics.feedback(dep.name, "", "", fb.reward)
        return _grpc_web_response(message_to_proto(out).SerializeToString())
    except APIException as e:
        if gw.metrics is not None:
            gw.metrics.ingress_error("", "feedback", e.error.code)
        failure = SeldonMessage.failure(e.error.code, e.error.message, e.info)
        return _grpc_web_response(message_to_proto(failure).SerializeToString())
    except Exception as e:  # noqa: BLE001
        log.exception("grpc-web feedback failed")
        if gw.metrics is not None:
            gw.metrics.ingress_error(
                "", "feedback", ErrorCode.APIFE_MICROSERVICE_ERROR.code
            )
        return _grpc_web_error(13, str(e))
