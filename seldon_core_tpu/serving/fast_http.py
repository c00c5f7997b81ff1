"""Fast data-plane HTTP/1.1 ingress: a purpose-built asyncio.Protocol server.

Why this exists: the serving hot path (predict request -> response) spends
more CPU in a general-purpose web framework's per-request machinery than in
the entire graph walk + XLA dispatch. This server implements exactly what
the data plane needs — POST with Content-Length bodies, keep-alive, a small
exact-path route table — over the SAME transport-neutral handlers
(serving/wire.py) the aiohttp apps use, so semantics cannot drift. Measured
on the bench stack-ceiling config it roughly halves per-request server
overhead vs the aiohttp app.

Not a general web server, by design:
- no chunked request bodies (411 if no Content-Length; serving clients and
  the reference's engines always send it),
- no TLS (terminate at the LB, as the reference's ingress does),
- no websockets. Streaming RESPONSES exist for exactly one surface: the
  generative tier's per-token SSE endpoint (chunked transfer, see
  _write_stream) — request bodies stay Content-Length-framed.
The full aiohttp apps remain for everything else (admin, tests, tooling);
`PredictorServer`/platform keep them unless fast ingress is requested.
"""

from __future__ import annotations

import asyncio
import logging
from typing import Awaitable, Callable, Mapping

from seldon_core_tpu.serving.wire import WireRequest, WireResponse, WireStreamResponse
from seldon_core_tpu.telemetry import flight

log = logging.getLogger(__name__)

Handler = Callable[[WireRequest], Awaitable[WireResponse]]

_MAX_BODY = 64 * 1024 * 1024  # matches the aiohttp apps' client_max_size
_MAX_HEADER = 64 * 1024

# RFC 7230 3.2.6 token charset for header field-names (must stay in lockstep
# with fastcodec.cpp is_tchar — the C parser rejects non-token names too)
_TCHAR = frozenset(
    "!#$%&'*+-.^_`|~0123456789"
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
)

_STATUS_LINES = {
    200: b"HTTP/1.1 200 OK\r\n",
    204: b"HTTP/1.1 204 No Content\r\n",
    400: b"HTTP/1.1 400 Bad Request\r\n",
    401: b"HTTP/1.1 401 Unauthorized\r\n",
    404: b"HTTP/1.1 404 Not Found\r\n",
    411: b"HTTP/1.1 411 Length Required\r\n",
    413: b"HTTP/1.1 413 Payload Too Large\r\n",
    500: b"HTTP/1.1 500 Internal Server Error\r\n",
    503: b"HTTP/1.1 503 Service Unavailable\r\n",
    504: b"HTTP/1.1 504 Gateway Timeout\r\n",
}


def _status_line(code: int) -> bytes:
    return _STATUS_LINES.get(code) or f"HTTP/1.1 {code} Status\r\n".encode()


class PyHead:
    """One accepted request head from the pure-Python fallback parser."""

    __slots__ = ("method", "path", "headers", "clen", "body_start")

    def __init__(self, method, path, headers, clen, body_start):
        self.method = method
        self.path = path
        self.headers = headers
        self.clen = clen
        self.body_start = body_start


def parse_head_py(raw: bytes) -> "PyHead | int | tuple[int, bytes]":
    """The fallback head parse + framing policy, as a PURE function.

    Returns a PyHead (request accepted; body may still be streaming in), 0
    (head incomplete — read more), or ``(status, message)`` to reject. This
    is the semantic reference the C fast path (native/fastcodec.cpp
    http_parse_head + HttpProtocol._dispatch_parsed's policy) must agree
    with — tests/test_fast_http.py fuzzes the two against each other."""
    head_end = raw.find(b"\r\n\r\n")
    if head_end < 0:
        if len(raw) > _MAX_HEADER:
            return (400, b"header too large")
        return 0
    lines = raw[:head_end].split(b"\r\n")
    if any(b"\n" in ln or b"\r" in ln for ln in lines):
        # bare LF/CR anywhere in the head (request line included): an
        # LF-tolerant front proxy would see an extra line (e.g. a hidden
        # Transfer-Encoding header) where we see one — reject, matching
        # the C parser's whole-head CRLF discipline
        return (400, b"bad line terminator")
    try:
        method, path, _ = lines[0].decode("latin-1").split(" ", 2)
    except ValueError:
        return (400, b"bad request line")
    if not method or not path:
        return (400, b"bad request line")
    headers: dict[str, str] = {}
    for line in lines[1:]:
        if line[:1] in (b" ", b"\t"):
            # obs-fold continuation, colon or not — same rule as the C
            # parser (a colon-less fold would silently skip below)
            return (400, b"bad header name")
        k, sep, v = line.decode("latin-1").partition(":")
        if not sep:
            continue
        if not k or any(c not in _TCHAR for c in k):
            # RFC 7230 3.2.4/3.2.6: field-name must be pure token chars —
            # rejects "Transfer-Encoding : chunked" (space before colon)
            # and form-feed/NBSP/NUL variants, same as the C path
            return (400, b"bad header name")
        key = k.lower()
        # OWS is SP/HT ONLY (RFC 7230 3.2.3): str.strip()'s wider notion of
        # whitespace (form feed, vertical tab, NEL) would accept
        # "Content-Length:\x0c10" that the C parser rejects — divergence in
        # the desync family the fuzz test exists to catch
        v = v.strip(" \t")
        if key == "content-length":
            if not (v.isascii() and v.isdigit()):
                # digits-only: bare int() would also accept '+4', '-4',
                # '1_0' and unicode digits, and a negative value slips
                # past every downstream bound check
                return (400, b"bad content-length")
            if key in headers and int(headers[key]) != int(v):
                # RFC 7230 3.3.2: differing duplicate Content-Length
                # values MUST be rejected (CL.CL desync); numeric
                # comparison so '4' vs '04' tolerates, like the C path
                return (400, b"conflicting content-length")
        headers[key] = v
    if "transfer-encoding" in headers:
        # same rule as the C path: any TE (chunked, "gzip, chunked", …) is
        # rejected outright — never frame a TE request by CL
        return (400, b"Transfer-Encoding not supported")
    if "content-length" in headers:
        clen = int(headers["content-length"])
    elif method in ("GET", "HEAD", "DELETE", "OPTIONS"):
        clen = 0
    else:
        # POST/PUT without Content-Length (incl. chunked): out of this
        # server's contract — guessing clen=0 would misparse the body
        # bytes as the next request line
        return (411, b"Content-Length required")
    if clen > _MAX_BODY:
        return (413, b"body too large")
    return PyHead(method, path, headers, clen, head_end + 4)


class HttpProtocol(asyncio.Protocol):
    """One connection. Requests are processed strictly in order (no
    pipelining concurrency): parse -> schedule handler task -> write
    response -> parse next. Incoming bytes buffer while a handler runs."""

    def __init__(self, routes: Mapping[tuple[str, str], Handler]):
        self._routes = routes
        self._transport: asyncio.Transport | None = None
        self._buf = bytearray()
        self._busy = False
        self._closing = False
        # head parsed, body still streaming in: cache the parse so large
        # uploads don't re-parse (or re-copy) the buffer per TCP chunk
        self._pending_head = None

    # ------------------------------------------------------------- plumbing
    def connection_made(self, transport: asyncio.BaseTransport) -> None:
        self._transport = transport  # type: ignore[assignment]

    def connection_lost(self, exc: Exception | None) -> None:
        self._closing = True
        self._transport = None

    def data_received(self, data: bytes) -> None:
        self._buf += data
        if not self._busy:
            self._try_dispatch()

    # -------------------------------------------------------------- parsing
    def _try_dispatch(self) -> None:
        """Parse one complete request from the buffer and run its handler.
        The C head parser (native/fastcodec.cpp http_parse_head) handles the
        hot path in one pass; the Python parse below stays as the fallback
        and the semantic reference."""
        from seldon_core_tpu import native

        if self._pending_head is not None:
            # head already parsed — only waiting on body bytes (either
            # parser's head object; both cache here)
            if isinstance(self._pending_head, PyHead):
                self._dispatch_py(self._pending_head)
            else:
                self._dispatch_parsed(self._pending_head)
            return
        # only the head region crosses into C: copying the whole buffer
        # would make chunked large-body uploads O(n^2) in memcpy
        parsed = native.parse_http_head(
            bytes(self._buf[: _MAX_HEADER + 4])
        )
        if parsed is not None:
            self._dispatch_parsed(parsed)
            return
        self._try_dispatch_py()

    def _dispatch_parsed(self, parsed) -> None:
        from seldon_core_tpu import native

        buf = self._buf
        if parsed == 0:
            if len(buf) > _MAX_HEADER:
                self._respond_simple(400, b"header too large")
                self._close()
            return
        if parsed == -1:
            self._respond_simple(400, b"bad request")
            self._close()
            return
        flags = parsed.flags
        method = parsed.method
        if flags & native.HDRF_HAS_TE:
            # reject ANY Transfer-Encoding, even alongside Content-Length:
            # framing by CL while a TE-honoring front proxy frames by
            # chunked is the classic TE.CL request-smuggling desync
            self._respond_simple(400, b"Transfer-Encoding not supported")
            self._close()
            return
        if flags & native.HDRF_HAS_CLEN:
            clen = parsed.content_length
        elif method in ("GET", "HEAD", "DELETE", "OPTIONS"):
            clen = 0
        else:
            self._respond_simple(411, b"Content-Length required")
            self._close()
            return
        if clen > _MAX_BODY:
            self._respond_simple(413, b"body too large")
            self._close()
            return
        if len(buf) - parsed.body_start < clen:
            self._pending_head = parsed  # wait for the body; parse once
            return
        self._pending_head = None
        body = bytes(buf[parsed.body_start : parsed.body_start + clen])
        # gRPC-Web paths carry auth as arbitrary metadata headers (the
        # reference's oauth_token key) that the C parser's two fixed
        # capture slots don't cover — keep the validated head for a
        # targeted scan below, before the buffer is consumed. The W3C
        # traceparent propagation header (trace continuation across remote
        # engine hops) gets the same treatment, gated on a copy-free find
        # so untraced traffic pays nothing; the header name is lowercase
        # per the W3C spec (the Python fallback parser captures any case).
        head_bytes = (
            bytes(buf[: parsed.body_start])
            if parsed.path.startswith("/seldon.")
            else b""
        )
        if not head_bytes and (
            buf.find(b"traceparent", 0, parsed.body_start) != -1
            or buf.find(b"Traceparent", 0, parsed.body_start) != -1
        ):
            head_bytes = bytes(buf[: parsed.body_start])
        del buf[: parsed.body_start + clen]

        headers: dict[str, str] = {}
        if parsed.content_type is not None:
            headers["content-type"] = parsed.content_type
        if parsed.authorization is not None:
            headers["authorization"] = parsed.authorization
        if head_bytes:
            token = _header_from_head(head_bytes, b"oauth_token")
            if token is not None:
                headers["oauth_token"] = token
            tp = _header_from_head(head_bytes, b"traceparent")
            if tp is not None:
                headers["traceparent"] = tp
        path = parsed.path.split("?", 1)[0]
        req = WireRequest(
            method=method,
            path=path,
            headers=headers,
            body=body,
            declared_ctype=bool(flags & native.HDRF_HAS_CTYPE),
        )
        handler = self._routes.get((method, path))
        keep_alive = not (flags & native.HDRF_CONN_CLOSE)
        self._busy = True
        task = asyncio.ensure_future(self._run(handler, req, keep_alive))
        task.add_done_callback(self._on_handler_done)

    def _try_dispatch_py(self) -> None:
        # only the head region is copied/parsed — slicing the whole buffer
        # would make large-body uploads O(n^2) in memcpy per TCP chunk
        parsed = parse_head_py(bytes(self._buf[: _MAX_HEADER + 4]))
        if parsed == 0:
            return  # head incomplete; wait for more data
        if isinstance(parsed, tuple):
            status, text = parsed
            self._respond_simple(status, text)
            self._close()
            return
        self._dispatch_py(parsed)

    def _dispatch_py(self, parsed: "PyHead") -> None:
        buf = self._buf
        method, path, headers, clen, body_start = (
            parsed.method,
            parsed.path,
            parsed.headers,
            parsed.clen,
            parsed.body_start,
        )
        if len(buf) - body_start < clen:
            # wait for the body; cache the parse (mirrors the C path — a
            # large upload must not re-copy + re-parse per TCP chunk)
            self._pending_head = parsed
            return
        self._pending_head = None
        body = bytes(buf[body_start : body_start + clen])
        del buf[: body_start + clen]

        path = path.split("?", 1)[0]
        handler = self._routes.get((method, path))
        keep_alive = headers.get("connection", "").lower() != "close"
        req = WireRequest(
            method=method,
            path=path,
            headers=headers,
            body=body,
            declared_ctype="content-type" in headers,
        )
        self._busy = True
        task = asyncio.ensure_future(self._run(handler, req, keep_alive))
        task.add_done_callback(self._on_handler_done)

    # ------------------------------------------------------------- handling
    async def _run(self, handler: Handler | None, req: WireRequest, keep_alive: bool) -> None:
        if handler is None:
            self._respond_simple(404, b"not found", keep_alive)
            return
        try:
            resp = await handler(req)
        except Exception:  # noqa: BLE001 - handler contract is no-raise; belt+braces
            log.exception("fast-ingress handler failed for %s", req.path)
            resp = WireResponse(status=500, body=b'{"status":"FAILURE"}')
        if isinstance(resp, WireStreamResponse):
            await self._write_stream(resp, keep_alive)
            return
        self._write_response(resp, keep_alive)

    def _on_handler_done(self, task: asyncio.Task) -> None:
        if exc := task.exception():
            log.error("fast-ingress task error: %s", exc)
        self._busy = False
        if self._transport is not None and not self._closing and self._buf:
            self._try_dispatch()

    # -------------------------------------------------------------- writing
    def _write_response(self, resp: WireResponse, keep_alive: bool = True) -> None:
        t = self._transport
        if t is None:
            return
        extra = b""
        for k, v in resp.headers.items():
            extra += f"{k}: {v}\r\n".encode()
        if resp.status == 204:
            # RFC 7230 3.3.2: a 204 MUST NOT carry Content-Length or a
            # body (CORS preflights ride this) — a desync-pedantic front
            # proxy may reject the header we'd otherwise always write
            t.write(
                _status_line(204)
                + extra
                + (b"Connection: keep-alive\r\n\r\n" if keep_alive else b"Connection: close\r\n\r\n")
            )
            if not keep_alive:
                self._close()
            return
        t.write(
            _status_line(resp.status)
            + b"Content-Type: " + resp.content_type.encode() + b"\r\n"
            + b"Content-Length: " + str(len(resp.body)).encode() + b"\r\n"
            + extra
            + (b"Connection: keep-alive\r\n\r\n" if keep_alive else b"Connection: close\r\n\r\n")
            + resp.body
        )
        if not keep_alive:
            self._close()

    async def _write_stream(self, resp: WireStreamResponse, keep_alive: bool = True) -> None:
        """Streaming (SSE) response under Transfer-Encoding: chunked — the
        one place the fast ingress emits a body it does not know the length
        of up front. Each event is one chunk, flushed as it is produced, so
        a generative client sees token i while token i+1 is still being
        decoded. Chunked framing keeps the connection reusable; a consumer
        that vanishes mid-stream just ends the write loop."""
        t = self._transport
        if t is None:
            # connection already gone: still close the event source so the
            # in-flight generation is cancelled, not left running for a
            # vanished client
            aclose = getattr(resp.events, "aclose", None)
            if aclose is not None:
                try:
                    await aclose()
                except Exception:  # noqa: BLE001 - nothing to respond to
                    log.exception("stream close failed")
            return
        extra = b""
        for k, v in resp.headers.items():
            extra += f"{k}: {v}\r\n".encode()
        t.write(
            _status_line(resp.status)
            + b"Content-Type: " + resp.content_type.encode() + b"\r\n"
            + b"Transfer-Encoding: chunked\r\n"
            + b"Cache-Control: no-cache\r\n"
            + extra
            + (b"Connection: keep-alive\r\n\r\n" if keep_alive else b"Connection: close\r\n\r\n")
        )
        try:
            async for chunk in resp.events:
                if self._transport is None or self._closing:
                    break
                if not chunk:
                    continue
                # named for a profiler session: what the decode loop's
                # thread does between a round's phases
                with flight.annotate(flight.ANN_SSE_WRITE):
                    self._transport.write(
                        f"{len(chunk):x}\r\n".encode() + chunk + b"\r\n"
                    )
        finally:
            # close the event source DETERMINISTICALLY: on client
            # disconnect the break above leaves the async generator
            # suspended, and only aclose() runs its finally blocks (which
            # cancel the in-flight generation) — waiting for GC would keep
            # a vanished client's sequences occupying KV slots
            aclose = getattr(resp.events, "aclose", None)
            if aclose is not None:
                try:
                    await aclose()
                except Exception:  # noqa: BLE001 - teardown must not mask the response
                    log.exception("stream close failed")
            if self._transport is not None and not self._closing:
                self._transport.write(b"0\r\n\r\n")
                if not keep_alive:
                    self._close()

    def _respond_simple(self, status: int, text: bytes, keep_alive: bool = False) -> None:
        self._write_response(
            WireResponse(status=status, body=text, content_type="text/plain"),
            keep_alive,
        )

    def _close(self) -> None:
        self._closing = True
        if self._transport is not None:
            self._transport.close()


async def start_fast_server(
    routes: Mapping[tuple[str, str], Handler], host: str, port: int
) -> asyncio.AbstractServer:
    loop = asyncio.get_running_loop()
    return await loop.create_server(lambda: HttpProtocol(routes), host, port)


# ----------------------------------------------------------- route builders
def engine_routes(service, state: dict, metrics=None) -> dict:
    """The engine data-plane route table (fast twin of serving/rest.py)."""
    from seldon_core_tpu.serving import wire

    async def predictions(req: WireRequest) -> WireResponse:
        return await wire.engine_predictions(service, req)

    async def predictions_stream(req: WireRequest):
        return await wire.engine_predictions_stream(service, req)

    async def feedback(req: WireRequest) -> WireResponse:
        return await wire.engine_feedback(service, req)

    async def ready(req: WireRequest) -> WireResponse:
        if state["paused"] or not service.executor.ready():
            return WireResponse.text("paused" if state["paused"] else "loading", 503)
        return WireResponse.text("ready")

    async def ping(req: WireRequest) -> WireResponse:
        return WireResponse.text("pong")

    async def pause(req: WireRequest) -> WireResponse:
        state["paused"] = True
        return WireResponse.text("paused")

    async def unpause(req: WireRequest) -> WireResponse:
        state["paused"] = False
        return WireResponse.text("unpaused")

    async def prometheus(req: WireRequest) -> WireResponse:
        m = metrics or getattr(service, "metrics", None)
        return WireResponse.text((m.export() if m is not None else b"").decode())

    routes: dict = {
        ("POST", "/api/v0.1/predictions"): predictions,
        # per-token SSE streaming for generative deployments; the buffered
        # /predictions contract above is untouched
        ("POST", "/api/v0.1/predictions/stream"): predictions_stream,
        ("POST", "/api/v0.1/feedback"): feedback,
        ("GET", "/ready"): ready,
        ("GET", "/ping"): ping,
        ("GET", "/metrics"): prometheus,
        ("GET", "/prometheus"): prometheus,
    }
    for method in ("GET", "POST"):
        routes[(method, "/pause")] = pause
        routes[(method, "/unpause")] = unpause

    # internal microservice API (reference internal-api.md) — same surface
    # as the aiohttp app
    def _unit_method(name: str):
        async def handler(req: WireRequest) -> WireResponse:
            return await wire.engine_unit_method(service, req, name)

        return handler

    for name in wire.INTERNAL_API_METHODS:
        routes[("POST", f"/{name}")] = _unit_method(name)
    return routes


def _header_from_head(head: bytes, name: bytes) -> str | None:
    """Pull ONE extra header out of a head the C parser has already
    VALIDATED (strict CRLF lines, token field-names, no obs-fold) — the C
    fast path copies out only content-type/authorization; gRPC-Web
    metadata keys like oauth_token need this targeted scan. LAST duplicate
    wins, matching both the Python fallback's dict assignment and the C
    parser's overwrite-on-match for its captured headers (C/Python
    agreement is the fuzz-enforced invariant here)."""
    target = name + b":"
    found: str | None = None
    for line in head.split(b"\r\n")[1:]:
        if line[: len(target)].lower() == target:
            found = line[len(target) :].strip(b" \t").decode("latin-1")
    return found


def gateway_routes(gw) -> dict:
    """The gateway data-plane route table (fast twin of gateway/app.py)."""
    from seldon_core_tpu.serving import wire

    async def predictions(req: WireRequest) -> WireResponse:
        return await wire.gateway_predictions(gw, req)

    async def feedback(req: WireRequest) -> WireResponse:
        return await wire.gateway_feedback(gw, req)

    async def token(req: WireRequest) -> WireResponse:
        return await wire.gateway_token(gw, req)

    async def ready(req: WireRequest) -> WireResponse:
        return WireResponse.text("ready")

    async def ping(req: WireRequest) -> WireResponse:
        return WireResponse.text("pong")

    async def prometheus(req: WireRequest) -> WireResponse:
        m = gw.metrics
        return WireResponse.text((m.export() if m is not None else b"").decode())

    async def grpc_web_predict(req: WireRequest) -> WireResponse:
        return await wire.gateway_grpc_web_predict(gw, req)

    async def grpc_web_feedback(req: WireRequest) -> WireResponse:
        return await wire.gateway_grpc_web_feedback(gw, req)

    routes = {
        ("POST", "/api/v0.1/predictions"): predictions,
        ("POST", "/api/v0.1/feedback"): feedback,
        ("POST", "/oauth/token"): token,
        ("GET", "/ready"): ready,
        ("GET", "/ping"): ping,
        ("GET", "/metrics"): prometheus,
        ("GET", "/prometheus"): prometheus,
    }
    async def grpc_web_preflight(req: WireRequest) -> WireResponse:
        # CORS preflight: browser gRPC-Web clients send OPTIONS with
        # Access-Control-Request-Headers for the non-simple content type +
        # metadata headers before the real POST
        return WireResponse(
            status=204,
            body=b"",
            content_type="text/plain",
            headers=dict(wire.GRPC_WEB_CORS_HEADERS),
        )

    # gRPC-Web unary: the ONE route table (wire.GRPC_WEB_ROUTES) shared
    # with the aiohttp gateway app, so the transports cannot drift
    for path, method in wire.GRPC_WEB_ROUTES:
        routes[("OPTIONS", path)] = grpc_web_preflight
        routes[("POST", path)] = (
            grpc_web_predict if method == "Predict" else grpc_web_feedback
        )
    return routes
