"""gRPC ingress gateway with oauth_token metadata auth.

Parity (C16): reference api-frontend SeldonGrpcServer.java +
HeaderServerInterceptor.java:42-75 — reads metadata key ``oauth_token``,
validates it against the token store, resolves the principal's deployment,
and forwards Seldon.Predict / Seldon.SendFeedback. The reference keeps a
per-deployment ManagedChannel cache (:114-132, 197-203); the in-process
backend makes that a dict lookup, and the channel-cache behavior survives in
RemoteBackend's pooled session.

Two server modes (the floor analysis is in docs/reference/external-api.md
§"gRPC ingress floor"; rates on the current host: not measured):

- ``aio`` (default): pure grpc.aio — everything on the event loop. A
  zero-logic echo already costs ~19 asyncio callback dispatches per unary
  call under cProfile, where the complete REST fast-ingress path turns a
  request around in ~2: the Python gRPC stack is the floor, not the
  gateway's auth + proto decode + encode.
- ``sync``: the C-core ``grpc.server`` with a small thread pool; HTTP/2
  framing, flow control, and proto parse run in C threads, and each RPC
  bridges ONCE into the asyncio loop (run_coroutine_threadsafe) where
  auth -> codec -> backend -> audit stay loop-confined exactly as in the
  REST path. On a single shared core the thread<->loop bridge hop costs
  more than C saves for the loop-confined batcher, so aio stays the
  default there. On multi-core hosts the C threads run beside the loop
  and ``mode='sync'`` is the right pick.
"""

from __future__ import annotations

import asyncio

import grpc

from seldon_core_tpu.core.codec_proto import (
    feedback_from_proto,
    message_from_proto,
    message_to_proto,
)
from seldon_core_tpu.core.errors import APIException
from seldon_core_tpu.core.message import SeldonMessage
from seldon_core_tpu.proto.services import add_service

OAUTH_METADATA_KEY = "oauth_token"  # HeaderServerInterceptor.java:42-44


def _gateway_methods(gw):
    """The loop-confined request coroutines shared by both server modes."""

    def _auth(metadata) -> tuple[str, object]:
        token = ""
        for key, value in metadata or ():
            if key == OAUTH_METADATA_KEY:
                token = value
                break
        principal = gw.oauth.principal(token) if token else None
        if not principal:
            from seldon_core_tpu.core.errors import ErrorCode

            raise APIException(ErrorCode.APIFE_GRPC_NO_PRINCIPAL_FOUND, "oauth_token")
        return principal, gw._deployment(principal)

    async def predict(request, metadata):
        try:
            principal, dep = _auth(metadata)
            msg = message_from_proto(request)
            # W3C trace context rides gRPC metadata exactly like the REST
            # header — forwarded so the engine continues the caller's trace
            tp = next(
                (v for k, v in metadata or () if k == "traceparent"), None
            )
            out = await gw.backend.predict(dep, msg, traceparent=tp)
            gw.audit.send(principal, msg, out)
            return message_to_proto(out)
        except APIException as e:
            msg = SeldonMessage.failure(e.error.code, e.error.message, e.info)
            return message_to_proto(msg)

    async def send_feedback(request, metadata):
        try:
            principal, dep = _auth(metadata)
            out = await gw.backend.feedback(dep, feedback_from_proto(request))
            return message_to_proto(out)
        except APIException as e:
            msg = SeldonMessage.failure(e.error.code, e.error.message, e.info)
            return message_to_proto(msg)

    return predict, send_feedback


async def start_gateway_grpc(
    gw, host: str = "0.0.0.0", port: int = 5000, mode: str = "aio"
):
    """Start the gRPC ingress. ``mode='aio'`` (default) = pure grpc.aio,
    fastest when the backend shares the core with the loop; ``mode='sync'``
    = C-core server + one loop bridge per RPC, the pick for multi-core
    hosts (see module docstring for the measured tradeoff). Both return an
    object with an async ``stop(grace)``."""
    if mode == "aio":
        return await _start_aio(gw, host, port)
    if mode != "sync":
        raise ValueError(f"grpc gateway mode must be 'sync' or 'aio', got {mode!r}")
    return await _start_sync(gw, host, port)


async def _start_aio(gw, host: str, port: int) -> grpc.aio.Server:
    server = grpc.aio.server(
        options=[
            ("grpc.max_receive_message_length", 64 * 1024 * 1024),
            ("grpc.max_send_message_length", 64 * 1024 * 1024),
        ]
    )
    predict, send_feedback = _gateway_methods(gw)

    async def predict_rpc(request, context):
        return await predict(request, context.invocation_metadata())

    async def feedback_rpc(request, context):
        return await send_feedback(request, context.invocation_metadata())

    add_service(
        server, "Seldon", {"Predict": predict_rpc, "SendFeedback": feedback_rpc}
    )
    server.add_insecure_port(f"{host}:{port}")
    await server.start()
    return server


class _SyncBridgeServer:
    """C-core grpc.server whose handlers bridge into the asyncio loop.

    The worker thread does only: deserialized-request in (C parse already
    done), ONE run_coroutine_threadsafe into the loop that owns the
    batcher/backend, blocking result wait, serialized response out (C).
    App logic stays loop-confined — the same single-writer discipline the
    REST ingress relies on, so no gateway/backend state needs locks."""

    def __init__(self, server: grpc.Server, loop: asyncio.AbstractEventLoop):
        self._server = server
        self._loop = loop

    async def stop(self, grace):
        # grpc.Server.stop is thread-safe and non-blocking; wait off-loop
        event = self._server.stop(grace)
        await asyncio.get_running_loop().run_in_executor(None, event.wait)


async def _start_sync(gw, host: str, port: int) -> _SyncBridgeServer:
    from concurrent import futures as _futures

    loop = asyncio.get_running_loop()
    predict, send_feedback = _gateway_methods(gw)

    def bridge(coro_fn):
        def handler(request, context):
            fut = asyncio.run_coroutine_threadsafe(
                coro_fn(request, context.invocation_metadata()), loop
            )
            return fut.result()

        return handler

    server = grpc.server(
        # few threads: handlers only park on the loop bridge; C-core does
        # the HTTP/2 + parse work on its own event engine threads
        _futures.ThreadPoolExecutor(max_workers=4),
        options=[
            ("grpc.max_receive_message_length", 64 * 1024 * 1024),
            ("grpc.max_send_message_length", 64 * 1024 * 1024),
        ],
    )
    add_service(
        server,
        "Seldon",
        {"Predict": bridge(predict), "SendFeedback": bridge(send_feedback)},
    )
    server.add_insecure_port(f"{host}:{port}")
    server.start()
    return _SyncBridgeServer(server, loop)
