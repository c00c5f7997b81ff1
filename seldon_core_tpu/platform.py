"""All-in-one platform process: control plane + gateway + engines.

The reference splits this across three Java services and k8s (cluster-manager
operator, api-frontend gateway, one engine pod per predictor). On a TPU host
the economical shape is ONE process: deployments are applied through the
control API (or a watched directory of CR files), reconciled into in-process
executors with weights in HBM, and served through the OAuth2 gateway — no
per-request network hop anywhere in the graph.

CLI:
    python -m seldon_core_tpu.platform --port 8080 --grpc-port 5000 \
        [--watch-dir deployments/] [--apply dep.json ...] \
        [--audit-sink file://audit/] [--token-store file://tokens.jsonl]
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import signal

from aiohttp import web

from seldon_core_tpu.gateway import (
    DeploymentStore,
    Gateway,
    InProcessBackend,
    OAuthProvider,
    build_gateway_app,
    make_audit_sink,
    make_token_store,
)
from seldon_core_tpu.metrics import get_metrics
from seldon_core_tpu.operator import (
    DeploymentManager,
    add_operator_routes,
    watch_directory,
)

log = logging.getLogger(__name__)


class Platform:
    def __init__(
        self,
        *,
        token_store_url: str = "",
        audit_sink_url: str = "",
        metrics_enabled: bool = True,
        state_store_url: str = "",
        hbm_budget_bytes: int | None = None,
        allow_python_class: bool | None = None,
    ):
        self.metrics = get_metrics(metrics_enabled)
        self.oauth = OAuthProvider(token_store=make_token_store(token_store_url))
        self.store = DeploymentStore(oauth=self.oauth)
        self.backend = InProcessBackend()
        self.gateway = Gateway(
            store=self.store,
            oauth=self.oauth,
            backend=self.backend,
            audit=make_audit_sink(audit_sink_url),
            metrics=self.metrics,
        )
        self.manager = DeploymentManager(
            store=self.store,
            backend=self.backend,
            metrics=self.metrics,
            state_store_url=state_store_url,
            hbm_budget_bytes=hbm_budget_bytes,
            allow_python_class=allow_python_class,
        )
        self._fast_server = None

    def build_app(self) -> web.Application:
        app = build_gateway_app(self.gateway)
        add_operator_routes(app, self.manager)

        async def _gc_policy(request: web.Request) -> web.Response:
            # operator-invoked re-freeze for tenants applied at runtime
            # (gc_policy.py): call during a quiet window — freeze pins any
            # in-flight request state permanently
            from seldon_core_tpu.serving.gc_policy import apply_serving_gc_policy

            return web.json_response({"frozen": apply_serving_gc_policy()})

        app.router.add_post("/v1/gc-policy", _gc_policy)
        return app

    async def serve(
        self,
        host: str = "0.0.0.0",
        port: int = 8080,
        grpc_port: int | None = 5000,
        watch_dir: str | None = None,
        watch_interval_s: float = 5.0,
        watch_k8s: bool = False,
        k8s_namespace: str = "default",
        fast_ingress: bool = False,
        admin_port: int = 8082,
        grpc_mode: str = "aio",
    ):
        self._fast_server = None
        if fast_ingress:
            # data plane on the purpose-built ingress (serving/fast_http.py,
            # ~half the per-request overhead); the FULL aiohttp app — incl.
            # the control-plane API — moves to the admin port, the
            # reference engine's admin-port-8082 topology (TomcatConfig
            # additionalPorts; operator wires admin=8082)
            from seldon_core_tpu.serving.fast_http import (
                gateway_routes,
                start_fast_server,
            )

            self._fast_server = await start_fast_server(
                gateway_routes(self.gateway), host, port
            )
        app_port = admin_port if fast_ingress else port
        runner = web.AppRunner(self.build_app())
        await runner.setup()
        await web.TCPSite(runner, host, app_port).start()
        if fast_ingress:
            log.info(
                "platform fast ingress on %s:%s, admin REST on %s:%s",
                host, port, host, app_port,
            )
        else:
            log.info("platform REST on %s:%s", host, port)

        grpc_server = None
        if grpc_port:
            from seldon_core_tpu.gateway.grpc_gateway import start_gateway_grpc

            grpc_server = await start_gateway_grpc(
                self.gateway, host=host, port=grpc_port, mode=grpc_mode
            )
            log.info("platform gRPC on %s:%s (%s)", host, grpc_port, grpc_mode)

        # event-loop health probe: one tenant's host-side compute stalling
        # the shared loop is visible as seldon_tpu_event_loop_lag_ms before
        # it becomes cross-tenant p99 (alert rule in deploy/monitoring)
        from seldon_core_tpu.metrics.registry import run_loop_lag_probe

        self._lag_probe = asyncio.create_task(run_loop_lag_probe(self.metrics))
        # gen-2 GC pauses are the measured multi-tenant tail-lag source —
        # freeze boot/warmup survivors out of the scan set (gc_policy.py)
        from seldon_core_tpu.serving.gc_policy import apply_serving_gc_policy

        apply_serving_gc_policy()

        watch_task = None
        if watch_dir:
            watch_task = asyncio.create_task(
                watch_directory(self.manager, watch_dir, watch_interval_s)
            )
        elif watch_k8s:
            from seldon_core_tpu.operator.k8s_watcher import KubernetesWatcher

            # construct BEFORE create_task: a missing kubernetes client must
            # fail the boot loudly, not kill a background task silently
            watcher = KubernetesWatcher(self.manager, namespace=k8s_namespace)
            watch_task = asyncio.create_task(watcher.run(interval_s=watch_interval_s))
        return runner, grpc_server, watch_task


async def _amain(args) -> None:
    platform = Platform(
        token_store_url=args.token_store,
        audit_sink_url=args.audit_sink,
        state_store_url=args.state_store,
        hbm_budget_bytes=int(args.hbm_budget_gb * (1 << 30))
        if args.hbm_budget_gb
        else None,
        # None -> DeploymentManager falls back to SELDON_TPU_ALLOW_PYTHON_CLASS
        allow_python_class=True if args.allow_python_class else None,
    )
    for path in args.apply or []:
        import json as _json

        with open(path) as f:
            result = platform.manager.apply(_json.load(f))
        log.info("apply %s: %s %s", path, result.action, result.message)

    runner, grpc_server, watch_task = await platform.serve(
        host=args.host,
        port=args.port,
        grpc_port=args.grpc_port,
        watch_dir=args.watch_dir,
        watch_k8s=args.watch_k8s,
        k8s_namespace=args.k8s_namespace,
        fast_ingress=args.fast_ingress,
        admin_port=args.admin_port,
        grpc_mode=args.grpc_mode,
    )

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()

    lag_probe = getattr(platform, "_lag_probe", None)
    if lag_probe is not None:
        lag_probe.cancel()
    if watch_task is not None:
        watch_task.cancel()
    if grpc_server is not None:
        await grpc_server.stop(5)
    if platform._fast_server is not None:
        platform._fast_server.close()
        await platform._fast_server.wait_closed()
    await runner.cleanup()


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8080)
    parser.add_argument("--grpc-port", type=int, default=5000)
    watch_group = parser.add_mutually_exclusive_group()
    watch_group.add_argument("--watch-dir", default=None)
    watch_group.add_argument(
        "--watch-k8s",
        action="store_true",
        help="watch SeldonDeployment CRs on the Kubernetes API server "
        "(needs the 'kubernetes' package); mutually exclusive with --watch-dir",
    )
    parser.add_argument("--k8s-namespace", default="default")
    parser.add_argument("--apply", nargs="*", help="CR JSON files to apply at boot")
    parser.add_argument("--token-store", default="", help="'' | file://p | redis://h")
    parser.add_argument("--audit-sink", default="", help="'' | mem:// | file://d | kafka://h")
    parser.add_argument("--state-store", default="", help="'' | file://d | redis://h (router state)")
    parser.add_argument(
        "--hbm-budget-gb",
        type=float,
        default=0.0,
        help="reject deployments whose params would exceed this HBM budget (0 = unlimited)",
    )
    parser.add_argument("--no-grpc", action="store_true")
    parser.add_argument(
        "--grpc-mode",
        choices=("aio", "sync"),
        default="aio",
        help="gRPC ingress implementation: 'aio' (pure grpc.aio — fastest "
        "when the backend shares the core with the event loop) or 'sync' "
        "(C-core server + one loop bridge per RPC — the pick for "
        "multi-core hosts; see docs/reference/external-api.md section 5)",
    )
    parser.add_argument(
        "--fast-ingress",
        action="store_true",
        help="serve the data plane on the purpose-built HTTP ingress "
        "(serving/fast_http.py, lower per-request overhead) and move the "
        "full REST app incl. the control-plane API to --admin-port",
    )
    parser.add_argument(
        "--admin-port",
        type=int,
        default=8082,  # the reference engine's admin port
        help="control-plane/admin REST port when --fast-ingress is on",
    )
    parser.add_argument(
        "--allow-python-class",
        action="store_true",
        help="let CRs mount local user classes in-process (PYTHON_CLASS "
        "implementation) — CR authors gain code execution in this process, "
        "so only enable when every CR source is trusted",
    )
    args = parser.parse_args()
    if args.no_grpc:
        args.grpc_port = None
    logging.basicConfig(level=logging.INFO)
    from seldon_core_tpu.utils.compile_cache import enable_compile_cache

    log.info("compile cache: %s", enable_compile_cache())
    asyncio.run(_amain(args))


if __name__ == "__main__":
    main()
