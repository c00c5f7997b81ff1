"""Predictor server bootstrap: one process = one host's data plane.

Parity: the reference engine pod (App.java + EnginePredictor.init +
SeldonGrpcServer + Tomcat): decode the graph from env/file, build the
executor, warm up XLA programs, serve REST (ENGINE_SERVER_PORT, default
8000) + gRPC (ENGINE_SERVER_GRPC_PORT, default 5000), drain gracefully on
shutdown (the reference drains Tomcat for 20 s; we stop accepting, flush the
micro-batcher, then exit).

CLI:
    python -m seldon_core_tpu.serving.server --deployment dep.json \
        [--predictor NAME] [--port 8000] [--grpc-port 5000] [--no-batch]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal

from aiohttp import web

from seldon_core_tpu.engine.executor import GraphExecutor, build_executor
from seldon_core_tpu.graph.defaulting import default_deployment
from seldon_core_tpu.graph.spec import PredictorSpec, SeldonDeployment
from seldon_core_tpu.graph.validation import validate_deployment
from seldon_core_tpu.metrics import get_metrics
from seldon_core_tpu.serving.batcher import MicroBatcher, make_batcher
from seldon_core_tpu.serving.rest import build_app
from seldon_core_tpu.serving.service import PredictionService
from seldon_core_tpu.utils import env as envmod

GRACE_DRAIN_S = float(os.environ.get(envmod.ENGINE_DRAIN_SECONDS, "5"))


class PredictorServer:
    def __init__(
        self,
        predictor: PredictorSpec,
        *,
        deployment_name: str = "",
        enable_batching: bool = True,
        metrics_enabled: bool = True,
        mesh=None,
    ):
        self.predictor = predictor
        self.deployment_name = deployment_name
        self.metrics = get_metrics(metrics_enabled)
        context: dict = {}
        if mesh is None:
            from seldon_core_tpu.parallel.mesh import mesh_from_spec

            mesh = mesh_from_spec(predictor.tpu.mesh)
        context["mesh"] = mesh
        self.mesh = mesh

        def feedback_hook(unit_name: str, reward: float) -> None:
            self.metrics.feedback(self.deployment_name, predictor.name, unit_name, reward)

        def unit_call_hook(unit_name: str, method: str, duration_s: float) -> None:
            self.metrics.unit_call(
                self.deployment_name, predictor.name, unit_name, method, duration_s
            )

        def shadow_hook(shadow_unit: str, agree: bool) -> None:
            self.metrics.shadow_compare(
                self.deployment_name, predictor.name, shadow_unit, agree
            )

        from seldon_core_tpu.metrics.registry import MetricsResilienceEvents

        self.executor: GraphExecutor = build_executor(
            predictor,
            context=context,
            feedback_metrics_hook=feedback_hook,
            unit_call_hook=unit_call_hook,
            shadow_compare_hook=shadow_hook,
            resilience_events=MetricsResilienceEvents(self.metrics, deployment_name),
        )
        # generative tier: a single-node decoder deployment with
        # tpu.decode_slots > 0 gets the continuous-batching decode loop;
        # the fused whole-batch apply stays as the correctness oracle (and
        # the path every other deployment keeps)
        from seldon_core_tpu.serving.decode_scheduler import scheduler_for_executor

        self.decode_scheduler = scheduler_for_executor(
            self.executor,
            predictor.tpu,
            metrics=self.metrics,
            deployment_name=deployment_name,
        )
        self.batcher = (
            make_batcher(
                predictor.tpu,
                self.executor.execute,
                execute_many=self.executor.execute_many,
                metrics=self.metrics,
                deployment_name=deployment_name,
                decode_scheduler=self.decode_scheduler,
            )
            if enable_batching
            else None
        )
        self.service = PredictionService(
            self.executor,
            deployment_name=deployment_name,
            predictor_name=predictor.name,
            batcher=self.batcher,
            metrics=self.metrics,
            decode_npy=predictor.tpu.decode_npy_bindata,
            decode_scheduler=self.decode_scheduler,
            deadline_ms=predictor.tpu.deadline_ms,
        )
        self.state = {"paused": False}
        self.app = build_app(self.service, self.state, metrics=self.metrics)
        self._runner: web.AppRunner | None = None
        self._fast_server = None
        self._grpc_server = None

    # ------------------------------------------------------------ lifecycle
    async def start(
        self,
        host: str = "0.0.0.0",
        port: int = 8000,
        grpc_port: int | None = 5000,
        fast_ingress: bool = False,
    ):
        if fast_ingress:
            # purpose-built data-plane HTTP server (serving/fast_http.py):
            # same wire-core handlers, roughly half the per-request server
            # overhead of the general aiohttp app
            from seldon_core_tpu.serving.fast_http import (
                engine_routes,
                start_fast_server,
            )

            self._fast_server = await start_fast_server(
                engine_routes(self.service, self.state, metrics=self.metrics),
                host,
                port,
            )
        else:
            self._runner = web.AppRunner(self.app)
            await self._runner.setup()
            site = web.TCPSite(self._runner, host, port)
            await site.start()
        # event-loop health probe (seldon_tpu_event_loop_lag_ms): anything
        # stalling the loop is visible here before it becomes cross-request
        # p99
        from seldon_core_tpu.metrics.registry import run_loop_lag_probe

        self._lag_probe = asyncio.create_task(run_loop_lag_probe(self.metrics))
        # gen-2 GC pauses were the measured multi-tenant tail-lag source
        # (70-100 ms with 10^5 live objects) — freeze warmup survivors out
        # of the scan set before taking traffic
        from seldon_core_tpu.serving.gc_policy import apply_serving_gc_policy

        apply_serving_gc_policy()
        if grpc_port:
            try:
                from seldon_core_tpu.serving.grpc_server import start_grpc_server

                self._grpc_server = await start_grpc_server(self.service, host, grpc_port)
            except ImportError:
                self._grpc_server = None

    async def stop(self):
        self.state["paused"] = True  # readiness false -> LB drains
        await asyncio.sleep(0)
        probe = getattr(self, "_lag_probe", None)
        if probe is not None:
            probe.cancel()
        if self.batcher is not None:
            await self.batcher.close()
        if self.decode_scheduler is not None:
            await self.decode_scheduler.close()
        # let in-flight SHADOW mirror walks finish BEFORE closing the remote
        # channels/session they may still be using — the shutdown window's
        # candidate-validation traffic must not be lost or error spuriously
        await self.executor.drain_shadows()
        if self._grpc_server is not None:
            await self._grpc_server.stop(GRACE_DRAIN_S)
        if self._fast_server is not None:
            self._fast_server.close()
            await self._fast_server.wait_closed()
        if self._runner is not None:
            await self._runner.cleanup()
        # release remote-unit channels + the shared HTTP pool
        from seldon_core_tpu.engine.remote import RemoteUnit, _RestSession

        for node in self.executor.root.walk():
            if isinstance(node.unit, RemoteUnit):
                await node.unit.close()
        await _RestSession.close()

    def warmup(self):
        """Compile all batch buckets before serving (XLA first-compile cost
        must not land on a live request)."""
        for node in self.executor.root.walk():
            runtime = getattr(node.unit, "runtime", None)
            if runtime is not None and getattr(runtime, "feature_shape", None) is not None:
                runtime.warmup()
        if self.decode_scheduler is not None:
            self.decode_scheduler.warmup()


def _prepare(pred: PredictorSpec, dep_name: str) -> tuple[PredictorSpec, str]:
    """Default + validate uniformly, whichever config channel delivered the
    spec (file, env, or fallback) — the env path must not skip validation."""
    from seldon_core_tpu.graph.spec import DeploymentSpec

    dep = SeldonDeployment(spec=DeploymentSpec(name=dep_name or "default", predictors=[pred]))
    dep = default_deployment(dep)
    validate_deployment(dep)
    return dep.spec.predictors[0], dep.spec.name


def load_predictor_from_args(args) -> tuple[PredictorSpec, str]:
    if args.deployment:
        with open(args.deployment) as f:
            dep = SeldonDeployment.from_dict(json.load(f))
        dep = default_deployment(dep)
        validate_deployment(dep)
        preds = {p.name: p for p in dep.spec.predictors}
        pred = preds[args.predictor] if args.predictor else dep.spec.predictors[0]
        return pred, dep.spec.name
    found = envmod.predictor_from_env()
    if found is not None:
        return _prepare(*found)
    return _prepare(envmod.default_predictor(), "default")


async def _amain(args):
    # multi-host boot: when the operator injects JAX_COORDINATOR_ADDRESS /
    # JAX_NUM_PROCESSES / JAX_PROCESS_ID (the way the reference injects
    # ENGINE_* env — SeldonDeploymentOperatorImpl.java:100-103), wire
    # jax.distributed BEFORE any backend/model init so the mesh spans all
    # processes of the slice. No-ops single-host. Executed end-to-end by
    # tests/test_multihost.py on two OS processes.
    from seldon_core_tpu.parallel.mesh import initialize_distributed

    initialize_distributed()
    predictor, dep_name = load_predictor_from_args(args)
    server = PredictorServer(
        predictor,
        deployment_name=dep_name,
        enable_batching=not args.no_batch,
    )
    if args.warmup:
        server.warmup()
    await server.start(port=args.port, grpc_port=args.grpc_port)
    stop_event = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop_event.set)
    print(
        f"seldon-core-tpu predictor '{predictor.name}' of deployment '{dep_name}' "
        f"serving REST :{args.port}"
        + (f" gRPC :{args.grpc_port}" if args.grpc_port else ""),
        flush=True,
    )
    await stop_event.wait()
    await server.stop()


def main(argv=None):
    parser = argparse.ArgumentParser(description="seldon-core-tpu predictor server")
    parser.add_argument("--deployment", help="SeldonDeployment JSON file")
    parser.add_argument("--predictor", help="predictor name (default: first)")
    parser.add_argument(
        "--port", type=int, default=int(os.environ.get(envmod.ENGINE_SERVER_PORT, "8000"))
    )
    parser.add_argument(
        "--grpc-port",
        type=int,
        default=int(os.environ.get(envmod.ENGINE_SERVER_GRPC_PORT, "5000")),
    )
    parser.add_argument("--no-batch", action="store_true")
    parser.add_argument("--warmup", action="store_true")
    args = parser.parse_args(argv)
    from seldon_core_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    asyncio.run(_amain(args))


if __name__ == "__main__":
    main()
