"""Env-var config hand-off, reference-compatible.

The reference's load-bearing config mechanism is base64-JSON-in-env
(SURVEY §5.6): the operator injects ``ENGINE_PREDICTOR`` = b64(json(
PredictorSpec)) into the engine container (SeldonDeploymentOperatorImpl
.java:100-103) and the engine decodes it at boot (EnginePredictor.java:56-117).
Same contract here, same var names.
"""

from __future__ import annotations

import base64
import json
import os
from typing import Any

ENGINE_PREDICTOR = "ENGINE_PREDICTOR"
ENGINE_SELDON_DEPLOYMENT = "ENGINE_SELDON_DEPLOYMENT"
ENGINE_SERVER_PORT = "ENGINE_SERVER_PORT"  # default 8000 (CustomizationBean.java)
ENGINE_SERVER_GRPC_PORT = "ENGINE_SERVER_GRPC_PORT"  # default 5000 (SeldonGrpcServer.java:33)
ENGINE_DRAIN_SECONDS = "ENGINE_DRAIN_SECONDS"  # graceful-drain window, default 5
PREDICTIVE_UNIT_PARAMETERS = "PREDICTIVE_UNIT_PARAMETERS"
PREDICTIVE_UNIT_ID = "PREDICTIVE_UNIT_ID"
PREDICTIVE_UNIT_SERVICE_PORT = "PREDICTIVE_UNIT_SERVICE_PORT"  # default 5000
SELDON_DEPLOYMENT_ID = "SELDON_DEPLOYMENT_ID"
# state persistence for wrapped user objects (serving/microservice.py):
# store URL consumed by persistence/state.make_state_store
PERSISTENCE_STORE = "PERSISTENCE_STORE"  # default file://./.seldon_state
# redis state-store socket budget (persistence/state.RedisStateStore):
# connect AND per-op timeout in ms. A hung Redis must never wedge the
# serving loop mid-spill/preseed — operations past the budget degrade to
# skip-store (save dropped, load misses), matching the spill path's
# "store outage degrades, never aborts" contract.
PERSISTENCE_REDIS_TIMEOUT_MS = "PERSISTENCE_REDIS_TIMEOUT_MS"  # default 2000
# control-plane / tooling (not injected by the operator; read by humans'
# shells and CI): kubectl-proxy style API endpoint for the k8s watcher,
# the PYTHON_CLASS capability gate, and the release registry prefix
SELDON_TPU_K8S_API = "SELDON_TPU_K8S_API"
SELDON_TPU_ALLOW_PYTHON_CLASS = "SELDON_TPU_ALLOW_PYTHON_CLASS"
SELDON_TPU_REGISTRY = "SELDON_TPU_REGISTRY"
# loadtest/soak credentials (tools/loadtest.py; install.py wires them from
# a Secret in the rendered bundle) and the reference's test-client backdoor
# (gateway/app.py — AuthorizationServerConfiguration.java:78-96)
LOADTEST_OAUTH_KEY = "LOADTEST_OAUTH_KEY"
LOADTEST_OAUTH_SECRET = "LOADTEST_OAUTH_SECRET"
TEST_CLIENT_KEY = "TEST_CLIENT_KEY"
TEST_CLIENT_SECRET = "TEST_CLIENT_SECRET"
# RemoteUnit REST transport timeouts (engine/remote._RestSession). The
# reference bakes one 5 s total deadline into every call
# (InternalPredictionService.java:77); here connect and total are separate —
# a connect hang should fail in ~1 s while a legitimately slow model may use
# the whole total budget — and both are tunable without a rebuild.
ENGINE_REST_CONNECT_TIMEOUT_S = "ENGINE_REST_CONNECT_TIMEOUT_S"  # default 1.0
ENGINE_REST_TOTAL_TIMEOUT_S = "ENGINE_REST_TOTAL_TIMEOUT_S"  # default 5.0
# telemetry (telemetry/tracer.py reads these): process-wide tracing toggle,
# tail-sampling pool bounds, optional OTLP-JSON trace export, and the
# structured access log gate (telemetry/access_log.py)
ENGINE_TELEMETRY = "ENGINE_TELEMETRY"  # "off" disables tracing (default on)
ENGINE_TRACE_MAX_ERRORS = "ENGINE_TRACE_MAX_ERRORS"  # default 128
ENGINE_TRACE_SLOW_KEEP = "ENGINE_TRACE_SLOW_KEEP"  # default 32
ENGINE_TRACE_MAX_SAMPLED = "ENGINE_TRACE_MAX_SAMPLED"  # default 64
ENGINE_TRACE_SAMPLE_RATE = "ENGINE_TRACE_SAMPLE_RATE"  # default 0.05
ENGINE_OTLP_FILE = "ENGINE_OTLP_FILE"  # path; unset = no export
ENGINE_ACCESS_LOG = "ENGINE_ACCESS_LOG"  # "json" enables; default off
# decode-loop flight recorder (telemetry/flight.py reads these): per-round
# ring buffer kill switch + capacity. On by default — one O(1) append a
# round (PARITY.md "Instrumentation overhead"; on the chip: PERF.md, PR 26).
ENGINE_FLIGHT = "ENGINE_FLIGHT"  # "off" disables the recorder
ENGINE_FLIGHT_FRAMES = "ENGINE_FLIGHT_FRAMES"  # ring capacity, default 8192
# decode-round pipelining kill switch (serving/decode_scheduler.py): "off"
# forces the SERIAL round loop — round N+1's host phases wait for round N's
# readback instead of running under the in-flight dispatch. Default on.
ENGINE_DECODE_PIPELINE = "ENGINE_DECODE_PIPELINE"
# decode-loop sampling profiler (telemetry/profile.py reads these):
# always-on low-rate folded-stack sampler over the decode loop's thread,
# served by GET /decode/profile. "off" disables; rate default 19 Hz;
# folded-stack table bound default 512 entries (overflow counts, not grows)
ENGINE_DECODE_PROFILE = "ENGINE_DECODE_PROFILE"
ENGINE_DECODE_PROFILE_HZ = "ENGINE_DECODE_PROFILE_HZ"
ENGINE_DECODE_PROFILE_TABLE = "ENGINE_DECODE_PROFILE_TABLE"
# multi-replica decode scale-out (serving/affinity_router.py): "off"
# disables warm pre-seeding of scale-up replicas from spilled prefix-pool
# pages — new replicas then boot cold (diagnosis lever: isolates a preseed
# regression from the routing policy). Default on.
ENGINE_DECODE_REPLICA_PRESEED = "ENGINE_DECODE_REPLICA_PRESEED"


def rest_timeouts(env: dict | None = None) -> tuple[float, float]:
    """(connect_s, total_s) for the pooled REST session, env-tunable.
    Falls back to the defaults on unset OR unparsable values — a typo'd
    timeout must not take the data plane down at boot."""
    env = env if env is not None else os.environ
    out = []
    for key, default in (
        (ENGINE_REST_CONNECT_TIMEOUT_S, 1.0),
        (ENGINE_REST_TOTAL_TIMEOUT_S, 5.0),
    ):
        try:
            value = float(env.get(key, default))
        except (TypeError, ValueError):
            value = default
        out.append(value if value > 0 else default)
    return out[0], out[1]


def redis_timeout_s(env: dict | None = None) -> float:
    """Redis socket/connect timeout in SECONDS (redis-py's unit), from the
    PERSISTENCE_REDIS_TIMEOUT_MS env var. Falls back to the 2000 ms default
    on unset OR unparsable values — a typo'd timeout must not take state
    persistence down at boot."""
    env = env if env is not None else os.environ
    try:
        ms = float(env.get(PERSISTENCE_REDIS_TIMEOUT_MS, 2000.0))
    except (TypeError, ValueError):
        ms = 2000.0
    if ms <= 0:
        ms = 2000.0
    return ms / 1000.0


def encode_b64_json(obj: Any) -> str:
    return base64.b64encode(json.dumps(obj).encode()).decode("ascii")


def decode_b64_json(value: str) -> Any:
    return json.loads(base64.b64decode(value))


def predictor_from_env(env: dict | None = None):
    """Decode a PredictorSpec (or the first predictor of a full deployment)
    from the environment; returns (predictor_spec, deployment_name) or None.
    Mirrors EnginePredictor.init precedence: ENGINE_PREDICTOR, then
    ENGINE_SELDON_DEPLOYMENT, then ./deploymentdef.json, else None (caller
    falls back to the default SIMPLE_MODEL graph)."""
    from seldon_core_tpu.graph.spec import PredictorSpec, SeldonDeployment

    env = env if env is not None else dict(os.environ)
    raw = env.get(ENGINE_PREDICTOR)
    if raw:
        return PredictorSpec.model_validate(decode_b64_json(raw)), env.get(
            SELDON_DEPLOYMENT_ID, ""
        )
    raw = env.get(ENGINE_SELDON_DEPLOYMENT)
    if raw:
        dep = SeldonDeployment.from_dict(decode_b64_json(raw))
        if dep.spec.predictors:
            return dep.spec.predictors[0], dep.spec.name
    if os.path.exists("deploymentdef.json"):
        with open("deploymentdef.json") as f:
            dep = SeldonDeployment.from_dict(json.load(f))
        if dep.spec.predictors:
            return dep.spec.predictors[0], dep.spec.name
    return None


def default_predictor():
    """The reference's fallback graph when no config is present
    (EnginePredictor.java:131-150): a single SIMPLE_MODEL unit."""
    from seldon_core_tpu.graph.spec import PredictiveUnit, PredictorSpec

    return PredictorSpec(
        name="default",
        graph=PredictiveUnit.model_validate(
            {"name": "simple-model", "type": "MODEL", "implementation": "SIMPLE_MODEL"}
        ),
    )
