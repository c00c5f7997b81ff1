"""Tools: contract tester, load tester, wrap CLI, microservice runtime.

Reference test-strategy analogue (SURVEY §4): the contract test IS the
reference's de-facto model test (wrappers/tester.py + contract.json); here
it runs against a live in-process platform over real HTTP.
"""

import asyncio
import json
import os
import sys

import numpy as np
import pytest
from aiohttp import web

from seldon_core_tpu.tools.contract import generate_batch, generate_column, run as contract_run
from seldon_core_tpu.tools.loadtest import LoadStats, run_load
from seldon_core_tpu.tools.wrap import deployment_cr, wrap_model
from tests.conftest import free_port as _free_port

IRIS_CONTRACT = {
    "features": [
        {
            "name": "sepal_length",
            "dtype": "FLOAT",
            "ftype": "continuous",
            "range": [4, 8],
        },
        {
            "name": "sepal_width",
            "dtype": "FLOAT",
            "ftype": "continuous",
            "range": [2, 5],
        },
        {"name": "petal_length", "dtype": "FLOAT", "ftype": "continuous", "range": [1, 10]},
        {"name": "petal_width", "dtype": "FLOAT", "ftype": "continuous", "range": [0, 3]},
    ],
    "targets": [
        {"name": "class", "dtype": "FLOAT", "ftype": "continuous", "repeat": 3}
    ],
}


def test_generate_batch_continuous_ranges():
    rng = np.random.default_rng(0)
    names, batch = generate_batch(IRIS_CONTRACT, 16, rng)
    assert names == ["sepal_length", "sepal_width", "petal_length", "petal_width"]
    assert batch.shape == (16, 4)
    assert batch[:, 0].min() >= 4 and batch[:, 0].max() <= 8


def test_generate_batch_repeat_and_inf_range():
    contract = {
        "features": [
            {
                "name": "feat",
                "dtype": "FLOAT",
                "ftype": "continuous",
                "range": ["inf", "inf"],
                "repeat": 3,
            }
        ]
    }
    rng = np.random.default_rng(0)
    names, batch = generate_batch(contract, 4, rng)
    assert names == ["feat_0", "feat_1", "feat_2"]
    assert batch.shape == (4, 3)


def test_generate_categorical_strings():
    contract = {
        "features": [
            {
                "name": "color",
                "dtype": "STRING",
                "ftype": "categorical",
                "values": ["red", "green"],
            }
        ]
    }
    rng = np.random.default_rng(0)
    names, rows = generate_batch(contract, 5, rng)
    assert names == ["color"]
    assert all(r[0] in ("red", "green") for r in rows)


def _iris_cr(name="irisdep", key="lkey"):
    return {
        "apiVersion": "machinelearning.seldon.io/v1alpha1",
        "kind": "SeldonDeployment",
        "metadata": {"name": name},
        "spec": {
            "name": name,
            "oauth_key": key,
            "oauth_secret": "lsec",
            "predictors": [
                {
                    "name": "p",
                    "graph": {
                        "name": "ab",
                        "type": "ROUTER",
                        "implementation": "RANDOM_ABTEST",
                        "parameters": [
                            {"name": "ratioA", "value": "0.5", "type": "FLOAT"}
                        ],
                        "children": [
                            {
                                "name": "a",
                                "type": "MODEL",
                                "implementation": "JAX_MODEL",
                                "parameters": [
                                    {"name": "model", "value": "iris_logistic", "type": "STRING"}
                                ],
                            },
                            {
                                "name": "b",
                                "type": "MODEL",
                                "implementation": "JAX_MODEL",
                                "parameters": [
                                    {"name": "model", "value": "iris_mlp", "type": "STRING"}
                                ],
                            },
                        ],
                    },
                }
            ],
        },
    }


async def test_contract_and_loadtest_against_live_platform():
    """Boot the platform on a real port; run the contract tester (stdlib
    urllib, sync -> executor) and the async load tester against it, with the
    bandit feedback loop closed."""
    from seldon_core_tpu.platform import Platform

    platform = Platform(metrics_enabled=False)
    platform.manager.apply(_iris_cr())
    port = _free_port()
    runner, _, _ = await platform.serve(
        host="127.0.0.1", port=port, grpc_port=None, watch_dir=None
    )
    try:
        loop = asyncio.get_running_loop()
        responses = await loop.run_in_executor(
            None,
            lambda: contract_run(
                IRIS_CONTRACT,
                "127.0.0.1",
                port,
                rounds=3,
                batch_size=4,
                oauth_key="lkey",
                oauth_secret="lsec",
                seed=0,
            ),
        )
        assert len(responses) == 3
        for r in responses:
            assert np.asarray(r["data"]["ndarray"]).shape == (4, 3)
            assert "ab" in r["meta"]["routing"]  # router recorded its branch

        stats = await run_load(
            f"http://127.0.0.1:{port}",
            users=4,
            duration_s=1.0,
            features=4,
            oauth_key="lkey",
            oauth_secret="lsec",
            route_rewards=[0.2, 0.9],
        )
        summary = stats.summary()
        assert summary["errors"] == 0
        assert summary["requests"] > 0
        assert summary["feedback_sent"] > 0  # bandit loop closed
        assert summary["p99_ms"] >= summary["p50_ms"]
    finally:
        await runner.cleanup()


async def test_loadtest_multiprocess_workers_merge_stats():
    """Distributed load generation (VERDICT r3 Missing #2 / Next #4): N
    worker processes against a live platform, stats merged from raw latency
    dumps. Reference: locust master/slave (predict_rest_locust.py:17-30)."""
    from seldon_core_tpu.platform import Platform
    from seldon_core_tpu.tools.loadtest import run_load_multiprocess

    platform = Platform(metrics_enabled=False)
    platform.manager.apply(_iris_cr())
    port = _free_port()
    runner, _, _ = await platform.serve(
        host="127.0.0.1", port=port, grpc_port=None, watch_dir=None
    )
    try:
        loop = asyncio.get_running_loop()
        stats = await loop.run_in_executor(
            None,
            lambda: run_load_multiprocess(
                f"http://127.0.0.1:{port}",
                workers=2,
                users=4,
                duration_s=1.5,
                features=4,
                oauth_key="lkey",
                oauth_secret="lsec",
                static_payload=True,
            ),
        )
        summary = stats.summary()
        assert summary["workers"] == 2
        assert summary["errors"] == 0
        # merged latency distribution is the union of both workers' dumps:
        # EACH worker must have contributed (a silently-dropped .npy would
        # shrink requests and latencies together, so check per-worker)
        assert len(stats.worker_requests) == 2
        assert all(n > 0 for n in stats.worker_requests)
        assert sum(stats.worker_requests) == summary["requests"]
        assert summary["p99_ms"] >= summary["p50_ms"] > 0
    finally:
        await runner.cleanup()


def test_loadstats_windowed_rate_survives_drain_stall():
    """One multi-second stall at the end of a closed-loop run must not
    poison throughput: the rate counts completions inside the intended
    window; drain-tail requests keep their (real) latencies in the
    percentiles but stay out of the denominator."""
    s = LoadStats()
    s.started = 100.0
    s.deadline = 110.0  # 10 s window
    # 1000 requests completed in-window, 32 held hostage by a 90 s stall
    s.latencies_s = [0.01] * 1000 + [90.0] * 32
    s.completions_s = [100.0 + i * 0.01 for i in range(1000)] + [200.0] * 32
    s.finished = 200.0  # last drain completion
    out = s.summary()
    assert out["requests"] == 1032
    assert out["drain_requests"] == 32
    assert out["requests_per_sec"] == 100.0  # 1000 / 10 s, NOT 1032 / 100 s
    assert out["p99_ms"] >= 10000  # the stall is still visible in the tail
    # no deadline set (direct construction): legacy wall-clock behavior
    legacy = LoadStats(latencies_s=[0.01] * 10, started=0.0, finished=1.0)
    assert legacy.summary()["requests_per_sec"] == 10.0


def test_wrap_model_bundle(tmp_path):
    model_dir = tmp_path / "MyModel"
    model_dir.mkdir()
    (model_dir / "MyModel.py").write_text(
        "class MyModel:\n"
        "    def predict(self, X, names):\n"
        "        return X.sum(axis=1, keepdims=True)\n"
    )
    out = wrap_model(str(model_dir), "MyModel", "0.1", "myrepo")
    assert os.path.isfile(os.path.join(out, "Dockerfile"))
    dockerfile = open(os.path.join(out, "Dockerfile")).read()
    assert "seldon_core_tpu.serving.microservice" in dockerfile
    assert '"MyModel"' in dockerfile
    dep = json.load(open(os.path.join(out, "deployment.json")))
    assert dep["spec"]["predictors"][0]["componentSpec"]["containers"][0][
        "image"
    ] == "myrepo/MyModel:0.1"
    # build artifacts are executable
    assert os.access(os.path.join(out, "build_image.sh"), os.X_OK)
    # re-wrap without force fails; with force succeeds
    with pytest.raises(FileExistsError):
        wrap_model(str(model_dir), "MyModel", "0.1", "myrepo")
    wrap_model(str(model_dir), "MyModel", "0.2", "myrepo", force=True)


async def test_microservice_serves_user_class(tmp_path):
    """Full C18 loop: user class file -> microservice REST server -> predict,
    with typed PREDICTIVE_UNIT_PARAMETERS constructor injection."""
    from seldon_core_tpu.serving.microservice import (
        load_user_object,
        parse_parameters,
        serve_microservice,
    )

    model_dir = tmp_path / "m"
    model_dir.mkdir()
    (model_dir / "Scaler.py").write_text(
        "class Scaler:\n"
        "    def __init__(self, factor=1.0):\n"
        "        self.factor = factor\n"
        "    def predict(self, X, names):\n"
        "        return X * self.factor\n"
    )
    params = parse_parameters(
        json.dumps([{"name": "factor", "value": "2.5", "type": "FLOAT"}])
    )
    user = load_user_object("Scaler", str(model_dir), params)
    assert user.factor == 2.5

    port = _free_port()
    runner, grpc_server, _ = await serve_microservice(
        user, "Scaler", "MODEL", host="127.0.0.1", http_port=port
    )
    try:
        import aiohttp

        async with aiohttp.ClientSession() as session:
            async with session.post(
                f"http://127.0.0.1:{port}/api/v0.1/predictions",
                json={"data": {"ndarray": [[1.0, 2.0]]}},
            ) as resp:
                assert resp.status == 200
                body = await resp.json()
        assert body["data"]["ndarray"] == [[2.5, 5.0]]
    finally:
        await runner.cleanup()
    # model_dir leaves sys.path automatically after the load (sibling
    # isolation, ADVICE r2)
    assert str(model_dir) not in sys.path


async def test_microservice_grpc_only_has_no_rest(tmp_path):
    from seldon_core_tpu.serving.microservice import serve_microservice

    class Ident:
        def predict(self, X, names):
            return X

    gport = _free_port()
    runner, grpc_server, _ = await serve_microservice(
        Ident(), "Ident", "MODEL", host="127.0.0.1",
        grpc_port=gport, enable_rest=False,
    )
    try:
        assert runner is None  # no REST listener bound
        import grpc
        from seldon_core_tpu.proto import prediction_pb2 as pb
        from seldon_core_tpu.proto.services import ServiceStub

        async with grpc.aio.insecure_channel(f"127.0.0.1:{gport}") as channel:
            stub = ServiceStub(channel, "Model")
            req = pb.SeldonMessage()
            req.data.ndarray.values.add().list_value.values.add().number_value = 3.0
            reply = await stub.Predict(req)
            assert reply.data.ndarray.values[0].list_value.values[0].number_value == 3.0
    finally:
        await grpc_server.stop(None)


def test_contract_mixed_categorical_and_continuous_is_json_safe():
    contract = {
        "features": [
            {"name": "color", "dtype": "STRING", "ftype": "categorical",
             "values": ["red", "green"]},
            {"name": "x", "dtype": "FLOAT", "ftype": "continuous", "range": [0, 1]},
        ]
    }
    rng = np.random.default_rng(0)
    names, rows = generate_batch(contract, 4, rng)
    json.dumps({"data": {"names": names, "ndarray": rows}})  # must not raise
    assert isinstance(rows[0][1], float)


async def test_microservice_outlier_detector_service_type(tmp_path):
    """OUTLIER_DETECTOR service tier (reference microservice.py:140,162 +
    outlier_detector_microservice.py): user score() runs on /transform-input
    AND on the prediction path, tagging meta.tags.outlierScore while the
    data passes through unchanged."""
    import sys as _sys

    from seldon_core_tpu.serving.microservice import (
        load_user_object,
        serve_microservice,
    )

    model_dir = tmp_path / "od"
    model_dir.mkdir()
    (model_dir / "MaxScore.py").write_text(
        "import numpy as np\n"
        "class MaxScore:\n"
        "    def score(self, X, names):\n"
        "        return float(np.max(np.abs(X)))\n"
    )
    user = load_user_object("MaxScore", str(model_dir), {})
    port = _free_port()
    runner, grpc_server, _ = await serve_microservice(
        user, "MaxScore", "OUTLIER_DETECTOR", host="127.0.0.1", http_port=port
    )
    try:
        import aiohttp

        async with aiohttp.ClientSession() as session:
            async with session.post(
                f"http://127.0.0.1:{port}/api/v0.1/predictions",
                json={"data": {"ndarray": [[1.0, -7.5, 2.0]]}},
            ) as resp:
                assert resp.status == 200
                body = await resp.json()
        assert body["meta"]["tags"]["outlierScore"] == 7.5
        assert body["data"]["ndarray"] == [[1.0, -7.5, 2.0]]  # passthrough
    finally:
        await runner.cleanup()
    assert str(model_dir) not in _sys.path


def test_microservice_cli_accepts_outlier_detector():
    from seldon_core_tpu.serving.microservice import SERVICE_TYPES

    assert "OUTLIER_DETECTOR" in SERVICE_TYPES


async def test_audit_tail_reads_back_served_traffic(tmp_path):
    """The audit consumer (reference kafka read_predictions.py parity) reads
    the JSONL stream the gateway's sink wrote, with client attribution."""
    from seldon_core_tpu.core.message import SeldonMessage
    from seldon_core_tpu.gateway.audit import JsonlAuditSink
    from seldon_core_tpu.tools.audit_tail import iter_records

    sink = JsonlAuditSink(str(tmp_path))
    req = SeldonMessage.from_array(np.ones((1, 2), np.float32))
    resp = SeldonMessage.from_array(np.zeros((1, 3), np.float32))
    sink.send("client-a", req, resp)
    sink.send("client-b", req, resp)
    sink.send("client-a", req, resp)

    records = list(iter_records(f"file://{tmp_path}", None, follow=False))
    assert len(records) == 3
    assert sorted(r["client"] for r in records) == ["client-a", "client-a", "client-b"]
    for r in records:
        assert r["request"]["data"]["tensor"]["values"] == [1.0, 1.0]
        assert r["response"]["data"]["tensor"]["shape"] == [1, 3]

    only_a = list(iter_records(f"file://{tmp_path}", "client-a", follow=False))
    assert len(only_a) == 2

    # torn (no newline) AND corrupt (newline-terminated invalid JSON)
    # lines both leave the stream alive
    with (tmp_path / "client-a.jsonl").open("a") as f:
        f.write('{"corrupt": \n')  # invalid JSON, complete line
        f.write("{torn")  # partial write, no newline
    assert len(list(iter_records(f"file://{tmp_path}", "client-a", False))) == 2

    # truncation/rotation recovery inside one --follow stream: the offset
    # resets when the file shrinks instead of seeking past EOF forever
    gen = iter_records(f"file://{tmp_path}", "client-b", follow=True)
    first = next(gen)
    assert first["client"] == "client-b"
    (tmp_path / "client-b.jsonl").write_text("")  # logrotate-style truncation
    # smaller record than the consumed offset so the shrink is observable
    # (size-based reset; an equal-size rewrite is indistinguishable without
    # inode tracking)
    tiny = SeldonMessage.from_array(np.ones((1, 1), np.float32))
    sink.send("client-b", tiny, tiny)
    again = next(gen)  # would hang/starve without the getsize reset
    assert again["client"] == "client-b"
    assert again["request"]["data"]["tensor"]["shape"] == [1, 1]


def test_install_bundle_monitoring_renders_alertmanager_and_rules():
    """--with-monitoring (VERDICT r2 missing #4): prometheus + alertmanager
    + grafana render with the shipped serving rules wired into prometheus
    and a valid alertmanager route for them to land in."""
    from seldon_core_tpu.tools.install import build_bundle, to_yaml

    bundle = build_bundle(with_monitoring=True)
    kinds = {(m["kind"], m["metadata"]["name"]) for m in bundle}
    assert ("Deployment", "prometheus") in kinds
    assert ("Deployment", "alertmanager") in kinds
    assert ("Deployment", "grafana") in kinds
    assert ("ConfigMap", "alertmanager-config") in kinds

    rules_cm = next(
        m for m in bundle if m["metadata"]["name"] == "prometheus-rules"
    )
    assert "PredictionLatencyP99High" in rules_cm["data"]["seldon-rules.yaml"]
    prom_cm = next(
        m for m in bundle if m["metadata"]["name"] == "prometheus-config"
    )
    assert "alertmanager" in prom_cm["data"]["prometheus.yml"]
    am_cm = next(
        m for m in bundle if m["metadata"]["name"] == "alertmanager-config"
    )
    import yaml as _yaml

    cfg = _yaml.safe_load(am_cm["data"]["config.yml"])
    assert cfg["route"]["receiver"] == "default"
    assert to_yaml(bundle)  # whole bundle serializes


def test_release_set_version_rewrites_every_source(tmp_path, monkeypatch):
    """release.py (C29): one command rewrites the version everywhere it
    lives — version.py, pyproject, the values-layer image tag."""
    import shutil

    from seldon_core_tpu.tools import release

    (tmp_path / "seldon_core_tpu").mkdir()
    (tmp_path / "deploy").mkdir()
    root = release.REPO_ROOT  # the real checkout, wherever it lives
    shutil.copy(f"{root}/seldon_core_tpu/version.py", tmp_path / "seldon_core_tpu" / "version.py")
    shutil.copy(f"{root}/pyproject.toml", tmp_path / "pyproject.toml")
    shutil.copy(f"{root}/deploy/values.yaml", tmp_path / "deploy" / "values.yaml")
    monkeypatch.setattr(release, "REPO_ROOT", str(tmp_path))

    changed = release.set_version("9.9.9")
    assert set(changed) == {
        "seldon_core_tpu/version.py",
        "pyproject.toml",
        "deploy/values.yaml",
    }
    assert '__version__ = "9.9.9"' in (tmp_path / "seldon_core_tpu" / "version.py").read_text()
    assert 'version = "9.9.9"' in (tmp_path / "pyproject.toml").read_text()
    assert "seldon-core-tpu/platform:9.9.9" in (tmp_path / "deploy" / "values.yaml").read_text()


def test_install_monitoring_prometheus_rbac_and_grafana_provisioning():
    """Code-review r3: prometheus pod-SD needs its own SA + pods RBAC, and
    grafana needs a provisioning provider + datasource or it boots empty."""
    from seldon_core_tpu.tools.install import build_bundle

    bundle = build_bundle(with_monitoring=True)
    by_kind_name = {(m["kind"], m["metadata"]["name"]): m for m in bundle}
    assert ("ServiceAccount", "prometheus") in by_kind_name
    role = by_kind_name[("Role", "prometheus")]
    assert {"pods"} == set(role["rules"][0]["resources"])
    prom = by_kind_name[("Deployment", "prometheus")]
    assert prom["spec"]["template"]["spec"]["serviceAccountName"] == "prometheus"

    prov = by_kind_name[("ConfigMap", "grafana-provisioning")]
    assert "path: /var/lib/grafana/dashboards" in prov["data"]["dashboards.yaml"]
    assert "type: prometheus" in prov["data"]["datasources.yaml"]
    grafana = by_kind_name[("Deployment", "grafana")]
    mounts = grafana["spec"]["template"]["spec"]["containers"][0]["volumeMounts"]
    assert any("/etc/grafana/provisioning/datasources" in m["mountPath"] for m in mounts)

    # empty alertmanager_config override must still render the skeleton,
    # never an empty config.yml (alertmanager would crash-loop)
    from seldon_core_tpu.tools.install import build_bundle_from_values

    bundle2 = build_bundle_from_values(
        {"monitoring": {"enabled": True, "alertmanager_config": ""}}
    )
    am = next(m for m in bundle2 if m["metadata"]["name"] == "alertmanager-config")
    assert "receivers" in am["data"]["config.yml"]


def test_install_storage_pvc_and_hostpath_pv():
    """Reference persistence/ (host-volume / glusterfs create scripts)
    modernized as a values-gated PVC + optional static hostPath PV, mounted
    into the platform pod at mount_path."""
    from seldon_core_tpu.tools.install import build_bundle_from_values

    # dynamic provisioning (the glusterfs-create equivalent): PVC only
    bundle = build_bundle_from_values(
        {"storage": {"enabled": True, "size": "25Gi"}}
    )
    by_kind = {(m["kind"], m["metadata"]["name"]): m for m in bundle}
    pvc = by_kind[("PersistentVolumeClaim", "seldon-models")]
    assert pvc["spec"]["resources"]["requests"]["storage"] == "25Gi"
    assert ("PersistentVolume", "seldon-models-seldon") not in by_kind
    platform = by_kind[("Deployment", "seldon-core-tpu-platform")]
    spec = platform["spec"]["template"]["spec"]
    assert spec["volumes"][0]["persistentVolumeClaim"]["claimName"] == "seldon-models"
    mounts = spec["containers"][0]["volumeMounts"]
    assert mounts[0]["mountPath"] == "/var/seldon/models"

    # host-volume case: static PV bound to the claim, default SC disabled
    bundle = build_bundle_from_values(
        {"storage": {"enabled": True, "host_path": "/mnt/models"}}
    )
    by_kind = {(m["kind"], m["metadata"]["name"]): m for m in bundle}
    pv = by_kind[("PersistentVolume", "seldon-models-seldon")]
    assert pv["spec"]["hostPath"]["path"] == "/mnt/models"
    assert pv["spec"]["claimRef"]["name"] == "seldon-models"
    pvc = by_kind[("PersistentVolumeClaim", "seldon-models")]
    assert pvc["spec"]["storageClassName"] == ""

    # storage off (default): no volume objects, no mounts
    bundle = build_bundle_from_values({})
    kinds = {m["kind"] for m in bundle}
    assert "PersistentVolumeClaim" not in kinds
    platform = next(
        m for m in bundle if m["metadata"]["name"] == "seldon-core-tpu-platform"
    )
    assert "volumes" not in platform["spec"]["template"]["spec"]


def test_install_autoscaling_hpa():
    """Values-gated HPA targeting the platform Deployment (the reference's
    hand-set replicas, automated). HPA-managed Deployments must omit
    spec.replicas, carry a cpu request (utilization = usage/request), and
    multi-replica requires the shared redis token store."""
    import pytest

    from seldon_core_tpu.tools.install import build_bundle_from_values

    bundle = build_bundle_from_values(
        {
            "autoscaling": {"enabled": True, "min_replicas": 2, "max_replicas": 6},
            "redis": {"enabled": True},
        }
    )
    hpa = next(m for m in bundle if m["kind"] == "HorizontalPodAutoscaler")
    assert hpa["spec"]["scaleTargetRef"]["name"] == "seldon-core-tpu-platform"
    assert hpa["spec"]["minReplicas"] == 2
    assert hpa["spec"]["maxReplicas"] == 6
    assert (
        hpa["spec"]["metrics"][0]["resource"]["target"]["averageUtilization"] == 80
    )
    platform = next(
        m for m in bundle if m["metadata"]["name"] == "seldon-core-tpu-platform"
    )
    # replicas omitted (a re-apply must not snap the HPA's count back to 1)
    assert "replicas" not in platform["spec"]
    container = platform["spec"]["template"]["spec"]["containers"][0]
    assert container["resources"]["requests"]["cpu"] == "1"

    # in-memory tokens across replicas would be rejected: enforced
    with pytest.raises(ValueError, match="redis.enabled"):
        build_bundle_from_values({"autoscaling": {"enabled": True}})

    # any multi-replica envelope (max_replicas > 1) renders a PDB so
    # voluntary evictions can't take every serving pod at once
    assert any(m["kind"] == "PodDisruptionBudget" for m in bundle)
    # the gate boundary: max_replicas == 1 means no PDB (minAvailable 1
    # would block drains of the only pod)
    single = build_bundle_from_values(
        {"autoscaling": {"enabled": True, "max_replicas": 1}}
    )
    assert not any(m["kind"] == "PodDisruptionBudget" for m in single)

    # off by default, and the non-autoscaled Deployment keeps replicas: 1
    bundle = build_bundle_from_values({})
    assert not any(m["kind"] == "HorizontalPodAutoscaler" for m in bundle)
    assert not any(m["kind"] == "PodDisruptionBudget" for m in bundle)
    platform = next(
        m for m in bundle if m["metadata"]["name"] == "seldon-core-tpu-platform"
    )
    assert platform["spec"]["replicas"] == 1

    # the shipped production values example renders everything cleanly
    import yaml as _yaml

    with open(
        os.path.join(os.path.dirname(__file__), "..", "deploy",
                     "values-production.yaml")
    ) as f:
        prod = _yaml.safe_load(f)
    bundle = build_bundle_from_values(prod)
    kinds = {m["kind"] for m in bundle}
    for expected in (
        "HorizontalPodAutoscaler", "PodDisruptionBudget",
        "PersistentVolumeClaim", "CustomResourceDefinition",
    ):
        assert expected in kinds, expected


def test_soak_harness_reports_stability_signals():
    """tools/soak.py in a SUBPROCESS (its boot applies the serving GC
    policy — gc.freeze inside the shared pytest process would pin every
    prior test's leftovers permanently): the leak/stall detector runs the
    real gateway stack and reports RSS slope + loop lag + throughput."""
    import json as json_mod
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["JAX_PLATFORMS"] = "cpu"
    out_raw = subprocess.run(
        [sys.executable, "-m", "seldon_core_tpu.tools.soak", "--duration", "2", "--users", "4"],
        capture_output=True,
        text=True,
        timeout=180,
        env=env,
    )
    assert out_raw.returncode == 0, out_raw.stderr[-1500:]
    out = json_mod.loads(out_raw.stdout.strip().splitlines()[-1])
    assert out["errors"] == 0
    assert out["preds_per_sec"] > 0
    assert out["rss_end_mb"] > 0 and out["rss_start_mb"] > 0
    assert out["loop_lag_p99_ms"] is not None
    assert "rss_slope_net_mb_per_min" in out


@pytest.mark.chaos
def test_soak_trace_summary_attributes_slowest_traces():
    """tools/soak.py --trace-summary under a seeded fault schedule: the
    report ships per-trace attribution (slowest retained traces, top spans
    by self-time) so chaos runs come with built-in "where did the tail go".
    Subprocess for the same GC-policy reason as the soak smoke test."""
    import subprocess

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["JAX_PLATFORMS"] = "cpu"
    out_raw = subprocess.run(
        [
            sys.executable, "-m", "seldon_core_tpu.tools.soak",
            "--duration", "2", "--users", "4",
            "--trace-summary", "3",
            "--faults", "--fault-error-rate", "0.3", "--fault-seed", "1337",
        ],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    assert out_raw.returncode == 0, out_raw.stderr[-1500:]
    out = json.loads(out_raw.stdout.strip().splitlines()[-1])
    assert out["faulted"]["faults_injected"] > 0
    for leg in ("baseline", "faulted"):
        summary = out[leg]["trace_summary"]
        assert summary, f"{leg} leg retained no traces"
        assert len(summary) <= 3
        for entry in summary:
            assert entry["trace_id"] and entry["total_ms"] > 0
            assert 1 <= len(entry["top_spans"]) <= 3
            for span in entry["top_spans"]:
                assert span["name"] and span["self_ms"] >= 0
        # slowest-first ordering
        totals = [e["total_ms"] for e in summary]
        assert totals == sorted(totals, reverse=True)


def test_loadtest_worker_never_touches_jax():
    """One process per chip: loadtest workers are children of a process
    that may hold the chip, so the module they run must not import jax (and
    the launcher pins JAX_PLATFORMS=cpu in their environment besides)."""
    import subprocess

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [
            sys.executable, "-c",
            "import sys, seldon_core_tpu.tools.loadtest; "
            "print(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'jaxlib')))",
        ],
        capture_output=True, text=True, timeout=60, env=env,
    )
    assert out.returncode == 0, out.stderr[-1000:]
    assert out.stdout.strip() == "[]"
    import inspect

    from seldon_core_tpu.tools import loadtest

    assert 'env["JAX_PLATFORMS"] = "cpu"' in inspect.getsource(
        loadtest.run_load_multiprocess
    )
