"""Advanced inference graphs — the advanced_graphs.ipynb equivalent.

Parity (C30): the reference's notebooks/advanced_graphs.ipynb walks an
AB-test graph and a combiner graph on a live cluster. This script drives
the richer TPU-native set end-to-end on a live in-process platform:

1. transformer -> router -> models (full pre/post pipeline with split-batch
   routing under the micro-batcher);
2. 3-model AverageCombiner ensemble — fused by engine/fused.py into ONE
   XLA program (the reference runs 3 containers + 3 RPCs + a Java mean);
3. outlier-detector tier in front of a model, tagging every response;
4. the same predictions through the binary npy wire path.

    python examples/advanced_graphs.py
"""

import asyncio
import json
import os
import sys

# self-contained: put the repo root on sys.path instead of asking for
# PYTHONPATH=.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _cr(name: str, key: str, graph: dict, tpu: dict | None = None) -> dict:
    pred = {"name": "main", "graph": graph}
    if tpu:
        pred["tpu"] = tpu
    return {
        "apiVersion": "machinelearning.seldon.io/v1alpha1",
        "kind": "SeldonDeployment",
        "metadata": {"name": name},
        "spec": {
            "name": name,
            "oauth_key": key,
            "oauth_secret": f"{key}-secret",
            "predictors": [pred],
        },
    }


async def main() -> None:
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.platform import Platform

    platform = Platform()
    client = TestClient(TestServer(platform.build_app()))
    await client.start_server()

    async def token(key: str) -> str:
        resp = await client.post(
            "/oauth/token",
            data={
                "grant_type": "client_credentials",
                "client_id": key,
                "client_secret": f"{key}-secret",
            },
        )
        return (await resp.json())["access_token"]

    async def predict(key: str, payload: dict) -> dict:
        resp = await client.post(
            "/api/v0.1/predictions",
            json=payload,
            headers={"Authorization": f"Bearer {await token(key)}"},
        )
        assert resp.status == 200, await resp.text()
        return await resp.json()

    async def apply(cr: dict) -> None:
        resp = await client.post(
            "/apis/machinelearning.seldon.io/v1alpha1/seldondeployments", json=cr
        )
        applied = await resp.json()
        assert applied.get("action") == "created", applied

    print("== 1. transformer -> A/B router -> two iris models")
    await apply(
        _cr(
            "pipeline",
            "pipeline-key",
            {
                "name": "center",
                "type": "TRANSFORMER",
                "implementation": "MEAN_TRANSFORMER",
                "parameters": [
                    {"name": "means", "value": "5.8,3.0,3.7,1.2", "type": "STRING"}
                ],
                "children": [
                    {
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
                    }
                ],
            },
        )
    )
    routes = set()
    # bounded loop, not a fixed count: 12 coin flips all landing one side
    # is a 1-in-2048 walkthrough failure; 64 makes it ~1e-19
    for _ in range(64):
        body = await predict(
            "pipeline-key", {"data": {"ndarray": [[5.1, 3.5, 1.4, 0.2]]}}
        )
        routes.add(body["meta"]["routing"]["ab"])
        if routes == {0, 1}:
            break
    print(f"   routes exercised: {sorted(routes)} (A/B both taken)")
    assert routes == {0, 1}

    print("== 2. 3-model ensemble, fused to ONE XLA program")
    await apply(
        _cr(
            "ensemble",
            "ensemble-key",
            {
                "name": "avg",
                "type": "COMBINER",
                "implementation": "AVERAGE_COMBINER",
                "children": [
                    {
                        "name": f"m{i}",
                        "type": "MODEL",
                        "implementation": "JAX_MODEL",
                        "parameters": [
                            {"name": "model", "value": "iris_mlp", "type": "STRING"},
                            {"name": "seed", "value": str(i), "type": "INT"},
                        ],
                    }
                    for i in range(3)
                ],
            },
        )
    )
    body = await predict(
        "ensemble-key", {"data": {"ndarray": [[5.1, 3.5, 1.4, 0.2]]}}
    )
    probs = np.asarray(body["data"]["ndarray"])
    print(f"   ensemble proba: {np.round(probs, 3).tolist()}")
    assert np.allclose(probs.sum(axis=1), 1.0, rtol=1e-5)

    print("== 3. outlier detector tier in front of the model")
    await apply(
        _cr(
            "guarded",
            "guarded-key",
            {
                "name": "guard",
                "type": "TRANSFORMER",
                "implementation": "OUTLIER_DETECTOR",
                "parameters": [
                    {"name": "means", "value": "5.8,3.0,3.7,1.2", "type": "STRING"},
                    {"name": "stds", "value": "0.8,0.4,1.8,0.8", "type": "STRING"},
                    {"name": "threshold", "value": "4.0", "type": "FLOAT"},
                ],
                "children": [
                    {
                        "name": "clf",
                        "type": "MODEL",
                        "implementation": "JAX_MODEL",
                        "parameters": [
                            {"name": "model", "value": "iris_mlp", "type": "STRING"}
                        ],
                    }
                ],
            },
        )
    )
    normal = await predict("guarded-key", {"data": {"ndarray": [[5.1, 3.5, 1.4, 0.2]]}})
    weird = await predict("guarded-key", {"data": {"ndarray": [[50.0, 50.0, 50.0, 50.0]]}})
    print(
        f"   normal outlierScore={normal['meta']['tags']['outlierScore']:.2f} "
        f"weird outlierScore={weird['meta']['tags']['outlierScore']:.2f} "
        f"(tagged outlier={weird['meta']['tags'].get('outlier')})"
    )
    assert weird["meta"]["tags"]["outlier"] is True

    print("== 4. the binary npy wire path through the gateway")
    from seldon_core_tpu.core.codec_npy import array_from_npy, npy_from_array

    raw = npy_from_array(np.asarray([[5.1, 3.5, 1.4, 0.2]], np.float32))
    resp = await client.post(
        "/api/v0.1/predictions",
        data=raw,
        headers={
            "Content-Type": "application/x-npy",
            "Authorization": f"Bearer {await token('guarded-key')}",
        },
    )
    assert resp.status == 200 and resp.content_type == "application/x-npy"
    arr = array_from_npy(await resp.read())
    meta = json.loads(resp.headers["Seldon-Meta"])
    print(f"   npy roundtrip: proba={np.round(arr, 3).tolist()} puid={meta['puid'][:8]}…")

    await client.close()
    print("== advanced graphs all green")


if __name__ == "__main__":
    asyncio.run(main())
