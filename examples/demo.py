"""Runnable walkthrough — the notebooks-equivalent (reference C30:
kubectl_demo_minikube.ipynb / advanced_graphs.ipynb as a script).

Boots the whole platform in-process, then walks every major capability:
apply, OAuth, predict, A/B routing, reward feedback training a bandit,
request tracing, HBM accounting, metrics.

    python examples/demo.py
"""

import asyncio
import json
import os
import sys

# self-contained: put the repo root on sys.path instead of asking for
# PYTHONPATH=.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


async def main() -> None:
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.platform import Platform

    print("== boot platform (control plane + gateway + engine, one process)")
    platform = Platform()
    client = TestClient(TestServer(platform.build_app()))
    await client.start_server()

    print("== kubectl-apply an epsilon-greedy bandit over two iris models")
    cr = {
        "apiVersion": "machinelearning.seldon.io/v1alpha1",
        "kind": "SeldonDeployment",
        "metadata": {"name": "iris-bandit"},
        "spec": {
            "name": "iris-bandit",
            "oauth_key": "demo-key",
            "oauth_secret": "demo-secret",
            "predictors": [
                {
                    "name": "main",
                    "graph": {
                        "name": "eg",
                        "type": "ROUTER",
                        "implementation": "EPSILON_GREEDY",
                        "parameters": [
                            {"name": "epsilon", "value": "0.1", "type": "FLOAT"}
                        ],
                        "children": [
                            {
                                "name": "champion",
                                "type": "MODEL",
                                "implementation": "JAX_MODEL",
                                "parameters": [
                                    {"name": "model", "value": "iris_logistic", "type": "STRING"}
                                ],
                            },
                            {
                                "name": "challenger",
                                "type": "MODEL",
                                "implementation": "JAX_MODEL",
                                "parameters": [
                                    {"name": "model", "value": "iris_mlp", "type": "STRING"}
                                ],
                            },
                        ],
                    },
                    "tpu": {"batch_across_requests": False},
                }
            ],
        },
    }
    resp = await client.post(
        "/apis/machinelearning.seldon.io/v1alpha1/seldondeployments", json=cr
    )
    applied = await resp.json()
    print("   apply:", applied)
    if applied.get("action") != "created":
        await client.close()
        raise SystemExit(f"reconcile failed: {applied.get('message')}")

    print("== OAuth client_credentials -> bearer token")
    try:
        await _walkthrough(client, platform)
    finally:
        await client.close()
    print("== demo complete")


async def _walkthrough(client, platform) -> None:
    resp = await client.post(
        "/oauth/token", data={"client_id": "demo-key", "client_secret": "demo-secret"}
    )
    token = (await resp.json())["access_token"]
    auth = {"Authorization": f"Bearer {token}"}

    print("== predict + reward feedback loop (reward the challenger, arm 1)")
    for i in range(25):
        resp = await client.post(
            "/api/v0.1/predictions",
            json={"data": {"ndarray": [[5.1, 3.5, 1.4, 0.2]]}},
            headers=auth,
        )
        body = await resp.json()
        branch = body["meta"]["routing"]["eg"]
        await client.post(
            "/api/v0.1/feedback",
            json={
                "response": {"meta": body["meta"]},
                "reward": 1.0 if branch == 1 else 0.0,
            },
            headers=auth,
        )
    last10 = []
    for _ in range(10):
        resp = await client.post(
            "/api/v0.1/predictions",
            json={"data": {"ndarray": [[5.1, 3.5, 1.4, 0.2]]}},
            headers=auth,
        )
        last10.append((await resp.json())["meta"]["routing"]["eg"])
    print(f"   routes after training (1=challenger): {last10}")

    print("== request tracing (tags.trace)")
    resp = await client.post(
        "/api/v0.1/predictions",
        json={"meta": {"tags": {"trace": True}}, "data": {"ndarray": [[1, 2, 3, 4]]}},
        headers=auth,
    )
    body = await resp.json()
    print("   requestPath:", body["meta"]["requestPath"])
    for span in body["meta"]["tags"]["trace"]:
        print(f"   span {span['unit']}.{span['method']}: {span['ms']} ms")

    print("== HBM accounting")
    print("  ", platform.manager.hbm_usage())

    print("== status + teardown")
    resp = await client.get(
        "/apis/machinelearning.seldon.io/v1alpha1/seldondeployments"
    )
    print("   list:", json.dumps(await resp.json())[:140])
    await client.delete(
        "/apis/machinelearning.seldon.io/v1alpha1/seldondeployments/iris-bandit"
    )


if __name__ == "__main__":
    asyncio.run(main())
