"""The measured serving stack, built ONE way.

The soak harness and any other tool that measures the graph tier must
boot the exact stack the product boots (warmed PredictorServer behind
the OAuth gateway + in-process backend, serving GC policy applied) — a
second hand-rolled copy is how a tool silently stops measuring what the
platform runs. This is that single definition.
"""

from __future__ import annotations


def build_gateway_stack(
    predictor,
    *,
    deployment_name: str = "bench",
    oauth_key: str = "bench-key",
    oauth_secret: str = "bench-secret",
):
    """Returns (server, gw, oauth, token): warmed PredictorServer wired
    behind the OAuth gateway with the serving GC policy applied, exactly
    as PredictorServer.start / platform.serve do at boot."""
    from seldon_core_tpu.gateway.app import Gateway, InProcessBackend
    from seldon_core_tpu.gateway.oauth import OAuthProvider
    from seldon_core_tpu.gateway.store import DeploymentStore
    from seldon_core_tpu.graph.spec import DeploymentSpec
    from seldon_core_tpu.serving.gc_policy import apply_serving_gc_policy
    from seldon_core_tpu.serving.server import PredictorServer

    server = PredictorServer(predictor, deployment_name=deployment_name)
    server.warmup()
    apply_serving_gc_policy()
    oauth = OAuthProvider()
    store = DeploymentStore(oauth=oauth)
    backend = InProcessBackend()
    gw = Gateway(store=store, oauth=oauth, backend=backend)
    store.deployment_added(
        DeploymentSpec(
            name=deployment_name,
            oauth_key=oauth_key,
            oauth_secret=oauth_secret,
            predictors=[predictor],
        )
    )
    backend.register(deployment_name, server.service)
    token = oauth.issue_token(oauth_key, oauth_secret)["access_token"]
    return server, gw, oauth, token
