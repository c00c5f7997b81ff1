"""The system under test, built through its normal path, and its counters.

Two tiers, as chip_smoke.py (PR 23) boots them:

- ``generative``: the CR -> ``serving/server._prepare`` -> ``PredictorServer``
  with fast ingress; requests stream over
  ``POST /api/v0.1/predictions/stream`` into the decode scheduler;
- ``graph``: the CR -> ``Platform().manager.apply`` -> OAuth gateway -> fast
  ingress -> micro-batcher -> executor walk.

From the program this file takes objects and reads counters; it changes
nothing in them, except that a traced run hangs ``jax.profiler``
annotations round the scheduler's host phases and the batcher's walk, from
here, so the trace can name what the host did while the device idled.
"""

from __future__ import annotations

import asyncio
import contextlib
import copy
import socket

from harness.trace import HOST_PREFIX

# tokens generated by each request of the reference sample
REFERENCE_NEW_TOKENS = 24


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _models(graph: dict):
    if graph.get("implementation") == "JAX_MODEL":
        yield graph
    for c in graph.get("children", []):
        yield from _models(c)


async def _post_json(session, url: str, body: dict, headers: dict | None = None) -> dict:
    async with session.post(url, json=body, headers=headers or {}) as resp:
        out = await resp.json()
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {out}")
        return out


def _params(unit: dict) -> dict:
    return {p["name"]: p["value"] for p in unit.get("parameters", [])}


def _set_param(unit: dict, name: str, value, typ: str) -> None:
    unit["parameters"] = [p for p in unit.get("parameters", []) if p["name"] != name] + [
        {"name": name, "value": str(value), "type": typ}
    ]


def seeded_cr(config: dict, seed: int) -> dict:
    """The configuration's CR with every model's weights drawn from the
    run's seed: model i of the graph gets ``seed + i``."""
    import urllib.parse

    cr = copy.deepcopy(config["deployment"])
    for i, unit in enumerate(_models(cr["spec"]["predictors"][0]["graph"])):
        uri = _params(unit).get("model_uri")
        if uri is None:
            _set_param(unit, "seed", seed + i, "INT")
            continue
        u = urllib.parse.urlparse(uri)
        q = [(k, v) for k, v in urllib.parse.parse_qsl(u.query) if k != "seed"]
        q.append(("seed", str(seed + i)))
        _set_param(unit, "model_uri", u._replace(query=urllib.parse.urlencode(q)).geturl(), "STRING")
    return cr


class Generative:
    tier = "generative"
    path = "/api/v0.1/predictions/stream"
    auth = None
    families = {"step": "fused_step", "chunk": "fused_chunk"}

    def __init__(self, config: dict, seed: int):
        from seldon_core_tpu.graph.spec import SeldonDeployment
        from seldon_core_tpu.serving.server import PredictorServer, _prepare

        self.cr = seeded_cr(config, seed)
        dep = SeldonDeployment.from_dict(self.cr)
        pred, name = _prepare(dep.spec.predictors[0], dep.spec.name)
        self.server = PredictorServer(pred, deployment_name=name)
        self.sched = self.server.decode_scheduler
        tpu = self.cr["spec"]["predictors"][0]["tpu"]
        unit = _params(self.cr["spec"]["predictors"][0]["graph"])
        self.seq, self.vocab = int(unit["seq"]), int(unit["vocab"])
        self.geometry = {k: int(unit[k]) for k in ("hidden", "layers", "ffn", "vocab")}
        self.n_head = int(config["reference"]["n_head"])
        self.config = config
        s = self.sched
        # the serving builder warn-and-disables what it cannot serve: a cell
        # must measure the mechanisms its configuration names
        ok = (
            s is not None
            and not hasattr(s, "live_replicas")
            and s.n_slots == tpu["decode_slots"]
            and s.pool.page_size == tpu["decode_kv_page_size"]
            and s.pool.n_pages == tpu["decode_kv_pages"]
            and s.prefix_slots == tpu["decode_prefix_slots"]
            and s.prefill_chunk == min(tpu["decode_prefill_chunk"], self.seq)
        )
        if not ok:
            raise RuntimeError("the decode scheduler is not what the configuration names")

    @property
    def params(self):
        return self.sched.params

    def warm(self) -> None:
        # the scheduler's programs only: the fused whole-batch generate of the
        # same model (PredictorServer.warmup also compiles it) serves no
        # request of a cell
        self.sched.warmup()

    async def start(self) -> str:
        port = free_port()
        await self.server.start(host="127.0.0.1", port=port, grpc_port=None, fast_ingress=True)
        self.url = f"http://127.0.0.1:{port}"
        return self.url

    async def stop(self) -> None:
        await self.server.stop()

    def counters(self) -> dict:
        s, fl = self.sched, self.sched.flight
        snap = s.pool.alloc.snapshot()
        return {
            "rounds": fl.rounds,
            "occupancy_sum": s.stat_occupancy_sum,
            "steps": s.stat_steps,
            "tokens": s.stat_tokens,
            "admitted": s.stat_admitted,
            "retired": s.stat_retired,
            "chunk_dispatches": s.stat_chunk_dispatches,
            "prefix_hits": s.stat_prefix_hits,
            "prefix_misses": s.stat_prefix_misses,
            "prefix_tokens_saved": s.stat_prefix_tokens_saved,
            "admit_blocked_rounds": s.stat_admit_blocked_rounds,
            "gap_ns": fl.gap_ns_total,
            "pin_reclaims": snap["pin_reclaims"],
            "recompiles": s.recompiles_since_warmup(),
            "pages": s.pool.n_pages,
        }


    async def sample(self, traffic: dict, seed: int) -> list:
        """Before the window: for each prefix family of the mix a cold request
        that hints its shared prefix into the cache (this also primes the
        mix's system prompts), then a second request of the family with
        another tail, which must ride the cached prefix, then one exact
        repeat. Returns token id lists, prompt then generated."""
        import aiohttp

        from harness.traffic import token_ids

        fams = int(traffic.get("prefixes", 1))
        plen = int(traffic.get("shared_prefix_len", 0))
        tags = {"max_new_tokens": REFERENCE_NEW_TOKENS, "cache_prefix": int(traffic.get("cache_prefix", 0))}

        def body(k: int, uid: int) -> dict:
            req = {"prompt_len": self.seq, "prefix_len": plen, "prefix": k, "uid": uid}
            return {"meta": {"tags": tags}, "data": {"ndarray": [token_ids(seed, self.vocab, req)]}}

        served: list = []
        hits0 = self.sched.stat_prefix_hits
        async with aiohttp.ClientSession() as session:
            for wave in ([(k, 900000 + k) for k in range(fams)],
                         [(k, 910000 + k) for k in range(fams)],
                         [(0, 900000)]):
                outs = await asyncio.gather(
                    *(_post_json(session, self.url + "/api/v0.1/predictions", body(k, uid)) for k, uid in wave)
                )
                served += [[int(t) for t in o["data"]["ndarray"][0]] for o in outs]
        hits = self.sched.stat_prefix_hits - hits0
        if hits < (fams + 1 if plen else 1):
            raise RuntimeError(f"warm requests did not ride the prefix cache: {hits} hits")
        self._sample_rows = len(served)
        return served

    def kept(self, requests: list) -> list:
        """Answers of the measured traffic to judge after the window."""
        done = [r["ids"] for r in requests if r.get("gen_len", 0) >= REFERENCE_NEW_TOKENS]
        return done[: self._sample_rows]

    def judge(self, ref, served: list) -> dict:
        """Teacher-forced reference logits along the served tokens, at
        "highest" and at the chip's default precision (the noise). The batch
        is padded with repeats to the sample's rows: one compiled shape."""
        import numpy as np

        import inspect

        from harness.correct import judge_generated

        # a reference that reads its sizes from the configuration's file takes them
        # from the configuration as it is run (the rehearsal's tiny one)
        sizes = {"config": self.config} if "config" in inspect.signature(ref.logits).parameters else {}
        width, first = self.seq + REFERENCE_NEW_TOKENS, self.seq - 1
        served = [s[:width] for s in served]
        ids = np.asarray([served[r % len(served)] for r in range(self._sample_rows)], np.int64)
        exact, noisy = (
            np.asarray(ref.logits(self.params, ids, first, n_head=self.n_head, precision=p, **sizes), np.float64)
            for p in ("highest", "default")
        )
        return judge_generated(served, exact[: len(served)], noisy[: len(served)], first)

    def frames(self, t0: float, t1: float) -> list:
        """The flight recorder's frames committed inside [t0, t1) (its clock,
        perf_counter_ns, is CLOCK_MONOTONIC like the window's)."""
        return [f for f in self.sched.flight.snapshot() if t0 <= f.t_ns / 1e9 < t1]

    def annotate(self) -> None:
        import jax
        from seldon_core_tpu.telemetry.flight import FAMILIES, PHASES

        inner = self.sched._phase

        def phase(p: int):
            stack = contextlib.ExitStack()
            stack.enter_context(jax.profiler.TraceAnnotation(f"{HOST_PREFIX}host_in_{PHASES[p]}"))
            stack.enter_context(inner(p))
            return stack

        self.sched._phase = phase
        timed = self.sched._timed_call

        async def timed_call(family: int, fn):
            # enqueue, transfer and readback of one dispatch: what the host is
            # in when the device idles outside every phase
            with jax.profiler.TraceAnnotation(f"{HOST_PREFIX}dispatch_{FAMILIES[family]}"):
                return await timed(family, fn)

        self.sched._timed_call = timed_call


class Graph:
    tier = "graph"
    path = "/api/v0.1/predictions"
    families = {"forward": "serving_fn"}

    def __init__(self, config: dict, seed: int):
        from seldon_core_tpu.platform import Platform

        self.cr = seeded_cr(config, seed)
        spec = self.cr["spec"]
        self.name = spec["name"]
        self.auth = {"key": spec["oauth_key"], "secret": spec["oauth_secret"]}
        self.platform = Platform()
        res = self.platform.manager.apply(self.cr)
        if res.action != "created":
            raise RuntimeError(f"apply {self.name}: {res.message}")
        dep = self.platform.manager.get(self.name)
        self.service = next(iter(dep.services.values()))
        self.batcher = self.service.batcher
        self.runtimes = {
            u.spec.name: u.runtime
            for u in self.service.executor.units()
            if getattr(u, "runtime", None) is not None
        }
        self.model_uris = {
            unit["name"]: _params(unit)["model_uri"] for unit in _models(spec["predictors"][0]["graph"])
        }
        self._ref_params: dict = {}
        self.ref_args = {"n_head": int(config["reference"]["n_head"]), "means": float(config["reference"]["means"])}
        self.seq = int(config["reference"]["seq"])
        self.vocab = int(config["vocab_size"])
        self._handles = None

    def warm(self) -> None:
        self.platform.manager.get(self.name).warmup()

    async def start(self) -> str:
        port = free_port()
        self._handles = await self.platform.serve(
            host="127.0.0.1", port=port, grpc_port=None, fast_ingress=True,
            admin_port=free_port(),
        )
        self.url = f"http://127.0.0.1:{port}"
        return self.url

    async def stop(self) -> None:
        runner, _grpc, watch = self._handles
        self.platform._lag_probe.cancel()
        if watch is not None:
            watch.cancel()
        self.platform._fast_server.close()
        await self.platform._fast_server.wait_closed()
        await runner.cleanup()
        self.platform.manager.delete(self.name)

    def counters(self) -> dict:
        b = self.batcher
        return {
            "batches": b.stat_batches,
            "rows": b.stat_rows,
            "items": b.stat_items,
            "queue_wait_s": b.stat_queue_wait_s,
            "passthrough": b.stat_passthrough,
            "programs": sum(rt._jit._cache_size() for rt in self.runtimes.values()),
        }


    async def sample(self, traffic: dict, seed: int) -> list:
        """Before the window: a few requests of 1-3 rows through the gateway."""
        import aiohttp

        from harness.loadgen import bearer
        from harness.traffic import token_ids

        out = []
        async with aiohttp.ClientSession() as session:
            auth = await bearer(session, self.url, self.auth)
            for n, rows in enumerate((1, 3, 2, 1, 2, 3)):
                ids = [token_ids(seed, self.vocab, {"prompt_len": self.seq, "uid": 900000 + 10 * n + r})
                       for r in range(rows)]
                o = await _post_json(session, self.url + self.path, {"data": {"ndarray": ids}}, auth)
                out.append({"rows": ids, "out": o["data"]["ndarray"], "routing": o["meta"]["routing"]})
        return out

    def kept(self, requests: list) -> list:
        return [r for r in requests if r.get("out")][:256]

    def judge(self, ref, answered: list) -> dict:
        """Each answer against the reference forward of the branch the router
        named (``meta.routing``), with that branch's weights from the seed."""
        import jax
        import numpy as np

        from harness.correct import judge_probabilities
        from seldon_core_tpu.models.zoo import _parse_zoo_uri, get_model

        router = next(iter(answered[0]["routing"]))
        branches = list(self.model_uris)  # graph order: branch i is model i
        served, want = [], []
        for a in answered:
            name = branches[int(a["routing"][router])]
            if name not in self._ref_params:
                model, kw = _parse_zoo_uri(self.model_uris[name])
                self._ref_params[name] = jax.device_put(get_model(model, **{**kw, "seq": self.seq}).params)
            p = ref.probabilities(self._ref_params[name], np.asarray(a["rows"]), **self.ref_args)
            served.append(np.asarray(a["out"], np.float64))
            want.append(np.asarray(p, np.float64))
        verdict = judge_probabilities(np.concatenate(served), np.concatenate(want))
        verdict["branches"] = sorted(self._ref_params)
        return verdict

    def frames(self, t0: float, t1: float) -> list:
        return []

    def annotate(self) -> None:
        import jax

        inner = self.batcher._run_batch

        async def run_batch(items):
            with jax.profiler.TraceAnnotation(f"{HOST_PREFIX}batch_walk", rows=len(items)):
                return await inner(items)

        self.batcher._run_batch = run_batch


TIERS = {"generative": Generative, "graph": Graph}
