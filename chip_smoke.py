"""chip_smoke.py — prove the serving path starts and answers on the chip.

One process, one chip, real HTTP on loopback, through the objects the
product mains build (``python -m seldon_core_tpu.platform`` and
``python -m seldon_core_tpu.serving.server --deployment ... --warmup``):

- classic tier: the full DAG of examples/deployments/full_dag_bert.json
  (transformer -> epsilon-greedy router -> two zoo://bert_base, 12x768,
  seq 128, bf16) behind OAuth gateway -> fast ingress -> micro-batcher ->
  executor: JSON and application/x-npy requests plus one feedback call,
  probabilities compared with a direct float32 jax.numpy forward of the
  same params; then one bert_base deployment at seq 4096 whose served
  program must contain the COMPILED Pallas kernel, compared with its
  attn_kernel=blockwise twin.
- generative tier: tiny_gpt at the widest geometry the repo serves
  (examples/deployments/tiny_gpt_tensor_parallel.json: hidden 256, ffn
  1024, 4 layers) on the paged pool with prefix slots and chunked prefill,
  once with the float32 pool (greedy ids must EQUAL the whole-batch scan
  oracle on the same device) and once with the int8 pool of
  tiny_gpt_paged_kv.json (the repo's tolerance contract): buffered
  requests, one SSE stream, one repeat that must hit the prefix cache,
  zero recompiles after warmup, allocator audit green.

``--chips 4`` runs ONLY the cross-chip paths and what each is compared
with: tensor-parallel decode (tp=4) vs the single-device scheduler,
four decode replicas behind the affinity router vs one, and a
``{"data": 4}`` BERT-base forward vs single-device.

The last stdout line is ``{"ok": true, "device": {...}}`` — printed only
when every phase passed on an accelerator. With no accelerator the script
exits 2 and prints no result. ``--rehearse`` walks the same control flow
at a tiny size on the CPU backend (tests/test_chip_smoke.py runs it under
the ``slow`` marker); it can never print the passing line and always exits 3.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
DEPLOYMENTS = os.path.join(HERE, "examples", "deployments")

# bf16 BERT-base vs the float32 (highest matmul precision) forward of the
# same params: 12 layers of 8-bit-mantissa activations. Stated before the
# first chip run from the dtype, not fitted to it: 2-class probabilities
# agree to a few bf16 ulps of a value in [0, 1] (2^-8 each) — 0.03.
BF16_PROB_TOL = 0.03
# same bf16 model, two attention algorithms (Pallas vs blockwise): both
# accumulate in f32, so they differ by reduction order only
KERNEL_PROB_TOL = 0.02
# the int8 KV pool's own contract (tests/test_kv_pool.py): teacher-forced
# logits drift < 0.25, so a greedy pick sits within 2x that of the oracle's
# best logit, and most greedy tokens survive quantization
INT8_LOGIT_GAP_TOL = 0.5
INT8_MIN_AGREEMENT = 0.5

_events: collections.Counter = collections.Counter()


def log(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _logged(report: dict) -> dict:
    """A phase's report goes out the moment it exists, so a later failure
    does not swallow what earlier phases found (``_``-keys stay in-process)."""
    log({k: v for k, v in report.items() if not k.startswith("_")})
    return report


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _cache_counts() -> dict:
    return {
        "hits": _events["/jax/compilation_cache/cache_hits"],
        "misses": _events["/jax/compilation_cache/cache_misses"],
    }


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _load_cr(name: str) -> dict:
    with open(os.path.join(DEPLOYMENTS, name)) as f:
        return json.load(f)


def _where(tree) -> dict:
    """Which devices hold a pytree's arrays, as JAX reports them."""
    import jax

    devs = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            devs |= set(leaf.devices())
    devs = sorted(devs, key=lambda d: d.id)
    return {
        "platform": devs[0].platform if devs else None,
        "device_kind": devs[0].device_kind if devs else None,
        "device_ids": [d.id for d in devs],
    }


def _graph_models(graph: dict):
    if graph.get("implementation") == "JAX_MODEL":
        yield graph
    for c in graph.get("children", []):
        yield from _graph_models(c)


def _set_param(unit: dict, name: str, value, typ: str = "STRING") -> None:
    params = [p for p in unit.get("parameters", []) if p["name"] != name]
    params.append({"name": name, "value": str(value), "type": typ})
    unit["parameters"] = params


def _params_of(unit: dict) -> dict:
    return {p["name"]: p["value"] for p in unit.get("parameters", [])}


# --------------------------------------------------------------- classic tier


def _bert_cr(name: str, uri: str, tpu: dict) -> dict:
    return {
        "apiVersion": "machinelearning.seldon.io/v1alpha1",
        "kind": "SeldonDeployment",
        "metadata": {"name": name},
        "spec": {
            "name": name,
            "oauth_key": f"{name}-key",
            "oauth_secret": f"{name}-secret",
            "predictors": [
                {
                    "name": "main",
                    "graph": {
                        "name": "bert",
                        "type": "MODEL",
                        "implementation": "JAX_MODEL",
                        "parameters": [
                            {"name": "model_uri", "value": uri, "type": "STRING"}
                        ],
                    },
                    "tpu": tpu,
                }
            ],
        },
    }


class _Platform:
    """The quick-start platform process, in this process: Platform() +
    manager.apply per CR + warmup + serve(fast_ingress=True) on loopback —
    the objects platform._amain builds, torn down the way it tears down."""

    def __init__(self):
        from seldon_core_tpu.platform import Platform

        self.platform = Platform()
        self.port = _free_port()
        self._handles = None

    def apply(self, cr: dict) -> None:
        res = self.platform.manager.apply(cr)
        check(res.action == "created", f"apply {cr['spec']['name']}: {res.message}")

    def runtimes(self, name: str) -> dict:
        out = {}
        dep = self.platform.manager.get(name)
        for svc in dep.services.values():
            for unit in svc.executor.units():
                rt = getattr(unit, "runtime", None)
                if rt is not None:
                    out[unit.spec.name] = rt
        return out

    def warmup(self, name: str) -> None:
        self.platform.manager.get(name).warmup()

    async def serve(self) -> str:
        self._handles = await self.platform.serve(
            host="127.0.0.1",
            port=self.port,
            grpc_port=None,
            fast_ingress=True,
            admin_port=_free_port(),
        )
        return f"http://127.0.0.1:{self.port}"

    async def close(self) -> None:
        runner, _grpc, watch_task = self._handles
        self.platform._lag_probe.cancel()
        if watch_task is not None:
            watch_task.cancel()
        self.platform._fast_server.close()
        await self.platform._fast_server.wait_closed()
        await runner.cleanup()
        for name in list(self.platform.manager.names()):
            self.platform.manager.delete(name)


async def _token(session, base: str, key: str, secret: str) -> dict:
    async with session.post(
        f"{base}/oauth/token",
        data={
            "grant_type": "client_credentials",
            "client_id": key,
            "client_secret": secret,
        },
    ) as resp:
        check(resp.status == 200, f"oauth token: HTTP {resp.status}")
        return {"Authorization": f"Bearer {(await resp.json())['access_token']}"}


async def _predict_json(session, base: str, auth: dict, ids: np.ndarray):
    async with session.post(
        f"{base}/api/v0.1/predictions",
        json={"data": {"ndarray": ids.tolist()}},
        headers=auth,
    ) as resp:
        body = await resp.json()
        check(resp.status == 200, f"JSON predict: HTTP {resp.status} {body}")
    return np.asarray(body["data"]["ndarray"], np.float64), body["meta"]


async def _predict_npy(session, base: str, auth: dict, ids: np.ndarray):
    import io

    buf = io.BytesIO()
    np.save(buf, ids.astype(np.int32))
    async with session.post(
        f"{base}/api/v0.1/predictions",
        data=buf.getvalue(),
        headers={**auth, "Content-Type": "application/x-npy"},
    ) as resp:
        raw = await resp.read()
        check(resp.status == 200, f"npy predict: HTTP {resp.status} {raw[:200]!r}")
        check(
            resp.headers.get("Content-Type", "").startswith("application/x-npy"),
            "npy request did not get an npy response",
        )
        meta = json.loads(resp.headers["Seldon-Meta"])
    return np.load(io.BytesIO(raw)).astype(np.float64), meta


def _well_formed(probs: np.ndarray, rows: int, what: str) -> None:
    check(probs.shape == (rows, 2), f"{what}: shape {probs.shape}, want ({rows}, 2)")
    check(bool(np.all(np.isfinite(probs))), f"{what}: non-finite probabilities")
    check(
        bool(np.allclose(probs.sum(axis=1), 1.0, atol=2e-2)),
        f"{what}: rows do not sum to 1: {probs.sum(axis=1)}",
    )


def _bert_reference(model: str, kwargs: dict):
    """Direct float32 forward of the same zoo params at the highest matmul
    precision — independent of ModelRuntime, the batcher and the executor."""
    import jax

    from seldon_core_tpu.models.bert import apply_bert
    from seldon_core_tpu.models.zoo import get_model

    params = jax.device_put(get_model(model, **kwargs).params)
    fwd = jax.jit(apply_bert)

    def ref(ids: np.ndarray) -> np.ndarray:
        with jax.default_matmul_precision("highest"):
            return np.asarray(fwd(params, np.asarray(ids, np.int32)), np.float64)

    return ref


def _cache_sizes(runtimes: dict) -> int:
    return sum(rt._jit._cache_size() for rt in runtimes.values())


async def phase_classic(geo: dict, n_devices: int) -> dict:
    import aiohttp
    import jax

    cache0 = _cache_counts()
    t_boot = time.perf_counter()
    cr = _load_cr("full_dag_bert.json")
    pred = cr["spec"]["predictors"][0]
    asked = pred["tpu"].pop("mesh")
    log(
        {
            "phase": "classic",
            "note": f"example mesh {asked} needs "
            f"{int(np.prod(list(asked.values())))} devices; serving the same "
            f"graph on the defaulted mesh {{'data': {n_devices}}} of the "
            "devices present",
        }
    )
    for unit in _graph_models(pred["graph"]):
        uri = _params_of(unit)["model_uri"]
        if geo["bert"] != "bert_base":
            _set_param(unit, "model_uri", uri.replace("bert_base", geo["bert"]))
        _set_param(unit, "seq", geo["seq"], "INT")
    pred["tpu"]["max_batch"] = geo["bert_max_batch"]
    dag = cr["spec"]["name"]

    long_uri = f"zoo://{geo['bert']}?seq={geo['long_seq']}&max_len={geo['long_seq']}"
    long_tpu = {"max_batch": 1, "batch_buckets": [1], "dtype": "bfloat16"}
    long_crs = {
        "auto": _bert_cr("bert-long-auto", long_uri, dict(long_tpu)),
        "blockwise": _bert_cr(
            "bert-long-blockwise", long_uri + "&attn_kernel=blockwise", dict(long_tpu)
        ),
    }

    plat = _Platform()
    plat.apply(cr)
    for c in long_crs.values():
        plat.apply(c)
    t_warm = time.perf_counter()
    for name in (dag, "bert-long-auto", "bert-long-blockwise"):
        plat.warmup(name)
    cold_s = time.perf_counter() - t_warm
    runtimes = {
        f"{dep}/{unit}": rt
        for dep in (dag, "bert-long-auto", "bert-long-blockwise")
        for unit, rt in plat.runtimes(dep).items()
    }
    check(len(runtimes) == 4, f"expected 4 BERT runtimes, got {sorted(runtimes)}")
    programs = _cache_sizes(runtimes)
    base = await plat.serve()
    boot_s = time.perf_counter() - t_boot

    rng = np.random.default_rng(geo["seed"])
    vocab = geo["bert_vocab"]
    answered: list = []  # (kind, ids, probs, branch) — judged after the timed window
    t_req = time.perf_counter()
    try:
        async with aiohttp.ClientSession() as session:
            auth = await _token(session, base, "bert-key", "bert-secret")

            async def one(kind: str, rows: int):
                ids = rng.integers(0, vocab, (rows, geo["seq"]))
                fn = _predict_json if kind == "json" else _predict_npy
                probs, meta = await fn(session, base, auth, ids)
                _well_formed(probs, rows, f"{kind} predict")
                branch = int(meta["routing"]["eg"])
                check(branch in (0, 1), f"router picked branch {branch}")
                answered.append((kind, ids, probs, branch))
                return meta

            meta = await one("json", 1)
            await one("json", 3)
            await one("npy", 2)
            # one feedback call: the rewarded arm's mean becomes finite, so
            # the never-pulled arm (mean = +inf) takes the next requests
            async with session.post(
                f"{base}/api/v0.1/feedback",
                json={"response": {"meta": meta}, "reward": 1.0},
                headers=auth,
            ) as resp:
                check(resp.status == 200, f"feedback: HTTP {resp.status}")
            await one("npy", 1)
            # concurrent arrivals: the micro-batcher coalesces them
            await asyncio.gather(one("json", 2), one("npy", 5), one("json", 1))
            branches = collections.Counter(b for *_, b in answered)
            check(
                set(branches) == {0, 1},
                f"feedback did not move the router: branches served {dict(branches)}",
            )

            # long-context: the COMPILED Pallas kernel on a served path
            ids = rng.integers(0, vocab, (1, geo["long_seq"]))
            long_probs = {}
            for kernel, c in long_crs.items():
                name = c["spec"]["name"]
                a = await _token(session, base, f"{name}-key", f"{name}-secret")
                probs, _ = await _predict_npy(session, base, a, ids)
                _well_formed(probs, 1, f"long-context {kernel}")
                long_probs[kernel] = probs
            kdiff = float(np.abs(long_probs["auto"] - long_probs["blockwise"]).max())
            check(
                kdiff <= KERNEL_PROB_TOL,
                f"seq {geo['long_seq']}: auto vs blockwise differ by {kdiff}",
            )
        warm_s = time.perf_counter() - t_req

        refs = {
            seed: _bert_reference(geo["bert"], {"seed": seed, "seq": geo["seq"]})
            for seed in (0, 1)  # bert-a, bert-b
        }
        worst = 0.0
        for kind, ids, probs, branch in answered:
            diff = float(np.abs(probs - refs[branch](ids)).max())
            worst = max(worst, diff)
            check(
                diff <= BF16_PROB_TOL,
                f"{kind} predict: served bf16 probabilities differ from the "
                f"float32 forward by {diff} > {BF16_PROB_TOL}",
            )

        def _has_kernel(dep: str) -> bool:
            rt = plat.runtimes(dep)["bert"]
            x = jax.ShapeDtypeStruct((1, geo["long_seq"]), np.int32)
            return "tpu_custom_call" in rt._jit.lower(rt.params, x).as_text()

        kernel_compiled = _has_kernel("bert-long-auto")
        if jax.default_backend() != "cpu":
            check(
                kernel_compiled,
                f"the seq-{geo['long_seq']} auto deployment's served program "
                "holds no compiled Pallas kernel (tpu_custom_call)",
            )
        check(
            not _has_kernel("bert-long-blockwise"),
            "attn_kernel=blockwise program holds a Pallas kernel",
        )
        recompiles = _cache_sizes(runtimes) - programs
        check(recompiles == 0, f"classic tier recompiled {recompiles}x after warmup")
        where = _where([rt.params for rt in runtimes.values()])
        check(
            len(where["device_ids"]) == n_devices,
            f"BERT params on devices {where['device_ids']}, want {n_devices}",
        )
    finally:
        await plat.close()
    return _logged({
        "phase": "classic",
        "model": geo["bert"],
        "seq": geo["seq"],
        "long_seq": geo["long_seq"],
        "requests": len(answered) + len(long_probs),
        "branches_served": dict(branches),
        "max_prob_diff_vs_f32": worst,
        "bf16_tol": BF16_PROB_TOL,
        "pallas_vs_blockwise_diff": kdiff,
        "pallas_kernel_compiled_on_served_path": kernel_compiled,
        "boot_s": round(boot_s, 2),
        "cold_compile_s": round(cold_s, 2),
        "warm_requests_s": round(warm_s, 2),
        "programs_compiled": programs,
        "recompiles_after_warmup": recompiles,
        "compile_cache": _delta(_cache_counts(), cache0),
        "params_on": where,
    })


# ------------------------------------------------------------ generative tier


def _gen_cr(geo: dict, kv_dtype: str, tpu_overrides: dict | None = None) -> dict:
    """tiny_gpt at the tensor-parallel example's geometry on ONE device's
    paged pool; kv_dtype '' = float32 pool, 'int8' = the paged_kv example."""
    cr = _load_cr("tiny_gpt_tensor_parallel.json")
    pred = cr["spec"]["predictors"][0]
    tpu = pred["tpu"]
    tpu.pop("decode_mesh_axes")
    if kv_dtype:
        paged = _load_cr("tiny_gpt_paged_kv.json")["spec"]["predictors"][0]["tpu"]
        check(paged["decode_kv_dtype"] == kv_dtype, "paged_kv example changed")
        for k in ("decode_prefix_slots", "decode_prefill_chunk",
                  "decode_kv_page_size", "decode_kv_pages"):
            check(tpu[k] == paged[k], f"examples disagree on {k}")
        tpu["decode_kv_dtype"] = kv_dtype
    tpu.update(tpu_overrides or {})
    for k, v in geo["gen_params"].items():
        _set_param(pred["graph"], k, v, "INT")
    return cr


def _predictor(cr: dict):
    """Defaulted + validated the way serving/server.py prepares any spec."""
    from seldon_core_tpu.graph.spec import SeldonDeployment
    from seldon_core_tpu.serving.server import _prepare

    dep = SeldonDeployment.from_dict(cr)
    return _prepare(dep.spec.predictors[0], dep.spec.name)


async def _gen_request(session, base, ids, tags: dict, stream: bool = False):
    body = {"meta": {"tags": tags}, "data": {"ndarray": ids.tolist()}}
    if not stream:
        async with session.post(f"{base}/api/v0.1/predictions", json=body) as resp:
            out = await resp.json()
            check(resp.status == 200, f"generate: HTTP {resp.status} {out}")
        arr = np.asarray(out["data"]["ndarray"], np.int64)
        return arr, out["meta"]["tags"]
    events = []
    async with session.post(f"{base}/api/v0.1/predictions/stream", json=body) as resp:
        check(resp.status == 200, f"stream: HTTP {resp.status}")
        check(
            resp.headers.get("Content-Type", "").startswith("text/event-stream"),
            "stream endpoint did not answer text/event-stream",
        )
        buf = b""
        async for chunk in resp.content.iter_any():
            buf += chunk
        for frame in buf.split(b"\n\n"):
            if frame.startswith(b"data: "):
                events.append(json.loads(frame[len(b"data: "):]))
    return events


def _oracle(params, max_new: int):
    """The whole-batch scan oracle (models/decoder.generate — what
    zoo._apply_tiny_gpt serves without the scheduler) and its teacher-forced
    logits, jitted on the scheduler's own device with its own params."""
    import jax

    from seldon_core_tpu.models.decoder import generate, sequence_logits

    gen = jax.jit(lambda p, x: generate(p, x, max_new))

    def ids(prompts: np.ndarray) -> np.ndarray:
        return np.asarray(gen(params, np.asarray(prompts, np.int32)), np.int64)

    def logits(seqs: np.ndarray, precision: str) -> np.ndarray:
        with jax.default_matmul_precision(precision):
            # a fresh jit per precision: the context is read at trace time
            out = jax.jit(sequence_logits)(params, np.asarray(seqs, np.int32))
        return np.asarray(out, np.float64)

    return ids, logits


def _judge_served(served: list, oracle_ids, oracle_logits, seq_len: int, int8: bool) -> dict:
    """Hold every served sequence (buffered, streamed, warm repeat) to the
    oracle. ``served``: (what, prompt_row, ids[seq_len + n]) triples.

    Greedy identity with the scan oracle is a float32-arithmetic contract.
    The chip's default float32 matmul is bf16 passes, and programs of
    different shape (chunk ladder vs whole prompt, cold vs prefix-hit) round
    differently, so a near-tie argmax may flip. What must hold everywhere:
    teacher-forced along the SERVED tokens, the oracle's exact (precision
    "highest") logit of each served token trails its best logit by at most
    2*delta, where delta is the chip's own rounding noise for this model —
    the oracle's default-precision logits against its exact ones, measured
    here (0 on the CPU backend, which makes the rule identity there). A
    wrong page, position or mask moves logits by O(1), far outside it."""
    width = max(len(ids) for _, _, ids in served)
    seqs = np.zeros((len(served), width), np.int64)
    for r, (_, _, ids) in enumerate(served):
        seqs[r, : len(ids)] = ids
    exact = oracle_logits(seqs, "highest")
    noise = np.abs(oracle_logits(seqs, "default") - exact)
    scan = oracle_ids(np.stack([seqs[r, :seq_len] for r in range(len(served))]))
    delta, worst, agree, total, first_div = 0.0, 0.0, 0, 0, None
    for r, (what, _, ids) in enumerate(served):
        for pos in range(seq_len, len(ids)):
            step = exact[r, pos - 1]
            gap = float(step.max() - step[ids[pos]])
            delta = max(delta, float(noise[r, pos - 1].max()))
            worst = max(worst, gap)
            total += 1
            if ids[pos] == scan[r, pos]:
                agree += 1
            elif first_div is None:
                top2 = np.sort(step)[-2:]
                first_div = {
                    "sequence": what,
                    "generated_position": pos - seq_len,
                    "served": int(ids[pos]),
                    "scan_oracle": int(scan[r, pos]),
                    "oracle_logit_gap_of_served": gap,
                    "oracle_top2_margin": float(top2[1] - top2[0]),
                }
    tol = INT8_LOGIT_GAP_TOL if int8 else 2.0 * delta
    verdict = {
        "greedy_agreement_with_scan_oracle": f"{agree}/{total}",
        "first_divergence": first_div,
        "worst_oracle_logit_gap": worst,
        "f32_matmul_rounding_delta": delta,
        "logit_gap_tol": tol,
    }
    check(
        worst <= tol,
        f"a served token trails the oracle's best logit by {worst} > {tol}: {verdict}",
    )
    if int8:
        check(agree / total > INT8_MIN_AGREEMENT, f"int8 pool: agreement {agree}/{total}")
    return verdict


def _schedulers(sched):
    live = getattr(sched, "live_replicas", None)
    return [sched] if live is None else [r for _, r in live]


def _assert_mechanisms(sched, tpu: dict) -> dict:
    """The serving builder warn-and-disables unservable opt-ins to keep a
    stale CR serving; the smoke asserts what it asked for is actually ON."""
    from seldon_core_tpu.serving.affinity_router import ReplicatedDecodeScheduler
    from seldon_core_tpu.serving.decode_scheduler import DecodeScheduler

    check(sched is not None, "decode_slots set but no decode scheduler was built")
    want_replicas = int(tpu.get("decode_replicas", 1))
    if want_replicas > 1:
        check(isinstance(sched, ReplicatedDecodeScheduler), "replicated tier not built")
        check(
            len(sched.live_replicas) == want_replicas,
            f"{len(sched.live_replicas)} replicas live, asked for {want_replicas}",
        )
    else:
        check(isinstance(sched, DecodeScheduler), f"scheduler is {type(sched)}")
    want_tp = int(np.prod(list((tpu.get("decode_mesh_axes") or {"tp": 1}).values())))
    for s in _schedulers(sched):
        check(s.tp == want_tp, f"scheduler runs at tp={s.tp}, asked for {want_tp}")
        check(s.n_slots == tpu["decode_slots"], f"n_slots {s.n_slots}")
        check(s.pool.page_size == tpu["decode_kv_page_size"], "page size")
        check(s.pool.n_pages == tpu["decode_kv_pages"], "page budget")
        check(s.pool.kv_dtype == tpu.get("decode_kv_dtype", ""), "kv dtype")
        want_parts = 6 if s.pool.kv_dtype == "int8" else 2
        check(len(s.pool.state) == want_parts, "pool state layout")
        if s.pool.kv_dtype == "int8":
            check(str(s.pool.state[0].dtype) == "int8", "int8 pool holds no int8")
        check(s.prefix_slots == tpu["decode_prefix_slots"], "prefix cache is off")
        check(
            s.prefill_chunk == tpu["decode_prefill_chunk"]
            and max(c for _rows, c in s.chunk_buckets) == s.prefill_chunk,
            f"chunked prefill is off: ladder {s.chunk_buckets}",
        )
    return {"tp": want_tp, "replicas": want_replicas}


async def run_generative(
    geo: dict,
    label: str,
    cr: dict,
    prompts: np.ndarray,
    *,
    cache_prefix: int = 0,
    openers: int = 1,
    stream: bool = True,
    repeat: bool = True,
) -> dict:
    """Boot one PredictorServer the way serving/server._amain does, drive it
    over HTTP, hold the answers to the scan oracle, audit, stop it."""
    import aiohttp

    from seldon_core_tpu.serving.server import PredictorServer

    cache0 = _cache_counts()
    seq_len, max_new = geo["gen_params"]["seq"], geo["gen_params"]["max_new_tokens"]
    t_boot = time.perf_counter()
    pred, dep_name = _predictor(cr)
    tpu = cr["spec"]["predictors"][0]["tpu"]
    server = PredictorServer(pred, deployment_name=f"{dep_name}-{label}")
    sched = server.decode_scheduler
    mech = _assert_mechanisms(sched, tpu)
    scheds = _schedulers(sched)
    # jit caches are keyed on the module-level fused functions, so any one
    # scheduler's count covers every scheduler (and replica) this process
    # ever built: report the delta
    programs = -sum(scheds[0].compile_counts().values())
    t_warm = time.perf_counter()
    server.warmup()
    cold_s = time.perf_counter() - t_warm
    programs += sum(scheds[0].compile_counts().values())
    port = _free_port()
    await server.start(host="127.0.0.1", port=port, grpc_port=None, fast_ingress=True)
    boot_s = time.perf_counter() - t_boot
    base = f"http://127.0.0.1:{port}"
    # heavy-tailed budgets like a real queue: a few long, most short
    budgets = [max_new if i % 3 == 0 else max(4, max_new // 4) for i in range(len(prompts))]
    served: list = []  # (what, prompt row, ids) — judged after the timed window
    try:
        async with aiohttp.ClientSession() as session:
            t_req = time.perf_counter()

            async def one(i: int):
                tags = {"max_new_tokens": budgets[i]}
                if cache_prefix:
                    tags["cache_prefix"] = cache_prefix
                arr, out_tags = await _gen_request(session, base, prompts[i : i + 1], tags)
                check(int(out_tags["gen_lens"][0]) == budgets[i], f"gen_lens {out_tags}")
                check(arr.shape == (1, seq_len + budgets[i]), f"shape {arr.shape}")
                check(bool(np.array_equal(arr[0, :seq_len], prompts[i])), "prompt not echoed")
                return (f"buffered[{i}]", i, arr[0])

            # each family's opener captures its prefix before the sharers land
            served += await asyncio.gather(*(one(i) for i in range(openers)))
            served += await asyncio.gather(*(one(i) for i in range(openers, len(prompts))))
            if stream:
                events = await _gen_request(
                    session, base, prompts[:1], {"max_new_tokens": budgets[0]}, stream=True
                )
                toks = [e["token"] for e in events if "token" in e]
                done = events[-1]
                check(done.get("done") is True, f"stream ended with {done}")
                check(len(toks) == budgets[0], f"streamed {len(toks)} tokens")
                check(
                    done["ids"][0] == prompts[0].tolist() + toks,
                    "the stream's done event disagrees with its token events",
                )
                served.append(("streamed[0]", 0, np.asarray(done["ids"][0], np.int64)))
            if repeat:
                hits0 = sched.stat_prefix_hits
                served.append(await one(0))
                check(
                    sched.stat_prefix_hits > hits0,
                    "a repeated prompt did not hit the prefix cache",
                )
                async with session.get(f"{base}/metrics") as resp:
                    metrics = await resp.text()
                check(
                    "seldon_tpu_decode_prefix_lookups_total{" in metrics
                    and 'outcome="hit"' in metrics,
                    "/metrics shows no prefix hit",
                )
            warm_s = time.perf_counter() - t_req

        verdict = _judge_served(
            served,
            *_oracle(scheds[0].params, max_new),
            seq_len,
            int8=tpu.get("decode_kv_dtype") == "int8",
        )
        recompiles = sched.recompiles_since_warmup()
        check(recompiles == 0, f"{recompiles} recompiles after warmup")
        for s in scheds:
            s.pool.alloc.check()
        hits, misses = sched.stat_prefix_hits, sched.stat_prefix_misses
    finally:
        await server.stop()
    return _logged({
        "phase": f"generative:{label}",
        **{k: geo["gen_params"][k] for k in ("hidden", "ffn", "layers", "seq", "max_new_tokens")},
        "kv_dtype": tpu.get("decode_kv_dtype") or "float32",
        **mech,
        "requests": len(served),
        **verdict,
        "prefix_hit_rate": round(hits / max(hits + misses, 1), 3),
        "recompiles_after_warmup": recompiles,
        "allocator_audit": "green",
        "boot_s": round(boot_s, 2),
        "cold_compile_s": round(cold_s, 2),
        "warm_requests_s": round(warm_s, 2),
        "programs_compiled": programs,
        "compile_cache": _delta(_cache_counts(), cache0),
        "params_on": [_where(s.params) for s in scheds],
        "kv_pages_on": [_where(s.pool.state) for s in scheds],
        "_served": served,
    })


def _shared_prompts(geo: dict, n: int, groups: int = 1) -> np.ndarray:
    """n prompts in ``groups`` families; a family shares its leading 3/4."""
    seq, vocab = geo["gen_params"]["seq"], geo["gen_vocab"]
    rng = np.random.default_rng(geo["seed"] + 1)
    heads = [rng.integers(0, vocab, seq * 3 // 4) for _ in range(groups)]
    return np.stack(
        [
            np.concatenate([heads[i % groups], rng.integers(0, vocab, seq - seq * 3 // 4)])
            for i in range(n)
        ]
    ).astype(np.int64)


async def phase_generative(geo: dict) -> None:
    prompts = _shared_prompts(geo, 5)
    for label, kv_dtype in (("f32-pool", ""), ("int8-pool", "int8")):
        await run_generative(geo, label, _gen_cr(geo, kv_dtype), prompts)


# ------------------------------------------------------------ four-chip paths


async def phase_four_chips(geo: dict) -> None:
    import aiohttp

    n = 4
    # --- tensor-parallel decode vs the single-device scheduler
    prompts = _shared_prompts(geo, 5)
    one_dev = {"mesh": {"data": 1}}  # or the defaulted data mesh replicates it 4x
    single = await run_generative(geo, "tp1", _gen_cr(geo, "", one_dev), prompts)
    check(len(single["params_on"][0]["device_ids"]) == 1, "tp1 baseline spans devices")
    tp = await run_generative(
        geo, "tp4", _gen_cr(geo, "", {**one_dev, "decode_mesh_axes": {"tp": n}}), prompts
    )
    check(tp["tp"] == n, "tp width")
    check(
        len(tp["params_on"][0]["device_ids"]) == n
        and len(tp["kv_pages_on"][0]["device_ids"]) == n,
        f"tp={n} params/pool not on {n} devices: {tp['params_on']} {tp['kv_pages_on']}",
    )
    # each was held to the scan oracle inside; here, to each other
    same = sum(
        int(np.array_equal(a[2], b[2])) for a, b in zip(single["_served"], tp["_served"])
    )
    log({"phase": "generative:tp4", "sequences_identical_to_tp1": f"{same}/{len(tp['_served'])}"})

    # --- replicas behind the affinity router vs one scheduler
    groups, per_group = 8, 6
    rprompts = _shared_prompts(geo, groups * per_group, groups=groups)
    cache_prefix = geo["gen_params"]["seq"] * 3 // 4
    pages = 1 + 12 * 6 + groups * 3 + 3  # slots' tails + every group's pinned prefix
    kw = dict(cache_prefix=cache_prefix, openers=groups, stream=False, repeat=False)
    base_tpu = {**one_dev, "decode_kv_pages": pages}
    solo = await run_generative(geo, "replicas1", _gen_cr(geo, "", base_tpu), rprompts, **kw)
    fleet = await run_generative(
        geo,
        "replicas4",
        _gen_cr(geo, "", {**base_tpu, "decode_replicas": n, "decode_router_policy": "affinity"}),
        rprompts,
        **kw,
    )
    homes = [tuple(w["device_ids"]) for w in fleet["params_on"]]
    pools = [tuple(w["device_ids"]) for w in fleet["kv_pages_on"]]
    check(
        len(set(homes)) == n and all(len(h) == 1 for h in homes) and homes == pools,
        f"{n} replicas do not sit on {n} distinct devices: params {homes} pools {pools}",
    )
    # round-robin's ceiling: every replica pays its own cold capture per group
    rr_floor = (len(rprompts) - n * groups) / len(rprompts)
    log({"phase": "generative:replicas4", "round_robin_floor": round(rr_floor, 3),
         "single_scheduler_hit_rate": solo["prefix_hit_rate"]})
    check(
        fleet["prefix_hit_rate"] > rr_floor,
        f"fleet hit rate {fleet['prefix_hit_rate']} not above the round-robin "
        f"floor {rr_floor:.3f}",
    )

    # --- the classic tier's data mesh: {"data": 4} BERT-base vs single-device
    cache0 = _cache_counts()
    t0 = time.perf_counter()
    tpu = {"max_batch": 8, "batch_buckets": [8], "dtype": "bfloat16"}
    uri = f"zoo://{geo['bert']}?seq={geo['seq']}"
    plat = _Platform()
    plat.apply(_bert_cr("bert-data4", uri, {**tpu, "mesh": {"data": n}}))
    plat.apply(_bert_cr("bert-data1", uri, {**tpu, "mesh": {"data": 1}}))
    plat.warmup("bert-data4")
    plat.warmup("bert-data1")
    base = await plat.serve()
    try:
        ids = np.random.default_rng(geo["seed"]).integers(
            0, geo["bert_vocab"], (8, geo["seq"])
        )
        probs = {}
        async with aiohttp.ClientSession() as session:
            for name in ("bert-data4", "bert-data1"):
                a = await _token(session, base, f"{name}-key", f"{name}-secret")
                probs[name], _ = await _predict_npy(session, base, a, ids)
                _well_formed(probs[name], 8, name)
        diff = float(np.abs(probs["bert-data4"] - probs["bert-data1"]).max())
        check(diff <= KERNEL_PROB_TOL, f"data=4 vs single-device differ by {diff}")
        ref = _bert_reference(geo["bert"], {"seed": 0, "seq": geo["seq"]})(ids)
        rdiff = float(np.abs(probs["bert-data4"] - ref).max())
        check(rdiff <= BF16_PROB_TOL, f"data=4 vs float32 forward differ by {rdiff}")
        rt4 = plat.runtimes("bert-data4")["bert"]
        rt1 = plat.runtimes("bert-data1")["bert"]
        w4, w1 = _where(rt4.params), _where(rt1.params)
        check(len(w4["device_ids"]) == n, f"data={n} params on {w4['device_ids']}")
        check(len(w1["device_ids"]) == 1, f"single-device params on {w1['device_ids']}")
        y = rt4.predict_device(np.asarray(ids, np.int32))
        shard_rows = sorted(s.data.shape[0] for s in y.addressable_shards)
        check(
            shard_rows == [8 // n] * n,
            f"data={n} output rows per device {shard_rows}, want {[8 // n] * n}",
        )
    finally:
        await plat.close()
    log(
        {
            "phase": "classic:data4",
            "model": geo["bert"],
            "data4_vs_single_device_diff": diff,
            "data4_vs_f32_diff": rdiff,
            "output_rows_per_device": shard_rows,
            "wall_s": round(time.perf_counter() - t0, 2),
            "compile_cache": _delta(_cache_counts(), cache0),
            "params_on": w4,
            "single_device_params_on": w1,
        }
    )


# ----------------------------------------------------------------------- main

REAL_GEOMETRY = {
    "seed": 0,
    "bert": "bert_base",
    "bert_vocab": 30522,
    "seq": 128,
    "long_seq": 4096,  # = ops/attention.PALLAS_MIN_SEQ, asserted below
    "bert_max_batch": 64,  # the example's own
    "gen_vocab": 512,
    # examples/deployments/tiny_gpt_tensor_parallel.json, as shipped
    "gen_params": {
        "hidden": 256, "ffn": 1024, "layers": 4,
        "seq": 64, "max_new_tokens": 32, "max_len": 128,
    },
}

# --rehearse: the same control flow at a size the CPU backend walks in about
# a minute; never a result
TINY_GEOMETRY = {
    **REAL_GEOMETRY,
    "bert": "bert_tiny",
    "bert_vocab": 1024,
    "seq": 16,
    "long_seq": 128,
    "bert_max_batch": 8,
    "gen_params": {
        "hidden": 256, "ffn": 256, "layers": 1,
        "seq": 64, "max_new_tokens": 32, "max_len": 128,
    },
}


async def _amain(args, device: dict) -> None:
    geo = TINY_GEOMETRY if args.rehearse else REAL_GEOMETRY
    if args.chips == 4:
        await phase_four_chips(geo)
    else:
        await phase_classic(geo, device["count"])
        await phase_generative(geo)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--rehearse",
        action="store_true",
        help="walk the control flow at a tiny size on the CPU backend; "
        "never prints the passing line, always exits 3",
    )
    args = ap.parse_args(argv)

    import jax

    from seldon_core_tpu import native
    from seldon_core_tpu.ops.attention import PALLAS_MIN_SEQ
    from seldon_core_tpu.utils.compile_cache import enable_compile_cache

    jax.monitoring.register_event_listener(lambda name, **kw: _events.update([name]))
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] == "cpu" and not args.rehearse:
        print(
            "chip_smoke: JAX found no accelerator (platform cpu) — nothing "
            "to prove here; run it on the chip",
            file=sys.stderr,
        )
        return 2
    if device["platform"] != "cpu" and args.rehearse:
        print("chip_smoke: --rehearse is for the CPU backend", file=sys.stderr)
        return 2
    if device["count"] != args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but JAX reports "
            f"{device['count']} devices",
            file=sys.stderr,
        )
        return 2
    assert REAL_GEOMETRY["long_seq"] >= PALLAS_MIN_SEQ
    log(
        {
            "phase": "start",
            "device": device,
            "rehearsal": args.rehearse,
            "compile_cache_dir": cache_dir,
            "compile_cache_entries_at_start": len(os.listdir(cache_dir))
            if cache_dir and os.path.isdir(cache_dir)
            else 0,
            "native_codec": "fastcodec.so" if native.available() else "python fallback",
            "jax": jax.__version__,
        }
    )
    t0 = time.perf_counter()
    try:
        asyncio.run(_amain(args, device))
    except SmokeFailure as e:
        log({"phase": "failed", "error": str(e)})
        print(json.dumps({"ok": False, "device": device}))
        return 1
    log(
        {
            "phase": "done",
            "wall_s": round(time.perf_counter() - t0, 2),
            "compile_cache": _cache_counts(),
        }
    )
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}))
        return 3
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # The verdict is printed; leave WITHOUT interpreter finalization. An XLA
    # worker thread that drops its last Python reference while CPython
    # finalizes is killed by the GIL guard inside a C++ frame and aborts
    # the process (seen 1 exit in 6 after the tensor-parallel phase on the
    # CPU backend) — and this script's exit code is its contract. It owns
    # no child process, temp file or socket by now.
    os._exit(code)
