"""One cell of the benchmark, once, in a new process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's deployment through the product's normal path
(``harness/deploy.py``), warms the cell's shapes, holds the configuration to
its plain reference (``reference/<config>.py``), starts the load child
(``harness/loadgen.py``), measures for ``--seconds`` and prints one JSON
object as the last line of stdout: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``. ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics, each from the
reader file ``metrics/`` or ``layer_metrics/<name>.py`` that BENCHMARK.json
names. Everything that belongs to one configuration, traffic mix or metric
is a file found by name: a later PR adds files and entries and edits none.

Without an accelerator, or with fewer chips than the cell asks for, it exits
2 and prints no result. ``--rehearse`` walks the same control flow on the CPU
backend at the tiny sizes of ``overrides/rehearse.json``; it can never print
a result line and always exits 3.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from harness import cells  # noqa: E402

def say(**kw) -> None:
    """An earlier line: JSON, flushed at once, never the result."""
    print(json.dumps(kw), flush=True)


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def rehearsal_sizes(config: dict, traffic: dict, cell: dict) -> tuple[dict, dict]:
    """Tiny sizes for the CPU walk: unit parameters and tpu keys by
    configuration, traffic keys by mix."""
    with open(os.path.join(HERE, "overrides", "rehearse.json")) as f:
        over = json.load(f)
    c = over["configs"].get(cell["config"], {})
    sizes = dict(c.get("parameters", {}))
    if "model_uri" in c:
        sizes["model_uri"] = c["model_uri"]
    config = merge(config, c.get("config", {}))
    pred = config["deployment"]["spec"]["predictors"][0]
    pred["tpu"].update(c.get("tpu", {}))

    def walk(unit):
        for p in unit.get("parameters", []):
            if p["name"] in sizes:
                p["value"] = str(sizes[p["name"]])
        for ch in unit.get("children", []):
            walk(ch)

    walk(pred["graph"])
    return config, merge(traffic, over["traffic"].get(cell["traffic"], {}))


async def sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        await asyncio.sleep(d)


# ----------------------------------------------------------------- one run


async def traced_slice(span: float) -> None:
    """Profile ``span`` seconds under the ``WINDOW`` annotation. Starting and
    stopping the profiler block for a while: off the serving loop."""
    import jax

    from harness.trace import TRACE_DIR, WINDOW

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # device ops and the benchmark's annotations, not every Python call
    opts.host_tracer_level = 1
    loop = asyncio.get_running_loop()
    await loop.run_in_executor(
        None, functools.partial(jax.profiler.start_trace, TRACE_DIR, profiler_options=opts)
    )
    with jax.profiler.TraceAnnotation(WINDOW):
        await asyncio.sleep(span)
    await loop.run_in_executor(None, jax.profiler.stop_trace)


async def run_load(dep, plan: dict, *, trace: bool, sample_queue: bool = False) -> dict:
    """Start the load child, place the window, read counters at its edges,
    trace a slice of it, collect the child's report."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    child = await asyncio.create_subprocess_exec(
        sys.executable, os.path.join(HERE, "harness", "loadgen.py"),
        stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE, env=env,
    )
    obs: dict = {}
    try:
        child.stdin.write(json.dumps(plan).encode() + b"\n")
        await child.stdin.drain()
        if (await child.stdout.readline()).strip() != b"READY":
            raise RuntimeError("the load child did not come up")
        # load starts when the child is ready, not a guessed time after its start
        t_load = time.monotonic() + 0.5
        t0 = t_load + plan["ramp_s"]
        t1 = t0 + plan["seconds"]
        obs.update(t_load=t_load, t0=t0, t1=t1)
        child.stdin.write(f"{t_load!r}\n".encode())
        await child.stdin.drain()
        talk = asyncio.ensure_future(child.stdout.read())
        await sleep_until(t0)
        obs["before"] = dep.counters()
        depth = []
        if trace:
            # a slice from the window's middle: a few seconds, as the guide says
            span = min(4.0, plan["seconds"] / 3)
            await sleep_until(t0 + (plan["seconds"] - span) / 2)
            await traced_slice(span)
        while sample_queue and time.monotonic() < t1:
            depth.append((time.monotonic() - t0, dep.sched.queue_depth, dep.sched.active))
            await asyncio.sleep(0.25)
        await sleep_until(t1)
        obs["after"] = dep.counters()
        obs["frames"] = dep.frames(t0, t1)
        obs["queue_samples"] = depth
        out = await talk
        if await child.wait() != 0:
            raise RuntimeError(f"the load child exited {child.returncode}")
        obs["report"] = json.loads(out)
    finally:
        if child.returncode is None:
            child.kill()
            await child.wait()
    return obs


def device_facts() -> dict:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": int(max(peaks))}


def read_metrics(bench: dict, cell: dict, o: dict, traced: bool) -> dict:
    """The cell's metrics of one group, each from its reader file; a reader
    that finds nothing to read leaves its metric out."""
    metrics = {}
    for m, reader in cells.readers(ROOT, bench, cell, traced):
        value = reader.read(o)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


async def amain(args, bench: dict, cell: dict, config: dict, traffic: dict) -> int:
    from harness.correct import compared
    from harness.deploy import TIERS
    from harness.observe import observations
    from harness.traffic import build_plan

    setup = {"imports_s": time.monotonic() - T_START}
    if args.trace:
        # keep every request's spans for the traced run (the product's default
        # store keeps a 5% sample); set before the services take the tracer
        from seldon_core_tpu import telemetry
        from seldon_core_tpu.telemetry.store import SpanStore

        telemetry.configure(telemetry.Tracer(store=SpanStore(slow_keep=0, max_sampled=50000, sample_rate=1.0)))
    t = time.monotonic()
    dep = TIERS[config["tier"]](config, args.seed)
    setup["weights_s"] = time.monotonic() - t
    t = time.monotonic()
    dep.warm()
    setup["compile_or_cache_and_warm_s"] = time.monotonic() - t
    await dep.start()
    t = time.monotonic()
    ref = cells.load_module(ROOT, bench, "reference", cell["config"])
    verdict = dep.judge(ref, await dep.sample(traffic, args.seed))
    setup["prime_and_reference_s"] = time.monotonic() - t
    say(phase="reference", when="before the window", **verdict)
    if args.trace:
        dep.annotate()

    plan = build_plan(traffic, seed=args.seed, seconds=args.seconds)
    plan.update(url=dep.url, path=dep.path, auth=dep.auth, seed=args.seed, vocab=dep.vocab, seq=dep.seq)
    if args.sweep:
        return await sweep(args, dep, bench, cell, config, traffic, plan)
    t = time.monotonic()
    obs = await run_load(dep, plan, trace=bool(args.trace))
    setup["load_child_start_and_ramp_s"] = obs["t0"] - t
    setup_s = obs["t0"] - T_START
    setup["other_s"] = setup_s - sum(setup.values())
    say(phase="setup", setup_s=setup_s, **setup)

    # after the window: answers the measured traffic got, against the reference
    kept = dep.kept(obs["report"]["requests"])
    after = dep.judge(ref, kept) if kept else {"ok": False, "why": "the window kept no answer"}
    say(phase="reference", when="answers from the window", **after)

    o = observations(dep, config, traffic, cell, obs, setup_s, args, device_facts())
    say(phase="client", **o["client_summary"])
    if args.trace:
        say(phase="trace", lines=o["trace_lines"], families=o["trace"]["families"],
            window_s=o["trace"]["window_s"], busy_s=o["trace"]["busy_s"])
        from harness import scopes

        scoped = scopes.of_run(o) or {}
        say(phase="scopes", step=scopes.per_dispatch_ms(scoped.get("step")),
            chunk=scopes.per_dispatch_ms(scoped.get("chunk")))
        if args.keep_trace_events:
            from harness.trace import trimmed

            os.makedirs(os.path.dirname(os.path.abspath(args.keep_trace_events)), exist_ok=True)
            with open(args.keep_trace_events, "w") as f:
                json.dump(trimmed(o["trace_events"], 0.3), f)
    result = {
        "correct": bool(verdict["ok"] and after["ok"]),
        "attempted": o["attempted"],
        "failed": o["failed"],
        "metrics": read_metrics(bench, cell, o, bool(args.trace)),
        "device": o["device"],
    }
    if args.trace and o["trace"]:
        result["breakdown"] = {"device_ops": o["trace"]["device_ops"], "idle_gaps": o["trace"]["idle_gaps"]}
    # each number compared beside its limit: last in the result's line, and the last lines on standard error
    result["compared"] = compared(before_window=verdict, window_answers=after)
    for name, c in result["compared"].items():
        print(f"run.py: compared {name} = {c['value']!r}, limit {c['limit']!r}", file=sys.stderr, flush=True)
    if args.rehearse:
        say(phase="rehearsal", note="CPU backend, tiny sizes: never a result", would_report=result)
        return 3
    print(json.dumps(result), flush=True)
    return 0


async def sweep(args, dep, bench: dict, cell: dict, config: dict, traffic: dict, plan0: dict) -> int:
    """The knee, once: several fixed rates in one process after one set-up.
    A rate is sustained when the not-yet-admitted backlog does not grow from
    the window's first third to its last. Beside each rate, what the cell's
    own readers make of that window (every metric that needs no trace), the
    gaps' histogram and how late the generator sent: the rate is chosen from
    them (PERF.md section 4)."""
    from harness.observe import observations
    from harness.traffic import build_plan

    untraced = argparse.Namespace(trace=0)
    for rate in [float(r) for r in args.sweep.split(",")]:
        at_rate = {**traffic, "rate_rps": rate}
        plan = build_plan(at_rate, seed=args.seed, seconds=args.seconds)
        plan.update({k: plan0[k] for k in ("url", "path", "auth", "seed", "vocab", "seq")})
        obs = await run_load(dep, plan, trace=False, sample_queue=True)
        q = obs["queue_samples"]
        third = args.seconds / 3
        first = [d for t, d, _ in q if t < third]
        last = [d for t, d, _ in q if t >= 2 * third]
        o = observations(dep, config, at_rate, cell, obs, None, untraced, device_facts())
        readings = {**read_metrics(bench, cell, o, False), **read_metrics(bench, cell, o, True)}
        client = o["client_summary"]
        say(phase="sweep", rate_rps=rate, due=o["attempted"], failed=o["failed"], errors=client["errors"],
            backlog_first_third=sum(first) / max(len(first), 1),
            backlog_last_third=sum(last) / max(len(last), 1),
            backlog_max=max([d for _, d, _ in q] or [0]),
            active_mean=sum(a for _, _, a in q) / max(len(q), 1),
            tokens_per_s_counted=(obs["after"]["tokens"] - obs["before"]["tokens"]) / args.seconds,
            **{name: m["value"] for name, m in readings.items()},
            **{k: client[k] for k in ("gap_hist_5ms", "gap_hist_1ms_near_p95", "generator_late_ms_max", "generator_late_over_5ms",
                                      "child_stalls_ms_at_s")})
        await asyncio.sleep(20.0)  # drain: cancelled streams retire, the queue empties
    return 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--candidate", default="", help="also read candidates/<name>.json: cells built and run but not listed")
    ap.add_argument("--keep-trace-events", default="", help="write the traced slice's first 0.3 s as plain events (JSON): the selfcheck's fixture is made so")
    ap.add_argument("--sweep", default="", help="comma-separated request rates: find the knee, print a table, no result")
    args = ap.parse_args(argv)

    try:
        found = cells.resolve(ROOT, args.workload, args.candidate)
    except KeyError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    bench, cell, config, traffic = (found[k] for k in ("bench", "cell", "config", "traffic"))
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        config, traffic = rehearsal_sizes(config, traffic, cell)

    import jax

    from seldon_core_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform == "cpu" and not args.rehearse:
        print("run.py: JAX found no accelerator (platform cpu): a cell is measured on the chip", file=sys.stderr)
        return 2
    if devs[0].platform != "cpu" and args.rehearse:
        print("run.py: --rehearse is for the CPU backend", file=sys.stderr)
        return 2
    if not args.rehearse and len(devs) < int(cell["chips"]):
        print(f"run.py: the cell asks for {cell['chips']} chips, JAX reports {len(devs)}", file=sys.stderr)
        return 2
    say(phase="start", workload=cell["name"], seed=args.seed, seconds=args.seconds, trace=args.trace,
        device={"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)},
        compile_cache_dir=cache_dir, rehearsal=args.rehearse)
    return asyncio.run(amain(args, bench, cell, config, traffic))


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the result is printed and the load child has ended: leave without
    # interpreter finalization, where an XLA thread can abort the process
    # (chip_smoke.py, PR 23)
    os._exit(code)
