"""The load child: executes a plan over real HTTP and reports event times.

Started by ``run.py`` as its own process so that generating load never
competes with the server for the interpreter lock, and never touches JAX
(it imports numpy, asyncio and aiohttp only; the parent pins
``JAX_PLATFORMS=cpu`` in its environment all the same). The plan arrives as
one JSON line on stdin; the child answers ``READY`` when it could start, reads
the moment load starts as a second line, and leaves its report as one JSON
object on stdout.
All times are ``time.monotonic()`` seconds: CLOCK_MONOTONIC is one clock for
every process of the machine, so the parent can place its window on it.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.traffic import token_ids  # noqa: E402


async def _sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        await asyncio.sleep(d)


async def bearer(session, url: str, auth: dict | None) -> dict:
    """The gateway's OAuth header (client credentials), or none to send."""
    if not auth:
        return {}
    async with session.post(
        url + "/oauth/token",
        data={"grant_type": "client_credentials", "client_id": auth["key"],
              "client_secret": auth["secret"]},
    ) as resp:
        if resp.status != 200:
            raise RuntimeError(f"oauth token: HTTP {resp.status}")
        return {"Authorization": "Bearer " + (await resp.json())["access_token"]}


async def _stream(session, plan: dict, req: dict, rec: dict) -> None:
    """One SSE generation: a time stamp per token event."""
    tags = {"max_new_tokens": int(req["max_new"])}
    if req.get("cache_prefix"):
        tags["cache_prefix"] = int(req["cache_prefix"])
    body = json.dumps(
        {"meta": {"tags": tags},
         "data": {"ndarray": [token_ids(plan["seed"], plan["vocab"], req)]}}
    ).encode()
    rec["sent"] = time.monotonic()
    times = rec["token_times"]
    try:
        async with session.post(
            plan["url"] + plan["path"], data=body,
            headers={"Content-Type": "application/json"},
        ) as resp:
            if resp.status != 200:
                rec["error"] = f"HTTP {resp.status}: {(await resp.text())[:200]}"
                return
            buf = b""
            async for chunk in resp.content.iter_any():
                now = time.monotonic()
                buf += chunk
                while b"\n\n" in buf:
                    frame, buf = buf.split(b"\n\n", 1)
                    if not frame.startswith(b"data: "):
                        continue
                    ev = json.loads(frame[6:])
                    if "token" in ev:
                        times.append(now)
                    elif ev.get("done"):
                        rec["done"] = now
                        rec["gen_len"] = int(ev["gen_lens"][0])
                        rec["ids"] = ev["ids"][0]
                    elif "error" in ev:
                        rec["error"] = json.dumps(ev["error"])[:200]
    except asyncio.CancelledError:
        rec["cut"] = True
        raise
    except Exception as e:  # noqa: BLE001 - a failed request is a result, not a crash
        rec["error"] = f"{type(e).__name__}: {e}"[:200]


async def _predict(session, plan: dict, body: bytes, headers: dict, rec: dict) -> None:
    """One buffered JSON prediction."""
    rec["sent"] = time.monotonic()
    try:
        async with session.post(plan["url"] + plan["path"], data=body, headers=headers) as resp:
            raw = await resp.read()
            rec["done"] = time.monotonic()
            if resp.status != 200:
                rec["error"] = f"HTTP {resp.status}: {raw[:120]!r}"
                return
            if rec.pop("keep", False):
                out = json.loads(raw)
                rec["puid_echoed"] = out["meta"].get("puid", "") == rec.get("puid")
                rec["out"] = out["data"]["ndarray"]
                rec["routing"] = out["meta"].get("routing", {})
    except asyncio.CancelledError:
        rec["cut"] = True
        raise
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"[:200]


def _json_bodies(plan: dict, client: int, n: int = 8) -> list[tuple[bytes, list]]:
    """A client's request bodies, drawn once: generating ids is not what is
    measured."""
    out = []
    for k in range(n):
        rows = [
            token_ids(plan["seed"], plan["vocab"],
                      {"prompt_len": plan["seq"], "uid": client * 100000 + k * 100 + r})
            for r in range(plan["clients"][client][0]["rows"])
        ]
        out.append((json.dumps(rows).encode(), rows))
    return out


async def _closed_client(session, plan, c: int, lane: list, headers, t1: float, recs: list):
    bodies = _json_bodies(plan, c) if plan["protocol"] == "json" else None
    i = n = 0
    while time.monotonic() < t1:
        req = lane[i]
        rec = {"client": c, "uid": req["uid"] + 1000000 * n, "token_times": []}
        recs.append(rec)
        if bodies is None:
            rec["max_new"] = req["max_new"]
            await _stream(session, plan, {**req, "uid": rec["uid"]}, rec)
        else:
            tail, rows = bodies[n % len(bodies)]
            # the client names the request, so the parent can find its spans
            rec["puid"] = f"bench-{c}-{n}"
            body = b'{"meta":{"puid":"' + rec["puid"].encode() + b'"},"data":{"ndarray":' + tail + b"}}"
            # keep a thin sample of answers for the parent's comparisons
            if n % 50 == 0:
                rec["keep"], rec["rows"] = True, rows
            await _predict(session, plan, body,
                           {**headers, "Content-Type": "application/json"}, rec)
        n += 1
        i = i + 1 if i + 1 < len(lane) else plan["cycle_from"]


async def _open_request(session, plan, req, t_load: float, recs: list):
    due = t_load + req["due"]
    await _sleep_until(due)
    rec = {"uid": req["uid"], "due": due, "measured": req["measured"],
           "max_new": req["max_new"], "token_times": []}
    recs.append(rec)
    await _stream(session, plan, req, rec)


async def _heartbeat(stalls: list, every_s: float = 0.005, over_s: float = 0.02) -> None:
    """A timer that does nothing: [time due, seconds late] each time it woke
    ``over_s`` or more late. A generator that sends late while this timer is
    late too was not busy: the process, or the machine, stood still."""
    while True:
        due = time.monotonic() + every_s
        await asyncio.sleep(every_s)
        late = time.monotonic() - due
        if late >= over_s:
            stalls.append([due, late])


async def _amain(plan: dict) -> dict:
    import aiohttp

    recs: list = []
    stalls: list = []
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=None)
    async with aiohttp.ClientSession(connector=conn, timeout=timeout) as session:
        headers = await bearer(session, plan["url"], plan.get("auth"))
        # everything slow is done: say so, and be told when load starts, so a
        # slow start of this process can never eat into the window
        sys.stdout.write("READY\n")
        sys.stdout.flush()
        loop = asyncio.get_running_loop()
        t_load = float(await loop.run_in_executor(None, sys.stdin.readline))
        t0 = t_load + plan["ramp_s"]
        t1 = t0 + plan["seconds"]
        await _sleep_until(t_load)
        beat = asyncio.ensure_future(_heartbeat(stalls))
        if plan["generator"] == "closed":
            tasks = [
                asyncio.ensure_future(_closed_client(session, plan, c, lane, headers, t1, recs))
                for c, lane in enumerate(plan["clients"])
            ]
            stop = t1
        else:
            tasks = [
                asyncio.ensure_future(_open_request(session, plan, req, t_load, recs))
                for req in plan["arrivals"]
            ]
            stop = t1 + plan["first_token_grace_s"]
        await asyncio.wait(tasks, timeout=max(0.0, stop - time.monotonic()))
        for t in [*tasks, beat]:
            t.cancel()
        await asyncio.gather(*tasks, beat, return_exceptions=True)
    return {"t_load": t_load, "t0": t0, "t1": t1, "requests": recs, "stalls": stalls}


def main() -> int:
    plan = json.loads(sys.stdin.readline())
    report = asyncio.run(_amain(plan))
    sys.stdout.write(json.dumps(report))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
