"""Decode-loop flight recorder (telemetry/flight.py + the scheduler's
per-round commit point) — ISSUE 9, extended by ISSUE 11's host-bubble
microscope (phase attribution, enqueue/readback split, sampling profiler).

The tier-1 guards this file pins:

1. the flight recorder is on by default, adds ZERO recompiles on the gen
   geometry, and its per-round append cost stays within budget;
2. the per-round stat commit is consolidated: stat_occupancy_sum and the
   flight frames agree exactly (the two-update-sites drift hazard is gone);
3. goodput / SLO attainment: TTFT breaches and deadline breaches are
   counted, auto-dump the ring into the span store as a force-retained
   trace, and tag the response;
4. GET /decode/flight and GET /decode/health serve live recorder data,
   and the profiler's ?duration_ms= auto-stop fires;
5. host-phase attribution: frames carry a per-phase gap split with
   sum(phase) <= gap and readback <= busy per family, phases + profiler
   ON still cost zero recompiles and stay within the overhead budget,
   and the sampling profiler is bounded-memory with valid folded output.
"""

import asyncio
import os
import re
import threading
import time

import numpy as np
import pytest

from seldon_core_tpu.models.decoder import init_decoder
from seldon_core_tpu.serving.decode_scheduler import DecodeScheduler
from seldon_core_tpu.telemetry import flight as flight_mod
from seldon_core_tpu.telemetry import profile as profile_mod
from seldon_core_tpu.telemetry.flight import FlightFrame, FlightRecorder, PhaseTimer
from seldon_core_tpu.telemetry.profile import StackProfiler

SEQ = 8
MAX_NEW = 8
VOCAB = 64

# generous CI budget for the <10 µs/round local target: shared runners
# jitter, but a recorder costing 50+ µs/round would be a real regression
OVERHEAD_BUDGET_US = 50.0


def _params():
    return init_decoder(seed=3, vocab=VOCAB, hidden=32, layers=1, ffn=64, max_len=32)


def _prompts(n, seed=1):
    return np.random.default_rng(seed).integers(0, VOCAB, (n, SEQ)).astype(np.int32)


def _frame(i, **kw):
    base = dict(
        seq=i, t_ns=1000 + i, mode="plain", active=2, prefilling=0, queued=0,
        admitted=0, retired=0, blocked="", tokens=2, accepted=0, proposed=0,
        spec_depth=0, busy_ns=(0, 1000, 0, 0, 0), gap_ns=500, kv_free=3,
        kv_live=2, kv_prefix=0, cow=0,
    )
    base.update(kw)
    return FlightFrame(**base)


# ------------------------------------------------------------- recorder unit


def test_ring_is_bounded_and_ordered():
    rec = FlightRecorder(n_slots=4, name="t", capacity=16, enabled=True)
    for i in range(40):
        rec.record(_frame(i))
    assert rec.rounds == 40
    frames = rec.snapshot()
    assert len(frames) == 16  # fixed memory regardless of rounds
    assert [f.seq for f in frames] == list(range(24, 40))  # oldest first
    assert [f.seq for f in rec.snapshot(4)] == [36, 37, 38, 39]


def test_the_default_ring_holds_a_benchmark_window(monkeypatch):
    """ISSUE 39: a 51-second window of 6.3 ms rounds fits the default ring
    (5,000 recorded rounds are all in ``snapshot()``), ``record`` stays O(1)
    (no walk of the ring: the running totals are the same adds at any
    capacity) and ENGINE_FLIGHT_FRAMES keeps its meaning."""
    from seldon_core_tpu.telemetry import flight as flight_mod

    monkeypatch.delenv("ENGINE_FLIGHT_FRAMES", raising=False)
    rec = FlightRecorder(n_slots=4, name="t", enabled=True)
    assert rec.capacity == flight_mod._DEFAULT_CAPACITY == 8192 >= 51.0 / 0.0063
    for i in range(5000):
        rec.record(_frame(i, chunk_rows=2, chunk_c=64, ingress_ns=7, ingress_requests=1, chunk_rows_held=i % 2,
                          chunk_rows_live=2, chunk_rows_kernel=2 * (i % 2)))
    frames = rec.snapshot()
    assert [f.seq for f in frames] == list(range(5000)) and rec.rounds == 5000
    assert frames[-1].chunk_c == 64 and frames[-1].to_dict()["ingress"] == [1, 0.0]
    # ISSUE 44: the slots a round left for a later one, in the frame's dict only where there were any
    assert "chunk_rows_held" in FlightFrame.__slots__ and "``chunk_rows_held``" in FlightFrame.__doc__
    assert (frames[-1].chunk_rows_held, frames[-1].to_dict()["chunk_rows_held"]) == (1, 1)
    assert frames[-2].chunk_rows_held == 0 and "chunk_rows_held" not in frames[-2].to_dict()
    assert _frame(0).chunk_rows_held == 0  # a round without a chunk dispatch
    # ISSUE 45: the prefilling rows whose attention ran in a chunk kernel, in the dict only where some did
    assert "chunk_rows_kernel" in FlightFrame.__slots__ and "``chunk_rows_kernel``" in FlightFrame.__doc__
    assert (frames[-1].chunk_rows_kernel, frames[-1].to_dict()["chunk_rows_kernel"]) == (2, 2)
    assert frames[-2].chunk_rows_kernel == 0 and "chunk_rows_kernel" not in frames[-2].to_dict()
    assert _frame(0).chunk_rows_kernel == 0 and "step" in FlightFrame.__doc__.split("``mla_run_pages``")[1][:200]
    assert rec.tokens_total == 2 * 5000 and len(rec._frames) == 8192
    monkeypatch.setenv("ENGINE_FLIGHT_FRAMES", "64")
    assert FlightRecorder(n_slots=4, name="small", enabled=True).capacity == 64


def test_aggregate_math_on_synthetic_frames():
    rec = FlightRecorder(n_slots=4, name="t", capacity=64, enabled=True)
    rec.record(_frame(0, busy_ns=(2000, 1000, 0, 0, 0), gap_ns=1000,
                      admitted=2, tokens=3, active=2, mode="chunk"))
    rec.record(_frame(1, busy_ns=(0, 3000, 0, 0, 0), gap_ns=3000,
                      retired=1, tokens=4, active=4, blocked="pages",
                      accepted=3, proposed=4, spec_depth=2, mode="chain"))
    agg = rec.aggregate()
    assert agg["rounds"] == 2
    assert agg["modes"] == {"chunk": 1, "chain": 1}
    # busy 6000ns, gap 4000ns -> bubble 4/10
    assert agg["bubble_fraction"] == pytest.approx(0.4, abs=1e-4)
    assert agg["busy_ms"] == {"chunk": 0.002, "step": 0.004}
    assert agg["occupancy_mean"] == pytest.approx((0.5 + 1.0) / 2)
    assert agg["tokens"] == 7
    assert agg["admitted"] == 2 and agg["retired"] == 1
    assert agg["blocked_rounds"] == {"pages": 1}
    assert agg["accept_rate"] == 0.75
    assert agg["spec_depth_mean"] == 2.0
    # the kill switch: record() becomes a no-op
    off = FlightRecorder(n_slots=4, name="off", capacity=16, enabled=False)
    off.record(_frame(0))
    assert off.rounds == 0 and off.snapshot() == []


def test_probe_rounds_excluded_from_accept_summaries():
    """PR 14: probe rounds (the controller's deliberate exploration —
    depth-1 recovery probes, full-shape width probes) are tagged in the
    frame, counted apart, and EXCLUDED from accept_rate in aggregate()
    and health() — probes accept badly by design and must not read as
    genuine degradation. Their own accept rides probe_accept_rate."""
    rec = FlightRecorder(n_slots=4, name="t", capacity=64, enabled=True)
    # 4 genuine spec rounds at accept 3/4, 2 probes at accept 0/1
    for i in range(4):
        rec.record(_frame(i, mode="tree", accepted=3, proposed=4, spec_depth=4,
                          spec_widths=(2, 2, 1, 1)))
    for i in range(4, 6):
        rec.record(_frame(i, mode="tree", accepted=0, proposed=1, spec_depth=1,
                          probe=True))
    agg = rec.aggregate()
    assert agg["accept_rate"] == 0.75  # 12/16, probes excluded
    assert agg["probe_rounds"] == 2
    assert agg["probe_accept_rate"] == 0.0
    health = rec.health()
    assert health["accept_rate"] == 0.75
    assert health["probe_rounds"] == 2
    # frames carry the tag + the tuned width mask for dump readability
    d_probe = rec.snapshot(1)[0].to_dict()
    assert d_probe["probe"] is True
    d_spec = rec.snapshot()[0].to_dict()
    assert d_spec["widths"] == [2, 2, 1, 1] and "probe" not in d_spec
    # spec_state (set by the scheduler's commit point) surfaces in health
    rec.spec_state = {"tree": "2,2,1,1", "widths": [2, 2, 1, 0],
                      "accept_ewma": 0.71, "depth": 3, "probes": 2}
    assert rec.health()["spec"]["widths"] == [2, 2, 1, 0]


def test_env_kill_switch(monkeypatch):
    monkeypatch.setenv(flight_mod.ENGINE_FLIGHT, "off")
    assert not flight_mod.flight_enabled()
    rec = FlightRecorder(n_slots=2, name="env-off")
    assert rec.enabled is False
    monkeypatch.setenv(flight_mod.ENGINE_FLIGHT, "on")
    assert FlightRecorder(n_slots=2, name="env-on").enabled is True


def test_recorder_overhead_within_budget():
    """Tier-1 guard (ii of the overhead contract): the measured per-round
    append cost stays within the CI budget (local target <10 µs;
    PARITY.md "Instrumentation overhead")."""
    us = FlightRecorder.measure_overhead(2000)
    assert us < OVERHEAD_BUDGET_US, f"flight append {us} µs/round"


# -------------------------------------------------- scheduler e2e + guards


def _run_requests(s, n=6, **submit_kw):
    rng = np.random.default_rng(0)

    async def go():
        outs = await asyncio.gather(
            *(s.submit(rng.integers(0, VOCAB, SEQ).astype(np.int32), **submit_kw)
              for _ in range(n))
        )
        await s.close()
        return outs

    return asyncio.run(go())


def test_scheduler_records_frames_zero_recompiles():
    """Tier-1 guard (i): the recorder is on by default, frames commit per
    round with the busy/gap split populated, and the instrumentation adds
    ZERO recompiles on the gen geometry."""
    s = DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=4)
    s.warmup()
    assert s.flight.enabled
    _run_requests(s, n=6)
    assert s.recompiles_since_warmup() == 0
    assert s.flight.rounds > 0
    frames = s.flight.snapshot()
    # every frame carries the pool state and the busy split; step rounds
    # attribute device time to the step family
    assert any(f.busy_ns[flight_mod.F_STEP] > 0 for f in frames)
    assert all(len(f.busy_ns) == len(flight_mod.FAMILIES) for f in frames)
    agg = s.flight.aggregate()
    assert agg["tokens"] == s.stat_tokens
    assert agg["admitted"] == 6 and agg["retired"] == 6
    # 6 requests through 4 slots: someone queued behind full slots
    assert agg["blocked_rounds"].get("slots", 0) > 0


@pytest.mark.parametrize("kw, want", [
    ({}, None),
    ({"sample_rows": 3}, [3, 0]),
    ({"sample_rows": 5, "sample_topk_rows": 2}, [5, 2]),
], ids=["greedy_round", "draws", "draws_with_top_k"])
def test_frame_sample_rows_round_trip(kw, want):
    """The sampler's two counts default to 0 / 0, read back as given, and
    show in the dump only for a round that drew."""
    f = _frame(0, **kw)
    assert (f.sample_rows, f.sample_topk_rows) == (kw.get("sample_rows", 0), kw.get("sample_topk_rows", 0))
    assert f.to_dict().get("sample_rows") == want
    rec = FlightRecorder(n_slots=4, name="t", capacity=4, enabled=True)
    rec.record(f)
    assert rec.snapshot()[0].to_dict().get("sample_rows") == want


@pytest.mark.parametrize("submit_kw, rows, topk_rows", [
    ({}, False, False),
    ({"temperature": 0.0, "top_k": 5}, False, False),  # a greedy row's top_k asks for nothing
    ({"temperature": 0.8}, True, False),
    ({"temperature": 0.8, "top_k": 5}, True, True),
], ids=["greedy", "greedy_with_top_k", "temperature", "temperature_and_top_k"])
def test_scheduler_frames_count_the_rows_that_sample(submit_kw, rows, topk_rows):
    """What the frames say of a round's dispatches is what their ``temps`` /
    ``topks`` asked: a generating or prefilling slot that samples is a row,
    in the chunk dispatch and in the step alike."""
    s = DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=4)
    s.warmup()
    _run_requests(s, n=6, **submit_kw)
    frames = s.flight.snapshot()
    assert s.recompiles_since_warmup() == 0
    assert any(f.sample_rows for f in frames) == rows
    assert any(f.sample_topk_rows for f in frames) == topk_rows
    for f in frames:
        # at most one row a slot in the chunk dispatch and one in the step
        assert 0 <= f.sample_topk_rows <= f.sample_rows <= 2 * s.n_slots
        if rows and f.busy_ns[flight_mod.F_STEP]:
            assert f.sample_rows > 0  # every generating slot of the step samples
            assert f.sample_topk_rows == (f.sample_rows if topk_rows else 0)


def test_commit_point_consolidates_occupancy():
    """Satellite: stat_occupancy_sum and the flight frames are written at
    ONE commit point — summing the frames' step-round occupancy reproduces
    the scheduler counter exactly, spec and plain paths alike."""
    draft = init_decoder(seed=3, vocab=VOCAB, hidden=32, layers=1, ffn=64,
                         max_len=32, resid_scale=0.1)
    for kw in ({}, {"draft_params": draft, "spec_k": 3}):
        s = DecodeScheduler(
            _params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2, **kw
        )
        s.warmup()
        _run_requests(s, n=4)
        step_frames = [
            f for f in s.flight.snapshot() if f.mode in ("plain", "chain", "tree")
        ]
        assert len(step_frames) == s.stat_steps
        assert sum(f.active / s.n_slots for f in step_frames) == pytest.approx(
            s.stat_occupancy_sum
        )
        if kw:
            assert any(f.mode == "chain" for f in step_frames)
            assert sum(f.accepted for f in step_frames) == s.stat_spec_accepted
            assert sum(f.proposed for f in step_frames) == s.stat_spec_proposed


def test_slo_breach_counts_dumps_and_tags():
    """An impossible TTFT SLO: every first token breaches — attainment
    hits 0, the ring auto-dumps into the span store as a force-retained
    trace, and execute_message tags the response rows breached."""
    import seldon_core_tpu.telemetry as telemetry
    from seldon_core_tpu.core.message import Meta, SeldonMessage

    telemetry.configure(telemetry.Tracer(store=telemetry.SpanStore()))
    s = DecodeScheduler(
        _params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2,
        slo_ttft_ms=0.0001, slo_itl_ms=10000.0,
    )
    s.warmup()
    s.flight.dump_interval_s = 0.0  # every breach dumps (no rate limit)

    async def go():
        # seed the ring with a completed request so later breach dumps
        # have frames to carry (a fresh scheduler's very first breach
        # fires before any round has committed)
        await s.submit(_prompts(1, seed=9)[0])
        msg = SeldonMessage.from_array(_prompts(2), meta=Meta(puid="p1"))
        out = await s.execute_message(msg)
        await s.close()
        return out

    out = asyncio.run(go())
    fl = s.flight
    assert fl.ttft_total == 3 and fl.ttft_ok == 0
    assert fl.itl_total > 0 and fl.itl_ok == fl.itl_total
    assert fl.goodput()["ttft_attainment"] == 0.0
    # breaches flip the per-row verdict the access log reads
    assert out.meta.tags["slo"] == ["breached", "breached"]
    assert fl.health()["status"] == "breaching"
    # the auto-dumps are retained (forced flag -> always-keep pool) and
    # the post-seed ones carry the breach-adjacent frames as events
    assert fl.dumps >= 2
    store = telemetry.get_tracer().store
    recs = [r for r in store.list() if r.puid.startswith("flight:")]
    assert recs, "flight dump not retained"
    roots = [r.root() for r in recs]
    assert all(rt.name == "decode.flight" for rt in roots)
    assert any(rt.events and rt.events[0].name == "frame" for rt in roots)
    assert all("forced" in r.flags for r in recs)


def test_goodput_counts_deadline_breaches():
    """Tokens of a request whose deadline budget expired count as breached
    goodput (the deadline is captured from the DEADLINE contextvar at
    submit, the same carrier the service stamps)."""
    from seldon_core_tpu.engine.resilience import DEADLINE, Deadline

    s = DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2)
    s.warmup()

    async def go():
        token = DEADLINE.set(Deadline(0.0001))  # already (about to be) gone
        try:
            out = await s.submit(_prompts(1)[0])
        finally:
            DEADLINE.reset(token)
        await s.close()
        return out

    asyncio.run(go())
    fl = s.flight
    assert fl.deadline_total == 1 and fl.deadline_met == 0
    assert fl.goodput_breached_tokens == MAX_NEW
    assert fl.goodput_met_tokens == 0
    assert fl.goodput()["goodput_fraction"] == 0.0


def test_slo_metrics_and_exemplar_wiring():
    """The registry's goodput/SLO/round metrics: counters land with the
    right labels and a breach inc carries the flight-dump exemplar in the
    OpenMetrics exposition."""
    from seldon_core_tpu.metrics.registry import HAVE_PROMETHEUS, get_metrics

    if not HAVE_PROMETHEUS:
        pytest.skip("prometheus_client not installed")
    m = get_metrics()
    m.decode_round("d", 0.002, 0.001)
    m.decode_bubble("d", 0.33)
    m.decode_goodput("d", 7, True)
    m.decode_goodput("d", 3, False)
    m.decode_slo("d", "ttft", True)
    m.decode_slo("d", "ttft", False, trace_id="ab" * 16)
    text = m.export().decode()
    assert 'seldon_tpu_decode_goodput_tokens_total{deployment_name="d",outcome="met"} 7.0' in text
    assert 'outcome="breached"} 3.0' in text
    assert 'seldon_tpu_decode_slo_attainment_total{deployment_name="d",kind="ttft",outcome="breach"} 1.0' in text
    assert 'seldon_tpu_decode_bubble_fraction{deployment_name="d"} 0.33' in text
    assert "seldon_tpu_decode_round_host_gap_seconds" in text
    om = m.export_openmetrics().decode()
    if "# EOF" in om and "openmetrics" in str(type(om)).lower() or True:
        # exemplar only exists in the OpenMetrics exposition; older
        # clients fall back to classic text (no exemplar — tolerated)
        assert ("trace_id" in om) or (om == text)


# ------------------------------------------------- operator API endpoints


async def test_decode_flight_and_health_endpoints():
    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.operator.api import add_operator_routes
    from seldon_core_tpu.operator.reconciler import DeploymentManager

    rec = FlightRecorder(n_slots=4, name="flight-ep", capacity=32, enabled=True)
    flight_mod.register(rec)
    for i in range(5):
        rec.record(_frame(i, tokens=3, admitted=(1 if i == 0 else 0),
                          rdb_ns=(0, 600, 0, 0, 0), rdy_ns=(0, 300, 0, 0, 0)))
    rec.note_goodput(12, True)
    rec.note_ttft(True)

    app = web.Application()
    add_operator_routes(app, DeploymentManager())
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        r = await client.get("/decode/flight?name=flight-ep&n=3")
        assert r.status == 200
        body = await r.json()
        ep = body["recorders"]["flight-ep"]
        assert len(ep["frames"]) == 3
        assert ep["aggregate"]["rounds"] == 5
        assert ep["aggregate"]["tokens"] == 15
        assert ep["frames"][-1]["busy_us"]["step"] == 1.0
        # the return leg rides the same body: a frame's, the window's, its share of the wall
        assert ep["frames"][-1]["rdy_us"] == {"step": 0.3} and ep["aggregate"]["return_ms"] == {"step": 0.002}
        assert ep["aggregate"]["return_of_wall"] == 0.2 and ep["aggregate"]["gc2_count"] == 0
        r = await client.get("/decode/health")
        assert r.status == 200
        health = (await r.json())["flight-ep"]
        assert health["status"] == "ok"
        assert health["goodput"]["tokens_met"] == 12
        assert health["goodput"]["ttft_attainment"] == 1.0
    finally:
        await client.close()


async def test_profiler_duration_ms_auto_stops(tmp_path):
    """Satellite: ?duration_ms= arms a background auto-stop (an operator
    cannot leave a device trace running), and both responses resolve the
    output dir."""
    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.operator.api import add_operator_routes
    from seldon_core_tpu.operator.reconciler import DeploymentManager

    app = web.Application()
    add_operator_routes(app, DeploymentManager())
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        out_dir = str(tmp_path / "prof")
        r = await client.post(f"/profiler/start?dir={out_dir}&duration_ms=150")
        body = await r.json()
        assert r.status == 200
        assert body["tracing"] == out_dir
        assert body["dir"] == os.path.abspath(out_dir)
        assert body["auto_stop_ms"] == 150
        # a second start while tracing is still a clean 409
        r = await client.post("/profiler/start")
        assert r.status == 409
        # ... until the timer fires; then the profiler is free again
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            await asyncio.sleep(0.1)
            r = await client.post(f"/profiler/start?dir={out_dir}2")
            if r.status == 200:
                break
        else:
            pytest.fail("auto-stop never released the profiler")
        # manual stop still works and resolves the dir; bad duration is 400
        r = await client.post("/profiler/stop")
        assert r.status == 200
        assert (await r.json())["dir"] == os.path.abspath(out_dir + "2")
        r = await client.post("/profiler/start?duration_ms=notanumber")
        assert r.status == 400
    finally:
        await client.close()


# ------------------------------------------- phase timer + readback split


def test_phase_timer_nesting_attributes_to_innermost():
    t = PhaseTimer(enabled=True)
    with t.phase(flight_mod.P_ACCEPT_WALK):
        time.sleep(0.002)
        with t.phase(flight_mod.P_EMIT_SLO):
            time.sleep(0.002)
        time.sleep(0.002)
    assert t.ns[flight_mod.P_EMIT_SLO] >= 1_000_000
    assert t.ns[flight_mod.P_ACCEPT_WALK] >= 2_000_000
    # innermost wins: the outer phase does NOT double-count the inner span
    total = sum(t.ns)
    assert t.ns[flight_mod.P_ACCEPT_WALK] + t.ns[flight_mod.P_EMIT_SLO] == total
    t.reset()
    assert sum(t.ns) == 0 and t._stack == []
    # disabled timer: shared no-op handles, arrays stay zero
    off = PhaseTimer(enabled=False)
    with off.phase(flight_mod.P_ADMIT):
        pass
    assert sum(off.ns) == 0


def test_phase_timer_commit_freezes_round():
    t = PhaseTimer(enabled=True)
    with t.phase(flight_mod.P_ADMIT):
        pass
    t0 = time.perf_counter_ns()
    frozen = t.commit(flight_mod.P_COMMIT, t0)
    assert len(frozen) == flight_mod.N_PHASES
    assert frozen[flight_mod.P_COMMIT] >= 0
    assert isinstance(frozen, tuple)


def test_phase_timer_overlap_mode_keeps_phase_sums_clean():
    """Overlap mode (the pipelined loop's window): phase segments timed
    between begin_overlap/end_overlap accrue to the single overlap_ns
    counter, NOT the per-phase array — overlapped host work sits inside
    the round's device-busy window, so booking it into ns would break
    sum(phase) <= gap."""
    t = PhaseTimer(enabled=True)
    with t.phase(flight_mod.P_SAMPLING):
        time.sleep(0.001)
    t.begin_overlap()
    with t.phase(flight_mod.P_ADMIT):
        time.sleep(0.002)
        with t.phase(flight_mod.P_ALLOC):
            time.sleep(0.001)
    t.end_overlap()
    with t.phase(flight_mod.P_COMMIT):
        time.sleep(0.001)
    # the overlapped spans landed in overlap_ns only
    assert t.overlap_ns >= 2_000_000
    assert t.ns[flight_mod.P_ADMIT] == 0
    assert t.ns[flight_mod.P_ALLOC] == 0
    # normal-mode spans on either side still attribute per phase
    assert t.ns[flight_mod.P_SAMPLING] >= 500_000
    assert t.ns[flight_mod.P_COMMIT] >= 500_000
    t.reset()
    assert t.overlap_ns == 0 and not t._overlap


def test_overlap_accounting_in_frames_aggregate_and_health():
    """The overlap columns (ISSUE 13): per-frame overlap_ns flows to
    to_dict/aggregate/health, overlap_of_gap + bubble_residual split the
    would-be serial gap, and a serial recorder reads 0.0/1.0-free (no
    overlap keys invented)."""
    rec = FlightRecorder(n_slots=4, name="ov", capacity=64, enabled=True)
    rec.record(_frame(0, busy_ns=(0, 4000, 0, 0, 0), gap_ns=1000, overlap_ns=3000))
    rec.record(_frame(1, busy_ns=(0, 4000, 0, 0, 0), gap_ns=2000, overlap_ns=0))
    agg = rec.aggregate()
    # gap 3000, overlap 3000: half the would-be serial gap was hidden
    assert agg["overlap_of_gap"] == pytest.approx(0.5, abs=1e-4)
    assert agg["bubble_residual"] == pytest.approx(0.5, abs=1e-4)
    assert agg["overlap_ms"] == pytest.approx(0.003, abs=1e-6)
    # bubble_fraction counts only the still-exposed gap: 3000/11000
    assert agg["bubble_fraction"] == pytest.approx(3000 / 11000, abs=1e-4)
    assert rec.health()["overlap_of_gap"] == pytest.approx(0.5, abs=1e-4)
    d = rec.snapshot(2)[0].to_dict()
    assert d["overlap_us"] == 3.0
    assert "overlap_us" not in rec.snapshot(2)[1].to_dict()
    # a recorder that never saw overlap (the serial loop): 0.0, residual 1.0
    ser = FlightRecorder(n_slots=4, name="ser", capacity=16, enabled=True)
    ser.record(_frame(0, gap_ns=1000))
    assert ser.aggregate()["overlap_of_gap"] == 0.0
    assert ser.aggregate()["bubble_residual"] == 1.0
    assert ser.health()["overlap_of_gap"] == 0.0


def test_pipelined_scheduler_frames_carry_overlap():
    """Scheduler e2e with the pipeline on (the default): step frames carry
    nonzero overlap_ns, sum(phase) <= gap survives, and the aggregate's
    overlap_of_gap is positive — the soak/profile-smoke gate's signal."""
    s = DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=4)
    s.warmup()
    assert s._pipeline_on()
    _run_requests(s, n=6)
    assert s.recompiles_since_warmup() == 0
    frames = s.flight.snapshot()
    assert any(f.overlap_ns > 0 for f in frames)
    for f in frames:
        assert sum(f.phase_ns) <= f.gap_ns + 50_000, (f.seq, f.phase_ns, f.gap_ns)
    agg = s.flight.aggregate()
    assert agg["overlap_of_gap"] > 0.0
    assert s.stat_pipelined_rounds > 0


def test_decode_pipeline_env_kill_switch(monkeypatch):
    monkeypatch.setenv(flight_mod.ENGINE_DECODE_PIPELINE, "off")
    assert not flight_mod.decode_pipeline_enabled()
    s = DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2)
    assert not s.pipeline_enabled and not s._pipeline_on()
    monkeypatch.setenv(flight_mod.ENGINE_DECODE_PIPELINE, "on")
    assert flight_mod.decode_pipeline_enabled()


def test_overhead_budget_with_phases_and_profiler_on():
    """Tier-1 guard: the frame append AND the phase timer stay within the
    CI overhead budget with the sampling profiler running hot against
    this very thread (the worst case the always-on path can present)."""
    prof = StackProfiler(hz=500, max_entries=64, enabled=True)
    prof.watch(threading.get_ident())
    assert prof.start()
    try:
        frame_us = FlightRecorder.measure_overhead(2000)
        phase_us = PhaseTimer.measure_overhead(2000)
    finally:
        prof.stop()
    assert frame_us < OVERHEAD_BUDGET_US, f"frame append {frame_us} µs/round"
    assert phase_us < OVERHEAD_BUDGET_US, f"phase timer {phase_us} µs/round"


def test_frames_carry_phase_and_readback_split():
    """Tier-1 guard (ISSUE 11): plain-path frames decompose the gap into
    phases (sum(phase) <= gap), every family's readback share is within
    its busy wall (enqueue + readback == busy by construction), and the
    aggregate/health read-outs carry the new keys — all at zero
    recompiles."""
    s = DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=4)
    s.warmup()
    _run_requests(s, n=6)
    assert s.recompiles_since_warmup() == 0
    frames = s.flight.snapshot()
    assert frames
    for f in frames:
        assert len(f.phase_ns) == flight_mod.N_PHASES
        assert len(f.rdb_ns) == len(flight_mod.FAMILIES)
        # phases are host gap: never more than the frame's gap (small
        # tolerance for timer-boundary jitter)
        assert sum(f.phase_ns) <= f.gap_ns + 50_000, (f.seq, f.phase_ns, f.gap_ns)
        for i, rdb in enumerate(f.rdb_ns):
            assert 0 <= f.rdy_ns[i] <= rdb <= f.busy_ns[i]
    step_frames = [f for f in frames if f.mode == "plain"]
    assert any(sum(f.phase_ns) > 0 for f in step_frames)
    # the step family actually reads tokens back -> nonzero readback split
    assert any(f.rdb_ns[flight_mod.F_STEP] > 0 for f in step_frames)
    d = step_frames[-1].to_dict()
    assert set(d.get("phase_us", {})) <= set(flight_mod.PHASES)
    if "rdb_us" in d:
        assert set(d["rdb_us"]) <= set(flight_mod.FAMILIES)
        assert set(d["enq_us"]) <= set(flight_mod.FAMILIES)
    agg = s.flight.aggregate()
    assert {"admit", "alloc", "sampling", "emit_slo", "commit"} <= set(
        agg["phase_ms"]
    )
    assert 0.0 < agg["phase_of_gap"] <= 1.05
    assert set(agg["readback_ms"]) <= set(flight_mod.FAMILIES)
    assert set(agg["enqueue_ms"]) <= set(flight_mod.FAMILIES)
    health = s.flight.health()
    assert health["top_gap_phase"] in flight_mod.PHASES
    assert 0.0 < health["phase_of_gap"] <= 1.05


def test_spec_frames_attribute_accept_walk_and_verify_readback():
    """Speculative rounds attribute their emission walk to accept_walk and
    carry the verify family's blocked readback (the PR 9 caveat — 'draft
    is free, verify absorbs the pair' — now split and visible)."""
    draft = init_decoder(seed=3, vocab=VOCAB, hidden=32, layers=1, ffn=64,
                         max_len=32, resid_scale=0.1)
    s = DecodeScheduler(
        _params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2,
        draft_params=draft, spec_k=3,
    )
    s.warmup()
    _run_requests(s, n=4)
    assert s.recompiles_since_warmup() == 0
    chain = [f for f in s.flight.snapshot() if f.mode == "chain"]
    assert chain
    assert any(f.phase_ns[flight_mod.P_ACCEPT_WALK] > 0 for f in chain)
    assert any(f.rdb_ns[flight_mod.F_VERIFY] > 0 for f in chain)
    # the draft column is enqueue-only on the async pair (its wait lands
    # in the verify readback) — never negative, never above busy
    for f in chain:
        assert f.rdb_ns[flight_mod.F_DRAFT] == f.rdy_ns[flight_mod.F_DRAFT] == 0
        assert 0 < f.rdy_ns[flight_mod.F_VERIFY] <= f.rdb_ns[flight_mod.F_VERIFY] <= f.busy_ns[flight_mod.F_VERIFY]


@pytest.mark.parametrize("kw, want", [
    (dict(), None),  # nothing read back: no split at all, as before
    (dict(rdb_ns=(0, 600, 0, 0, 0)), {}),  # a read with no mark (a recorder fed by an older caller): rdb alone
    (dict(rdb_ns=(0, 600, 0, 0, 0), rdy_ns=(0, 250, 0, 0, 0)), {"step": 0.2}),
    (dict(busy_ns=(900, 1000, 0, 0, 0), rdb_ns=(700, 600, 0, 0, 0), rdy_ns=(300, 250, 0, 0, 0)),
     {"chunk": 0.3, "step": 0.2}),
])
def test_frame_return_leg_round_trip(kw, want):
    """ISSUE 53: ``rdy_ns`` is a frame slot like ``rdb_ns`` (zeros by
    default) and the dump carries it as ``rdy_us`` beside ``rdb_us``, under
    the same condition."""
    f = _frame(0, **kw)
    assert len(f.rdy_ns) == len(flight_mod.FAMILIES) and "rdy_ns" in FlightFrame.__slots__
    d = f.to_dict()
    assert ("rdb_us" in d) == ("rdy_us" in d) == (want is not None)
    assert d.get("rdy_us") == want


def test_aggregate_return_leg_sums_the_frames():
    """``return_ms`` per family is the frames' ``rdy_ns`` summed, beside
    ``readback_ms``; ``return_of_wall`` its share of the rounds' wall, the
    device-idle time inside the dispatch wall that ``bubble_fraction`` (gap
    over wall) cannot hold."""
    rec = FlightRecorder(n_slots=4, name="t", capacity=64, enabled=True)
    rec.record(_frame(0, busy_ns=(4_000_000, 2_000_000, 0, 0, 0), gap_ns=1_000_000,
                      rdb_ns=(3_000_000, 1_500_000, 0, 0, 0), rdy_ns=(1_000_000, 500_000, 0, 0, 0)))
    rec.record(_frame(1, busy_ns=(0, 2_000_000, 0, 0, 0), gap_ns=1_000_000,
                      rdb_ns=(0, 1_000_000, 0, 0, 0), rdy_ns=(0, 500_000, 0, 0, 0)))
    rec.record(_frame(2, busy_ns=(0, 0, 0, 0, 1_000_000), gap_ns=1_000_000))  # a copy round reads nothing back
    agg = rec.aggregate()
    assert agg["readback_ms"] == {"chunk": 3.0, "step": 2.5}
    assert agg["return_ms"] == {"chunk": 1.0, "step": 1.0}
    assert agg["return_of_wall"] == pytest.approx(2.0 / 12.0, abs=1e-4)
    assert agg["bubble_fraction"] == pytest.approx(3.0 / 12.0, abs=1e-4)
    assert rec.aggregate(1)["return_ms"] == {} and rec.aggregate(1)["return_of_wall"] == 0.0
    assert FlightRecorder(n_slots=1, name="e", capacity=16, enabled=True).aggregate()["return_of_wall"] == 0.0


def test_oldest_generation_collections_are_named_and_counted(monkeypatch):
    """ISSUE 53: ``Gc2Watch`` opens and closes ``decode.gc2`` round a
    collection of generation 2 alone and adds to the recorder's two
    counters; a younger generation's returns at once; removed, it counts no
    more."""
    import gc

    names = []

    class Ann:
        def __exit__(self, *exc):
            names.append("end")

    monkeypatch.setattr(flight_mod, "annotate", lambda name, **kw: names.append(name) or Ann())
    rec = FlightRecorder(n_slots=1, name="gc", capacity=16, enabled=True)
    watch = flight_mod.Gc2Watch(rec)
    before = len(gc.callbacks)
    watch.install()
    try:
        gc.collect(0)
        gc.collect(1)
        assert rec.gc2_count == 0 and names == []
        gc.collect()
        gc.collect(2)
        assert rec.gc2_count == 2 and rec.gc2_ns_total > 0
        assert names == [flight_mod.ANN_GC2, "end"] * 2 and flight_mod.ANN_GC2 == "decode.gc2"
    finally:
        watch.remove()
    watch.remove()  # twice is once
    assert len(gc.callbacks) == before
    gc.collect()
    agg = rec.aggregate()
    assert agg["gc2_count"] == 2 and agg["gc2_ms_total"] == round(rec.gc2_ns_total / 1e6, 3)


def test_the_scheduler_watches_collections_while_its_loop_runs():
    """The hook lives as long as the decode loop does: installed where the
    loop starts, gone where it stops; a full collection between two requests
    is on the recorder."""
    import gc

    s = DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2)
    s.warmup()
    rows = _prompts(2)
    watching = []

    async def go():
        await s.submit(rows[0])
        watching.extend(cb for cb in gc.callbacks if isinstance(cb, flight_mod.Gc2Watch) and cb.rec is s.flight)
        gc.collect()
        await s.submit(rows[1])
        await s.close()

    asyncio.run(go())
    assert len(watching) == 1 and watching[0] not in gc.callbacks
    assert s.flight.gc2_count >= 1 and s.flight.aggregate()["gc2_count"] == s.flight.gc2_count


# ------------------------------------------------------ sampling profiler


def test_profiler_captures_stacks_with_folded_schema():
    prof = StackProfiler(hz=200, max_entries=64, enabled=True)
    stop = threading.Event()

    def busy():
        while not stop.is_set():
            sum(range(500))

    t = threading.Thread(target=busy, daemon=True)
    t.start()
    prof.watch(t.ident)
    assert prof.start()
    try:
        deadline = time.monotonic() + 5.0
        while prof.samples < 3 and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        prof.stop()
        stop.set()
    assert prof.samples >= 3, "sampler never caught the busy thread"
    folded = prof.folded()
    assert folded
    # flamegraph folded format: "frame;frame;frame count", leaf last
    assert all(re.fullmatch(r"\S.*? \d+", line) for line in folded)
    assert any("busy" in line.split(" ")[0].rsplit(";", 1)[-1] for line in folded)
    rep = prof.report(n=5)
    for key in ("enabled", "running", "hz", "samples", "missed",
                "truncated_samples", "table_entries", "table_cap", "top",
                "folded"):
        assert key in rep, key
    assert rep["top"] and rep["top"][0]["self_samples"] >= 1
    assert 0.0 < rep["top"][0]["fraction"] <= 1.0


def test_profiler_table_is_bounded():
    prof = StackProfiler(hz=10, max_entries=16, enabled=True)
    for i in range(100):
        prof._ingest(f"a;b;frame{i}")
    assert prof.samples == 100
    assert len(prof._table) == 16  # fixed memory regardless of stack variety
    assert prof.truncated == 100 - 16
    assert prof.report(n=3)["truncated_samples"] == 84
    # known stacks keep counting after the cap
    prof._ingest("a;b;frame0")
    assert prof._table["a;b;frame0"] == 2 and prof.truncated == 84


def test_profiler_start_stop_and_kill_switch(monkeypatch):
    prof = StackProfiler(hz=100, enabled=True)
    prof.watch(threading.get_ident())
    assert prof.start()
    assert prof.start()  # idempotent
    assert prof.running
    prof.stop()
    assert not prof.running
    # env kill switch: start() is a refusal, not an error
    monkeypatch.setenv(profile_mod.ENGINE_DECODE_PROFILE, "off")
    off = StackProfiler()
    assert off.enabled is False
    assert off.start() is False and not off.running
    monkeypatch.delenv(profile_mod.ENGINE_DECODE_PROFILE)
    assert StackProfiler().enabled is True
    # rate clamp
    p = StackProfiler(hz=50, enabled=True)
    assert p.set_hz(0.01) == 0.1
    assert p.set_hz(10_000) == 1000.0


def test_scheduler_registers_decode_thread_with_profiler():
    """The decode loop registers its thread with the process profiler as
    the loop task starts (always-on without operator action)."""
    prof = profile_mod.get_profiler()
    s = DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2)
    s.warmup()
    _run_requests(s, n=2)
    assert prof._target_ident is not None
    assert prof.enabled is False or prof.running


# ------------------------------------------- endpoint query validation


async def test_flight_and_profile_query_validation():
    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.operator.api import add_operator_routes
    from seldon_core_tpu.operator.reconciler import DeploymentManager

    rec = FlightRecorder(n_slots=2, name="qv", capacity=16, enabled=True)
    flight_mod.register(rec)
    rec.record(_frame(0))
    app = web.Application()
    add_operator_routes(app, DeploymentManager())
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        # malformed ?n/?window/?hz: 400 with a parseable error body, not a
        # 500 and not a silent default
        for url, param in (
            ("/decode/flight?n=0", "n"),
            ("/decode/flight?n=-3", "n"),
            ("/decode/flight?n=abc", "n"),
            ("/decode/flight?window=0", "window"),
            ("/decode/flight?window=1.5", "window"),
            ("/decode/profile?n=zero", "n"),
            ("/decode/profile?hz=0", "hz"),
            ("/decode/profile?hz=-5", "hz"),
        ):
            r = await client.get(url)
            assert r.status == 400, url
            body = await r.json()
            assert body["param"] == param and "error" in body and "got" in body
        # valid queries still serve
        r = await client.get("/decode/flight?name=qv&n=1&window=1")
        assert r.status == 200
        assert len((await r.json())["recorders"]["qv"]["frames"]) == 1
        r = await client.get("/decode/profile?n=5")
        assert r.status == 200
        body = await r.json()
        for key in ("enabled", "running", "hz", "samples", "top", "folded"):
            assert key in body, key
        # ?hz= retunes the live sampler (clamped, validated); the GET's
        # reach is capped at 200 Hz so a cached link cannot turn the
        # always-on sampler hot
        r = await client.get("/decode/profile?hz=42")
        assert r.status == 200
        assert (await r.json())["hz"] == 42.0
        r = await client.get("/decode/profile?hz=10000")
        assert r.status == 200
        assert (await r.json())["hz"] == 200.0
    finally:
        await client.close()


@pytest.mark.parametrize("kw, want", [
    ({}, None),
    ({"kv_win_live": 12}, [12, 0, 0]),
    ({"kv_win_live": 98, "kv_win_released": 3, "kv_win_written": 4}, [98, 3, 4]),
    ({"kv_win_released": 2}, [0, 2, 0]),
], ids=["one_page_kind", "live_only", "a_round_that_wrote_and_gave_back", "the_last_slot_retiring"])
def test_frame_window_kind_counts_round_trip(kw, want):
    """The window page kind's three counts default to 0, read back as given,
    and show in the dump only where one is set (a pool of one kind never)."""
    f = _frame(0, **kw)
    assert (f.kv_win_live, f.kv_win_released, f.kv_win_written) == tuple(
        kw.get(k, 0) for k in ("kv_win_live", "kv_win_released", "kv_win_written"))
    assert f.to_dict().get("kv_win") == want and f.to_dict()["kv"] == [3, 2, 0]  # kv stays the full kind's
    rec = FlightRecorder(n_slots=4, name="t", capacity=4, enabled=True)
    rec.record(f)
    assert rec.snapshot()[0].to_dict().get("kv_win") == want


@pytest.mark.parametrize("kw, want", [
    ({"ssm_rows": 64}, {"ssm": [64, 0, 0]}),
    ({"ssm_rows": 64, "moe_rows": 64, "moe_experts_hit": 170, "moe_load_max": 90, "moe_local_picks": 530},
     {"ssm": [64, 0, 0], "moe": [64, 170, 90], "moe_local_picks": 530}),
    ({"ssm_rows": 4, "state_restores": 2, "moe_rows": 1088, "moe_experts_hit": 176, "moe_load_max": 700,
      "moe_local_picks": 9000, "moe_grouped_calls": 11, "moe_compact_calls": 11},
     {"ssm": [4, 2, 0], "moe": [1088, 176, 700], "moe_local_picks": 9000, "moe_compact": [11, 11]}),
    ({"mla_ctx_rows": 500, "moe_rows": 64, "moe_local_picks": 30}, {"mla": [500, 30], "moe": [64, 0, 0]}),
], ids=["granite", "single_sublayers_step", "single_sublayers_wide_chunk", "latent_family"])
def test_frame_carries_state_rows_and_held_expert_counts_together(kw, want):
    """The hybrid family's frames with expert layers (PR 51) carry the held
    experts' counts beside ``ssm_rows``: both groups read back as given and
    show in one dump, the picks that landed here under their own key (the
    latent family keeps them beside its context rows), and a configuration
    without experts dumps what it dumped."""
    f = _frame(0, **kw)
    assert all(getattr(f, k) == v for k, v in kw.items())
    keys = ("ssm", "moe", "moe_local_picks", "moe_compact", "mla")
    assert {k: f.to_dict()[k] for k in keys if k in f.to_dict()} == want
    rec = FlightRecorder(n_slots=4, name="t", capacity=4, enabled=True)
    rec.record(f)
    got = rec.snapshot()[0].to_dict()
    assert {k: got[k] for k in keys if k in got} == want


@pytest.mark.parametrize("passes,kernel,shown", [(0, 0, None), (36, 0, [0, 36]), (36, 36, [36, 36])],
                         ids=["no_delta_rule_layers", "plain_forms", "kernels"])
def test_a_frame_carries_the_delta_rule_passes_and_those_in_a_kernel(passes, kernel, shown):
    """ISSUE 58: the delta-rule layer passes of the round's dispatches and those that ran in
    ops/gated_delta.py's kernels; in the frame's dict only for a configuration with such layers."""
    assert {"gdn_passes", "gdn_kernel_passes"} <= set(FlightFrame.__slots__) and "``gdn_kernel_passes``" in FlightFrame.__doc__
    f = _frame(0, gdn_passes=passes, gdn_kernel_passes=kernel)
    assert (f.gdn_passes, f.gdn_kernel_passes, f.to_dict().get("gdn_passes")) == (passes, kernel, shown)
