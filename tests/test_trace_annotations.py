"""The decode round names its own time (ISSUE 26): the scheduler writes
``jax.profiler`` trace annotations for every host state of a round and
``jax.named_scope`` names into the paged programs, itself and always.

What this file pins:

1. every ``flight.PHASES`` / ``flight.FAMILIES`` name is emitted as an
   annotation by a scheduler round, through the ONE emit helper
   (``flight.annotate``) — serial, pipelined, chunked, speculative and
   prefix/CoW rounds alike (the pipelined step is where a patched-on
   annotation used to be missed);
2. ``decode.round``'s ``round`` stat is the committed frame's index;
3. the two time-to-first-token counters of the FlightFrame sum to the
   per-request stamps;
4. the compiled ``_fused_step`` / ``_fused_chunk`` carry every scope name;
5. a real CPU profiler session records the annotations with their stats;
6. with no session a round's annotations construct nothing: a check and
   the shared no-op;
7. (ISSUE 39) a dispatch's annotations say which dispatch they are: a serial
   over all families, the frame's index, a chunk's ``chunk_buckets`` entry
   and a step's rows; the frame carries the entry (``chunk_c``) and the
   loop's ingress (``ingress_ns`` / ``ingress_requests``);
8. (ISSUE 53) a dispatch that reads back marks the moment its result was
   ready: one ``decode.copyout.<family>`` inside its ``decode.readback.*``
   with the dispatch's stats, and the frame's ``rdy_ns`` after that mark.
"""

import asyncio
import glob
import itertools
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import decoder
from seldon_core_tpu.models.decoder import init_decoder, paged_kv_init
from seldon_core_tpu.serving.decode_scheduler import DecodeScheduler
from seldon_core_tpu.telemetry import flight as flight_mod
from seldon_core_tpu.telemetry.flight import FAMILIES, PHASES, PhaseTimer

SEQ = 8
MAX_NEW = 8
VOCAB = 64


def _params(**kw):
    return init_decoder(seed=3, vocab=VOCAB, hidden=32, layers=1, ffn=64, max_len=32, **kw)


class _Recorder:
    """Stands in for ``flight.annotate``: every call is one event, ended
    by the handle's ``__exit__`` as the real TraceAnnotation is."""

    def __init__(self):
        self.events: list[dict] = []
        self.clock = itertools.count()  # the order of starts and ends, over all threads

    def __call__(self, name, **kw):
        ev = {"name": name, "kw": kw, "thread": threading.get_ident(), "open": True, "exits": 0,
              "start": next(self.clock)}
        self.events.append(ev)
        return _Handle(ev, self.clock)

    def names(self) -> set:
        return {e["name"] for e in self.events}


class _Handle:
    def __init__(self, ev, clock):
        self.ev, self.clock = ev, clock

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.ev["open"] = False
        self.ev["exits"] += 1
        self.ev["end"] = next(self.clock)
        return False


def _shared_prompts(n, shared, seed):
    ids = np.random.default_rng(seed).integers(0, VOCAB, (n, SEQ)).astype(np.int32)
    ids[1:, :shared] = ids[0, :shared]
    return ids


def _drive(s, ids, first_kw=None):
    async def go():
        outs = [await s.submit(ids[0], **(first_kw or {}))]
        outs += await asyncio.gather(*(s.submit(r) for r in ids[1:]))
        await s.close()
        return outs

    return asyncio.run(go())


# what each kind of round must name, beyond what every kind does
COMMON = {"round", "phase.admit", "phase.alloc", "phase.scatter", "phase.emit_slo", "phase.commit",
          "dispatch.chunk", "enqueue.chunk", "readback.chunk"}
STEP = {"phase.sampling", "dispatch.step", "enqueue.step", "readback.step"}
SPEC = {"phase.accept_walk", "dispatch.verify", "enqueue.verify", "readback.verify",
        "dispatch.draft", "enqueue.draft"}
CONFIGS = {
    "plain-serial": (dict(n_slots=2), False, COMMON | STEP),
    "plain-pipelined": (dict(n_slots=2), True, COMMON | STEP),
    "chunk-pipelined": (dict(n_slots=2, prefill_chunk=4), True, COMMON | STEP),
    # six slots over a 4-row bound: five prompts arrive together, a round takes four (ISSUE 44)
    "chunk-held-pipelined": (dict(n_slots=6, prefill_chunk=4), True, COMMON | STEP),
    "spec-serial": (dict(n_slots=2, spec_k=3), False, COMMON | SPEC),
    "spec-pipelined": (dict(n_slots=2, spec_k=3), True, COMMON | SPEC),
    "prefix-cow": (dict(n_slots=2, prefix_slots=4, prefill_chunk=4, kv_page_size=4, kv_pages=14), True,
                   COMMON | STEP | {"phase.prefix_match", "dispatch.copy", "enqueue.copy"}),
}


@pytest.fixture(scope="module")
def recorded():
    """Each configuration served once with the emit helper stubbed:
    {config: (recorder, scheduler)}."""
    out = {}
    real = flight_mod.annotate
    try:
        for name, (kw, pipelined, _want) in CONFIGS.items():
            kw = dict(kw)
            if "spec_k" in kw:
                kw["draft_params"] = _params(resid_scale=0.1)
            s = DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, **kw)
            s.warmup()
            s.pipeline_enabled = pipelined
            rec = _Recorder()
            flight_mod.annotate = rec
            try:
                if name == "prefix-cow":
                    _drive(s, _shared_prompts(10, shared=5, seed=11), {"cache_prefix": 5})
                elif name == "chunk-held-pipelined":
                    _drive(s, _shared_prompts(6, shared=0, seed=1))
                else:
                    _drive(s, _shared_prompts(5, shared=0, seed=1))
            finally:
                flight_mod.annotate = real
            out[name] = (rec, s)
    finally:
        flight_mod.annotate = real
    return out


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_a_round_names_its_host_states(recorded, config):
    rec, s = recorded[config]
    want = {flight_mod.ANN_PREFIX + n for n in CONFIGS[config][2]}
    assert want <= rec.names(), sorted(want - rec.names())
    # every annotation that was started was ended, exactly once
    assert all(not e["open"] and e["exits"] == 1 for e in rec.events)
    # only registered names, all under the one prefix
    registered = (
        {flight_mod.ANN_ROUND, flight_mod.ANN_IDLE_WAIT, flight_mod.ANN_SSE_WRITE, flight_mod.ANN_INGRESS}
        | set(flight_mod.ANN_PHASE) | set(flight_mod.ANN_DISPATCH)
        | set(flight_mod.ANN_ENQUEUE) | set(flight_mod.ANN_READBACK) | set(flight_mod.ANN_COPYOUT)
        | {flight_mod.ANN_GC2}
    )
    assert rec.names() <= registered
    assert all(n.startswith(flight_mod.ANN_PREFIX) for n in registered)
    assert (s.stat_pipelined_rounds > 0) == CONFIGS[config][1]
    # a dispatch holds its enqueue and its readback: order on the recorder's clock
    order = [e["name"] for e in rec.events]
    for fam in ("chunk", "step", "verify"):
        d = flight_mod.ANN_PREFIX + "dispatch." + fam
        if d in order:
            i = order.index(d)
            assert order.index(flight_mod.ANN_PREFIX + "enqueue." + fam) > i
            assert order.index(flight_mod.ANN_PREFIX + "readback." + fam) > i


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_round_annotation_carries_the_frame_index_and_clock(recorded, config):
    """One ``decode.round`` per committed frame (plus the ones an idle wait
    or the loop's end closed without a frame); ``round`` is the index the
    frame committed under and ``t_ns`` the round clock's start, so a trace
    round joins its FlightFrame and the recorder's clock the trace's."""
    rec, s = recorded[config]
    rounds = [e["kw"] for e in rec.events if e["name"] == flight_mod.ANN_ROUND]
    frames = s.flight.snapshot()
    assert frames and len(rounds) >= len(frames)
    by_index: dict[int, list] = {}
    for kw in rounds:
        assert set(kw) == {"round", "t_ns"}
        by_index.setdefault(kw["round"], []).append(kw["t_ns"])
    for f in frames:
        # the LAST annotation opened under a frame's index is the round that
        # committed it (an idle wait restarts the round under the same index)
        assert f.seq in by_index, (f.seq, sorted(by_index))
        assert by_index[f.seq][-1] <= f.t_ns
    starts = [kw["t_ns"] for kw in rounds]
    assert starts == sorted(starts)


def test_every_registered_phase_and_family_is_emitted(recorded):
    seen = set().union(*(rec.names() for rec, _ in recorded.values()))
    for i, p in enumerate(PHASES):
        assert flight_mod.ANN_PHASE[i] == f"decode.phase.{p}" and flight_mod.ANN_PHASE[i] in seen, p
    for i, f in enumerate(FAMILIES):
        assert flight_mod.ANN_DISPATCH[i] == f"decode.dispatch.{f}" and flight_mod.ANN_DISPATCH[i] in seen, f
        assert flight_mod.ANN_ENQUEUE[i] == f"decode.enqueue.{f}" and flight_mod.ANN_ENQUEUE[i] in seen, f
    # draft and copy dispatches read nothing back; the others do, and mark when their result was ready
    for i, f in enumerate(FAMILIES):
        assert flight_mod.ANN_COPYOUT[i] == f"decode.copyout.{f}"
        assert (f"decode.readback.{f}" in seen) == (f"decode.copyout.{f}" in seen) == (f in ("chunk", "step", "verify"))
    assert flight_mod.ANN_IDLE_WAIT in seen  # the loop waited for its first request


def test_one_helper_times_the_step_round_and_timed_call():
    """The step round has no timing of its own: it and
    ``_timed_call`` both go through ``_dispatch`` (busy = the handle's
    wall, rdb = the part after the mark), so the frame's columns and the
    annotations cannot drift apart."""
    s = DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2)
    s.warmup()
    _drive(s, _shared_prompts(4, shared=0, seed=2))
    frames = [f for f in s.flight.snapshot() if f.mode == "plain"]
    assert frames and s.stat_pipelined_rounds > 0
    step = flight_mod.F_STEP
    assert all(0 < f.rdb_ns[step] <= f.busy_ns[step] for f in frames)
    import inspect

    src = inspect.getsource(DecodeScheduler._step_round)
    assert "self._dispatch(F_STEP, " in src and "perf_counter_ns" not in src
    assert "self._dispatches[family]" in inspect.getsource(DecodeScheduler._timed_call)


def test_ttft_counters_sum_to_the_request_stamps():
    """admit_wait_ns / prefill_ns / first_tokens over the frames are the
    sums of the per-request stamps (t_enqueued, t_admitted, t_first_token)."""
    s = DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2, prefill_chunk=4)
    s.warmup()
    seqs = []
    install = s._install_admit

    def spy(seq, *a, **kw):
        seqs.append(seq)
        return install(seq, *a, **kw)

    s._install_admit = spy
    _drive(s, _shared_prompts(7, shared=0, seed=4))
    frames = s.flight.snapshot()
    assert len(seqs) == 7 == sum(f.admitted for f in frames) == sum(f.first_tokens for f in frames)
    assert all(q.t_enqueued <= q.t_admitted <= q.t_first_token for q in seqs)
    assert sum(f.admit_wait_ns for f in frames) == sum(int((q.t_admitted - q.t_enqueued) * 1e9) for q in seqs)
    assert sum(f.prefill_ns for f in frames) == sum(int((q.t_first_token - q.t_admitted) * 1e9) for q in seqs)
    # five of seven waited for a slot; every prompt took two chunk rounds
    assert sum(f.admit_wait_ns for f in frames) > 0 and sum(f.prefill_ns for f in frames) > 0
    d = next(f for f in frames if f.first_tokens).to_dict()
    assert d["first_tokens"] >= 1 and d["prefill_us"] > 0
    agg = s.flight.aggregate()
    assert agg["admit_wait_ms_mean"] >= 0 and agg["prefill_ms_mean"] > 0


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_compiled_fused_programs_carry_every_scope(program):
    """The scope names reach the compiled program's op metadata (what a
    device trace's op events carry) — metadata only, no instruction."""
    params = _params()
    pool = paged_kv_init(params, 8, 4)
    n = 2
    bt = jnp.zeros((n, 4), jnp.int32)
    vec = jnp.zeros((n,), jnp.int32)
    temps = jnp.zeros((n,), jnp.float32)
    if program == "step":
        args = (params, pool, bt, vec, vec, temps, vec, 0, jnp.int32(1))
        fn = decoder._fused_step
    else:
        args = (params, pool, bt, jnp.zeros((n, 4), jnp.int32), vec, vec, temps, vec, 0, jnp.int32(1))
        fn = decoder._fused_chunk
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert len(set(decoder.PAGED_SCOPES)) == len(decoder.PAGED_SCOPES) == 9
    for scope in decoder.PAGED_SCOPES:
        assert f"/{scope}/" in text, scope


def test_a_cpu_profiler_session_records_the_annotations(tmp_path):
    """The real emit path, end to end: a short ``jax.profiler`` session
    round a served batch holds ``decode.round`` with its two stats and the
    phase / dispatch / enqueue / readback events."""
    from jax.profiler import ProfileData

    s = DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2)
    s.warmup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _drive(s, _shared_prompts(3, shared=0, seed=5))
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")),
               key=os.path.getmtime)
    events = [e for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events if e.name.startswith("decode.")]
    names = {e.name for e in events}
    assert {"decode.round", "decode.phase.admit", "decode.phase.emit_slo", "decode.dispatch.step",
            "decode.enqueue.step", "decode.readback.step", "decode.copyout.step", "decode.dispatch.chunk"} <= names
    # the mark lies inside the blocking read, on the trace's own clock, and says which dispatch it was
    reads = {int(dict(e.stats)["seq"]): e for e in events if e.name == "decode.readback.step"}
    marks = [e for e in events if e.name == "decode.copyout.step"]
    assert len(marks) == len(reads) > 0
    for e in marks:
        stats = dict(e.stats)
        r = reads[int(stats["seq"])]
        assert dict(r.stats) == stats and {"seq", "round"} <= set(stats)
        assert r.start_ns <= e.start_ns and e.start_ns + e.duration_ns <= r.start_ns + r.duration_ns
    committed = {f.seq for f in s.flight.snapshot()}
    seen = set()
    for e in events:
        if e.name == "decode.round":
            stats = dict(e.stats)
            assert set(stats) == {"round", "t_ns"}
            seen.add(int(stats["round"]))
    assert committed <= seen
    # with no session the helper hands back the shared no-op
    assert flight_mod.annotate("decode.round", round=0, t_ns=0) is flight_mod._NOOP_CTX


def test_annotations_cost_a_check_and_a_shared_noop_without_a_session(monkeypatch):
    """What the overhead budget guarded, by a measure that a loaded machine
    does not move: with no profiler session a synthetic round and a served
    batch construct NO ``TraceAnnotation`` (every emit is the helper's one
    check and the shared no-op), stats or none; the same rounds inside a
    session construct one an annotation."""
    made = []
    real = jax.profiler.TraceAnnotation

    class Counted(real):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(flight_mod, "_trace_annotation", Counted)
    monkeypatch.setattr(flight_mod, "_session_on", real.is_enabled)
    assert not real.is_enabled()
    assert PhaseTimer.measure_overhead(50) > 0 and PhaseTimer.measure_overhead(20, phases_per_round=40) > 0
    s = DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2, prefill_chunk=4)
    s.warmup()
    _drive(s, _shared_prompts(3, shared=0, seed=6))
    assert s.flight.rounds > 0 and s._dispatch_seq > 0 and made == []
    for ann in (flight_mod.annotate(flight_mod.ANN_DISPATCH[0], seq=1, round=0, rows=2, c=4, live=1),
                flight_mod.annotate(flight_mod.ANN_COPYOUT[1], seq=1, round=0), flight_mod.annotate(flight_mod.ANN_GC2),
                flight_mod.annotate(flight_mod.ANN_ROUND, round=0, t_ns=0), flight_mod.Ingress()._ann):
        assert ann is flight_mod._NOOP_CTX
    # the counter does count: inside a session every emit constructs one
    monkeypatch.setattr(flight_mod, "_session_on", lambda: True)
    PhaseTimer.measure_overhead(1, phases_per_round=8, dispatches_per_round=2)
    assert len(made) == 1 + 6 + 2 * 4 + 2  # the round, six flat phases, two triples with their copyout, the nested pair


def test_each_sse_flush_is_named(monkeypatch):
    """What else the loop thread does between a round's phases: one
    ``decode.sse_write`` per chunk the stream writer flushes."""
    from seldon_core_tpu.serving.fast_http import HttpProtocol
    from seldon_core_tpu.serving.wire import WireStreamResponse, sse_frame

    class Transport:
        def __init__(self):
            self.wrote = []

        def write(self, b):
            self.wrote.append(bytes(b))

    async def events():
        for i in range(3):
            yield sse_frame({"token": i})
        yield b""  # an empty chunk is skipped, not flushed

    rec = _Recorder()
    monkeypatch.setattr(flight_mod, "annotate", rec)
    proto = object.__new__(HttpProtocol)
    proto._transport, proto._closing = Transport(), False
    asyncio.run(proto._write_stream(WireStreamResponse(events())))
    assert [e["name"] for e in rec.events] == [flight_mod.ANN_SSE_WRITE] * 3
    assert all(not e["open"] for e in rec.events)
    assert sum(b"data: " in w for w in proto._transport.wrote) == 3


# ------------------------------------------------- ISSUE 39: which dispatch


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_a_dispatch_says_which_it_was(recorded, config):
    """Every ``decode.dispatch.*`` carries ``seq`` (one serial over all
    families, in the order the loop entered them) and ``round`` (the frame
    the round committed under); its enqueue and readback events carry the
    same stats; a chunk adds its ladder entry and its live rows, a step its
    rows and the generating slots."""
    rec, s = recorded[config]
    pre = flight_mod.ANN_PREFIX
    dispatches = [e for e in rec.events if e["name"].startswith(pre + "dispatch.")]
    assert [e["kw"]["seq"] for e in dispatches] == list(range(1, len(dispatches) + 1)) == list(
        range(1, s._dispatch_seq + 1))
    assert len({e["name"] for e in dispatches}) >= 2  # monotonic ACROSS families
    by_seq = {e["kw"]["seq"]: e for e in dispatches}
    frames = {f.seq: f for f in s.flight.snapshot()}
    for e in dispatches:
        fam, kw = e["name"].rsplit(".", 1)[1], e["kw"]
        assert all(isinstance(v, int) for k, v in kw.items() if k not in ("write", "attn"))
        frame = frames[kw["round"]]
        assert frame.busy_ns[FAMILIES.index(fam)] > 0  # the frame that round committed holds the dispatch
        if fam == "chunk":
            assert set(kw) == {"seq", "round", "rows", "c", "live", "write", "attn"}
            # how its program's attention read the pool: this family has no chunk kernel, and the frame counts none
            assert kw["attn"] == s.programs.chunk_attn(kw["c"]) == "walk" and frame.chunk_rows_kernel == 0
            # the form its program's pool write took, by the comparison the program makes
            assert kw["write"] == ("page" if kw["c"] >= s.pool.page_size else "row")
            assert (kw["rows"], kw["c"]) in s.chunk_buckets and 1 <= kw["live"] <= kw["rows"] <= s.chunk_rows_cap
            assert (frame.chunk_rows, frame.chunk_c, frame.chunk_rows_live) == (kw["rows"], kw["c"], kw["live"])
            assert frame.to_dict()["chunk_c"] == kw["c"]
            # a round leaves slots for a later one only where it is full, and its frame says how many
            assert frame.chunk_rows_held == 0 or kw["live"] == s.chunk_rows_cap
            assert frame.to_dict().get("chunk_rows_held", 0) == frame.chunk_rows_held
        elif fam == "step":
            assert set(kw) == {"seq", "round", "rows", "live"}
            assert kw["rows"] == s.n_slots and 1 <= kw["live"] <= s.n_slots and frame.active >= kw["live"]
        else:
            assert set(kw) == {"seq", "round"}
    for e in rec.events:
        kind = e["name"][len(pre):].split(".")[0]
        if kind in ("enqueue", "readback", "copyout"):
            d = by_seq[e["kw"]["seq"]]
            # a draft's enqueue inside a verify dispatch carries the verify's stats
            assert e["kw"] == d["kw"]
    # a round without a chunk dispatch says so
    assert all(f.chunk_c == 0 and f.chunk_rows_held == 0 for f in frames.values() if not f.chunk_rows)
    assert any(f.chunk_c for f in frames.values())
    held = sum(f.chunk_rows_held for f in frames.values())
    assert held == s.stat_chunk_rows_held and (held > 0) == (config == "chunk-held-pipelined")


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_a_reading_dispatch_marks_when_its_result_was_ready(recorded, config):
    """ISSUE 53: one ``decode.copyout.<family>`` a dispatch that reads back,
    begun and ended INSIDE that dispatch's ``decode.readback.<family>`` on the
    same thread, with the dispatch's stats; none for a dispatch that reads
    nothing (a draft prefill, the copy ladder). The frame books what came
    after the mark: ``0 <= rdy_ns <= rdb_ns <= busy_ns`` per family, above 0
    exactly where the family read back."""
    rec, s = recorded[config]
    pre = flight_mod.ANN_PREFIX
    reads = {e["kw"]["seq"]: e for e in rec.events if e["name"].startswith(pre + "readback.")}
    marks = [e for e in rec.events if e["name"].startswith(pre + "copyout.")]
    assert len(marks) == len(reads) > 0 and {e["kw"]["seq"] for e in marks} == set(reads)
    for e in marks:
        r = reads[e["kw"]["seq"]]
        assert e["name"].rsplit(".", 1)[1] == r["name"].rsplit(".", 1)[1] in ("chunk", "step", "verify")
        assert e["kw"] == r["kw"] and {"seq", "round"} <= set(e["kw"]) and e["thread"] == r["thread"]
        assert r["start"] < e["start"] < e["end"] < r["end"]
    read_in_round: dict[int, set] = {}
    for e in marks:
        read_in_round.setdefault(e["kw"]["round"], set()).add(FAMILIES.index(e["name"].rsplit(".", 1)[1]))
    frames = s.flight.snapshot()
    for f in frames:
        for i, (rdy, rdb, busy) in enumerate(zip(f.rdy_ns, f.rdb_ns, f.busy_ns)):
            assert 0 <= rdy <= rdb <= busy, (f.seq, FAMILIES[i])
            assert (rdy > 0) == (i in read_in_round.get(f.seq, ())), (f.seq, FAMILIES[i])
        d = f.to_dict()
        assert set(d.get("rdy_us", {})) == {FAMILIES[i] for i in read_in_round.get(f.seq, ())} <= set(d.get("rdb_us", {}))
    agg = s.flight.aggregate()
    assert agg["return_ms"] == {FAMILIES[i]: round(sum(f.rdy_ns[i] for f in frames) / 1e6, 3)
                                for i in range(len(FAMILIES)) if any(f.rdy_ns[i] for f in frames)}
    wall = sum(sum(f.busy_ns) + f.gap_ns for f in frames)
    assert 0.0 < agg["return_of_wall"] == round(sum(sum(f.rdy_ns) for f in frames) / wall, 4) < 1.0


def test_a_submit_books_the_loops_ingress_into_its_round(monkeypatch):
    """``submit(ingress=...)``: the mark's time goes into the frame of the
    round the submit lands in, once a request (a second row of the same
    request adds nothing); the one that wakes an idle loop is not dropped
    by the wait's round reset; the annotation ends at the submit."""
    rec = _Recorder()
    monkeypatch.setattr(flight_mod, "annotate", rec)
    s = DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2)
    s.warmup()
    ids = _shared_prompts(4, shared=0, seed=7)
    marks = []

    async def go():
        first = flight_mod.Ingress()
        marks.append(first)
        await asyncio.sleep(0.002)  # the body's parse
        outs = [await s.submit(ids[0], ingress=first)]  # wakes the idle loop
        shared = flight_mod.Ingress()  # one request of two rows
        marks.append(shared)
        outs += await asyncio.gather(s.submit(ids[1], ingress=shared), s.submit(ids[2], ingress=shared),
                                     s.submit(ids[3]))
        await s.close()
        return outs

    assert len(asyncio.run(go())) == 4
    frames = s.flight.snapshot()
    assert sum(f.ingress_requests for f in frames) == 2
    assert sum(f.ingress_ns for f in frames) >= 2_000_000
    first = next(f for f in frames if f.ingress_requests)
    assert first.seq == 0 and first.ingress_ns >= 2_000_000  # survived the idle wait's reset
    assert first.to_dict()["ingress"][0] == 1
    assert all(f.ingress_ns == 0 for f in frames if not f.ingress_requests)
    ing = [e for e in rec.events if e["name"] == flight_mod.ANN_INGRESS]
    assert len(ing) == 2 and all(not e["open"] and e["exits"] == 1 for e in ing)
    assert all(m.done() is None for m in marks)


def test_predict_stream_hands_the_wire_layers_mark_to_submit():
    """The stream endpoint marks before it parses the body and the service
    hands that mark (or, for another caller, one of its own) to ``submit``."""
    import inspect

    from seldon_core_tpu.serving import service, wire

    src = inspect.getsource(wire.engine_predictions_stream)
    assert src.index("Ingress()") < src.index("message_from_json_fast(req.body)") < src.index("ingress=ingress")
    src = inspect.getsource(service.PredictionService.predict_stream)
    assert "ingress = Ingress()" in src and "ingress=ingress" in src


def test_both_forms_of_the_pool_write_are_named(recorded):
    """ISSUE 40: a chunk whose ``c`` is a page's worth of rows or more says
    ``write`` "page", a shorter one "row": the recorded configurations hold
    both sides of the comparison."""
    seen = {}
    for rec, s in recorded.values():
        for e in rec.events:
            if e["name"] == flight_mod.ANN_PREFIX + "dispatch.chunk":
                seen.setdefault(e["kw"]["write"], set()).add((e["kw"]["c"], s.pool.page_size))
    assert set(seen) == {"page", "row"}
    assert all(c >= ps for c, ps in seen["page"]) and all(c < ps for c, ps in seen["row"])


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_the_sparse_expert_programs_nest_what_pr_47_added_under_the_old_scopes(program):
    """The per-head gate under ``attn_out``, the shared expert, the held
    experts and the dense layer under ``mlp`` by ``ops/moe.py``'s names,
    ``win/*`` and ``full/*`` as they were: a reader of the nine old scopes
    still sees all of the time, a reader of the finer ones can split it."""
    from seldon_core_tpu.models import moe_decoder as md

    cfg = md.MoEDecoderConfig(
        vocab=96, hidden=64, layers=4, heads=4, heads_window=6, kv_heads=2, head_dim=16, ffn=32, experts=16,
        experts_per_tok=3, experts_held=4, window=8, full_first=True, rotary_full=0.5, attn_gate=True, dense_layers=1,
        dense_ffn=96, shared_expert=True, routed_scale=2.5)
    fam = md.moe_family(cfg)
    params = md.init_moe_decoder(cfg, 0, jnp.float32)
    pool = fam.paged_kv_init(params, (8, 6), 4)
    n = 2
    bt = (jnp.zeros((n, 4), jnp.int32),) * 2
    vec, temps = jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.float32)
    step, chunk = fam.fused_programs()
    if program == "step":
        args = (params, pool, bt, vec, vec, temps, vec, 0, jnp.int32(1), jnp.ones((n,), bool))
    else:
        args = (params, pool, bt, jnp.zeros((n, 4), jnp.int32), vec, vec, temps, vec, 0, jnp.int32(1))
    text = jax.jit(step if program == "step" else chunk).lower(*args).compile().as_text()
    for scope in ("attn_out/gate", "mlp/shared_expert", "mlp/dense", "mlp/moe_router", "mlp/moe_experts", "mlp/moe_combine",
                  "win/kv_gather", "win/attn", "full/attn", "qkv/rope"):
        assert f"/{scope}/" in text, scope
    for scope in decoder.PAGED_SCOPES:
        assert f"/{scope}/" in text, scope
    assert "/gate/" not in text.replace("/attn_out/gate/", "")  # the gate is nowhere but under attn_out


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_the_hybrid_programs_nest_the_expert_layer_under_mlp_beside_the_state_scopes(program):
    """A configuration of single sublayers (PR 51): the expert layer under
    ``mlp`` by ``ops/moe.py``'s names, the shared expert among them, the
    Mamba-2 mixer under the ``ssm_*`` names it had, attention as it was: a
    reader of the nine old scopes still sees all of the time."""
    from seldon_core_tpu.models import hybrid_decoder as hd
    from tests.test_hybrid_decoder import NCFG

    fam = hd.hybrid_family(NCFG)
    params = hd.init_hybrid_decoder(NCFG, 0, jnp.float32)
    pool, rec = fam.paged_kv_init(params, 8, 4), fam.state_init(params, 4)
    n = 2
    bt = jnp.zeros((n, 4), jnp.int32)
    vec, temps = jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.float32)
    step, chunk = fam.fused_programs()
    if program == "step":
        args = (params, pool, rec, bt, vec, vec, temps, vec, 0, jnp.int32(1), jnp.ones((n,), bool))
    else:
        args = (params, pool, rec, bt, jnp.zeros((n, 4), jnp.int32), vec, vec, temps, vec, 0, jnp.int32(1),
                jnp.zeros((3, n), jnp.int32))
    text = jax.jit(step if program == "step" else chunk).lower(*args).compile().as_text()
    for scope in ("mlp/shared_expert", "mlp/moe_router", "mlp/moe_dispatch", "mlp/moe_experts", "mlp/moe_combine",
                  "qkv/ssm_in", "attn/ssm_conv", "attn/ssm_scan", "attn_out/ssm_norm", "attn_out/ssm_out"):
        assert f"/{scope}/" in text, scope
    for scope in decoder.PAGED_SCOPES:
        assert f"/{scope}/" in text, scope
    assert "/shared_expert/" not in text.replace("/mlp/shared_expert/", "")  # nowhere but under mlp


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_the_hybrid_third_shape_nests_the_delta_rule_and_the_gate_under_the_old_scopes(program):
    """A configuration of the third shape (PR 57): the gated delta rule under
    ``gdn_*`` names nested as the Mamba-2 ones are, the gated attention's
    ``qkv/rope`` and ``attn_out/attn_gate``, the expert layer under ``mlp`` by
    ``ops/moe.py``'s names with the shared expert's gate among them: a reader
    of the nine old scopes still sees all of the time."""
    from seldon_core_tpu.models import hybrid_decoder as hd
    from tests.test_hybrid_decoder import QCFG

    fam = hd.hybrid_family(QCFG)
    params = hd.init_hybrid_decoder(QCFG, 0, jnp.float32)
    pool, rec = fam.paged_kv_init(params, 8, 4), fam.state_init(params, 4)
    n = 2
    bt = jnp.zeros((n, 4), jnp.int32)
    vec, temps = jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.float32)
    step, chunk = fam.fused_programs()
    if program == "step":
        args = (params, pool, rec, bt, vec, vec, temps, vec, 0, jnp.int32(1), jnp.ones((n,), bool))
    else:
        args = (params, pool, rec, bt, jnp.zeros((n, 4), jnp.int32), vec, vec, temps, vec, 0, jnp.int32(1),
                jnp.zeros((3, n), jnp.int32))
    text = jax.jit(step if program == "step" else chunk).lower(*args).compile().as_text()
    for scope in ("qkv/gdn_in", "attn/gdn_conv", "attn/gdn_scan", "attn_out/gdn_norm", "attn_out/gdn_out", "qkv/rope",
                  "attn_out/attn_gate", "mlp/shared_expert", "mlp/moe_router", "mlp/moe_dispatch", "mlp/moe_experts",
                  "mlp/moe_combine"):
        assert f"/{scope}/" in text, scope
    for scope in decoder.PAGED_SCOPES:
        assert f"/{scope}/" in text, scope
    assert "/gdn_scan/" not in text.replace("/attn/gdn_scan/", "")  # nowhere but under attn
    assert "/ssm_scan/" not in text  # the Mamba-2 names belong to the other two shapes
