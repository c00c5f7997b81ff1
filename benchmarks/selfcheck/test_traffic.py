import collections
import json
import os

import pytest
from conftest import BENCH, ROOT

from harness import traffic as T


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")) if f.endswith(".json"))


@pytest.mark.parametrize("mix", MIXES)
def test_two_seeds_offer_the_same_work(mix):
    a = T.build_plan(_mix(mix), seed=7, seconds=45)
    b = T.build_plan(_mix(mix), seed=2**31 + 11, seconds=45)
    assert T.offered_work(a) == T.offered_work(b)


def test_seed_changes_token_ids_only():
    mix = _mix("batch-unshared")
    a = T.build_plan(mix, seed=1, seconds=45)
    b = T.build_plan(mix, seed=2**31 + 5, seconds=45)
    # client c runs lane c whatever the seed: the order of the lanes over the clients decides which
    # requests share the first prefill rounds, and with that the closed loop's whole course
    assert a == b and [[r["max_new"] for r in lane] for lane in a["clients"]] == mix["lanes"]
    assert len({r["uid"] for lane in a["clients"] for r in lane}) == sum(len(lane) for lane in mix["lanes"])
    req = a["clients"][0][0]
    assert T.token_ids(1, 50257, req) != T.token_ids(2, 50257, req)
    assert T.token_ids(1, 50257, req) == T.token_ids(1, 50257, req)
    assert len(T.token_ids(2**31 + 5, 50257, req)) == mix["prompt_len"]


def test_lanes_hold_the_table_once_and_prompts_fit():
    mix = _mix("batch-unshared")
    assert len(mix["lanes"]) == mix["clients"] == 16
    cycled = [m for lane in mix["lanes"] for m in lane[mix["cycle_from"]:]]
    assert collections.Counter(cycled) == collections.Counter(mix["output_table"])
    assert all(mix["prompt_len"] + m <= 1024 for m in mix["output_table"])
    sums = [sum(lane[mix["cycle_from"]:]) for lane in mix["lanes"]]
    assert max(sums) <= 1.6 * min(sums)  # no lane carries far more than another


def test_open_loop_count_is_the_rate_times_the_window():
    mix = _mix("sysprompt-open")
    for seconds in (10, 45, 51):
        plan = T.build_plan(mix, seed=3, seconds=seconds)
        due = [a for a in plan["arrivals"] if a["measured"]]
        assert len(due) == round(mix["rate_rps"] * seconds)
        times = [a["due"] for a in due]
        assert times == sorted(times)
        assert mix["ramp_s"] <= times[0] and times[-1] < mix["ramp_s"] + seconds
    fams = collections.Counter(a["prefix"] for a in T.build_plan(mix, seed=3, seconds=400)["arrivals"] if a["measured"])
    total = sum(fams.values())
    assert [round(fams[k] / total, 1) for k in range(4)] == mix["prefix_shares"]


def test_the_open_cell_offers_a_tail_worth_of_requests_and_opens_at_steady_occupancy():
    """TTFT is bounded in this cell (PR 31): its median wants a hundred
    requests on either side and its 90th percentile twenty beyond it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mix = _mix("sysprompt-open")
    a = T.build_plan(mix, seed=0, seconds=bench["run_seconds"])
    b = T.build_plan(mix, seed=2**31 + 5, seconds=bench["run_seconds"])
    assert T.offered_work(a) == T.offered_work(b)  # the same work at the same times
    assert [(x["uid"], x["due"], x["max_new"], x["prefix"]) for x in a["arrivals"]] == [
        (x["uid"], x["due"], x["max_new"], x["prefix"]) for x in b["arrivals"]]
    assert sum(1 for x in a["arrivals"] if x["measured"]) >= 200
    assert 1 <= mix["inflight_at_start"] <= 16  # no more in flight than the deployment has slots
    assert mix["rate_rps"] * 4 == round(mix["rate_rps"] * 4)  # rounded to 0.25
    assert any(str(mix["rate_rps"]) in w["why"] for w in bench["workloads"] if w["traffic"] == "sysprompt-open")


def test_shared_prefix_is_shared_and_tails_are_not():
    base = {"prompt_len": 64, "prefix_len": 48, "prefix": 1}
    a = T.token_ids(9, 512, {**base, "uid": 1})
    b = T.token_ids(9, 512, {**base, "uid": 2})
    c = T.token_ids(9, 512, {**base, "prefix": 2, "uid": 1})
    assert a[:48] == b[:48] and a[48:] != b[48:] and a[:48] != c[:48]
