"""The bench artifact-of-record contract.

A driver may record only the LAST 2,000 bytes of bench.py's stdout, and a
record larger than that loses its headline numbers. These tests pin the
fix: compact_record() must stay comfortably under the cap on a WORST-CASE
fully populated record, must carry every headline figure, and must name the
device the record was taken on — which a run without a chip cannot produce.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench.py")


def _load_bench():
    spec = importlib.util.spec_from_file_location("bench", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("bench", mod)
    spec.loader.exec_module(mod)
    return mod


def _leg(pps: float, p50: float, p99: float, errors: int = 0) -> dict:
    # the full per-leg dicts carry far more (users, batch,
    # mean_batch_rows...) — compact_record must take only the quartet
    return {
        "preds_per_sec": pps,
        "p50_ms": p50,
        "p95_ms": p99 * 0.9,
        "p99_ms": p99,
        "requests": 123456,
        "errors": errors,
        "batch_per_request": 4,
        "users": 64,
        "mean_batch_rows": 127.9,
        "mean_queue_wait_ms": 12.34,
    }


def _tenants(n: int) -> dict:
    return {
        f"tenant{i}": {
            "preds_per_sec": 7051.09,
            "p99_ms": 88.16,
            "errors": 0,
            "mean_batch_rows": 44.0,
            "mean_queue_wait_ms": 2.95,
        }
        for i in range(n)
    }


def worst_case_full_record() -> dict:
    """Every section populated, numbers at realistic-max digit widths."""
    mt = lambda agg, lag: {  # noqa: E731
        "aggregate_preds_per_sec": agg,
        "tenants": _tenants(3),
        "hbm_param_bytes_total": 26799200123,
        "n_tenants": 3,
        "users_each": 11,
        "total_users": 33,
        "loop_lag_mean_ms": 2.564,
        "loop_lag_max_ms": lag,
    }
    ceiling = _leg(24141.53, 5.55, 10.85)
    ceiling["loadgen_sweep"] = {
        "workers_1_preds_per_sec": 24141.53,
        "workers_2_preds_per_sec": 23987.11,
        "workers_2_p99_ms": 11.92,
        "host_cpu_count": 1,
    }
    ceiling["combiner_ratio_cpu"] = {
        "fused_preds_per_sec": 1234.56,
        "fused_p99_ms": 25.01,
        "unfused_preds_per_sec": 592.81,
        "unfused_p99_ms": 55.02,
        "fused_errors": 0,
        "unfused_errors": 0,
        "fusion_speedup": 2.08,
    }
    ceiling["wire_matrix"] = {
        "model": "resnet_tiny_32x32x3_uint8",
        "rest_npy_preds_per_sec": 2241.15,
        "rest_npy_p99_ms": 18.41,
        "grpc_bindata_preds_per_sec": 1120.57,
        "grpc_bindata_p99_ms": 30.88,
        "rest_npy_errors": 0,
        "grpc_bindata_errors": 0,
    }
    ceiling["multi_tenant"] = mt(18233.19, 73.61)
    ceiling["multi_tenant_equal_users"] = mt(18233.19, 73.61)
    ceiling["multi_tenant_homogeneous"] = mt(21142.04, 3.14)
    fused = _leg(68.21, 466.01, 2870.99)
    fused.update(
        unfused_preds_per_sec=33.42,
        unfused_p99_ms=3870.22,
        unfused_errors=0,
        unfused_users=8,
    )
    bert = _leg(1234.56, 105.5, 871.2)
    bert.update(tflops=35.21, mfu_pct=61.77)
    gen = {
        "scenario": {
            "requests": 64,
            "n_slots": 8,
            "seq": 16,
            "max_new_cap": 64,
            "budgets": "choice(8,16,32,64; p=.4/.3/.2/.1)",
            "stagger_ms": 2.0,
            "spec_k": 4,
            "resid_scale": 0.1,
            "draft": "1-of-4 layers, seed-shared",
        },
        "scheduler": {
            "tokens_per_sec": 1690.42,
            "ttft_p50_ms": 630.44,
            "ttft_p99_ms": 1265.01,
            "inter_token_p99_ms": 26.81,
            "slot_occupancy_mean": 0.893,
            "recompiles_after_warmup": 0,
            "steps": 1234,
            "loop": {
                "frames": 1234,
                "bubble_fraction": 0.3127,
                "overlap_of_gap": 0.232,
                "bubble_residual": 0.768,
                "occupancy": 0.8911,
                "blocked_rounds": 17,
                "record_us": 4.812,
                "phases": {
                    "admit": 0.1324, "prefix_match": 0.0009,
                    "alloc": 0.1127, "scatter": 0.0135,
                    "emit_slo": 0.058, "accept_walk": 0.0411,
                    "sampling": 0.0691, "commit": 0.0223,
                },
            },
        },
        "serial_loop": {
            "tokens_per_sec": 1573.1,
            "ttft_p50_ms": 655.02,
            "recompiles_after_warmup": 0,
            "loop": {
                "frames": 1221, "bubble_fraction": 0.3127,
                "overlap_of_gap": 0.0, "bubble_residual": 1.0,
                "occupancy": 0.888, "blocked_rounds": 19, "record_us": 4.7,
            },
        },
        "pipeline": {
            "outputs_identical": True,
            "tokens_per_sec_pipelined": 1690.42,
            "tokens_per_sec_serial": 1573.1,
            "bubble_fraction_pipelined": 0.2471,
            "bubble_fraction_serial": 0.3127,
            "overlap_of_gap": 0.232,
        },
        "spec": {
            "tokens_per_sec": 2890.13,
            "ttft_p50_ms": 601.22,
            "ttft_p99_ms": 1103.44,
            "inter_token_p99_ms": 31.02,
            "slot_occupancy_mean": 0.881,
            "recompiles_after_warmup": 0,
            "steps": 412,
            "accept_rate": 0.941,
            "tokens_per_dispatch": 4.31,
            "spec_dispatches": 410,
        },
        "scan": {
            "tokens_per_sec": 261.63,
            "ttft_p50_ms": 3279.11,
            "ttft_p99_ms": 4411.92,
        },
        "prefix": {
            "scenario": {
                "requests": 24, "seq": 64, "shared_prefix": 56,
                "prefix_slots": 8, "chunk": 8, "max_new": 8,
            },
            "monolithic": {
                "tokens_per_sec": 1411.02, "ttft_cold_p50_ms": 171.33,
                "ttft_warm_p50_ms": 41.27, "ttft_warm_p99_ms": 88.19,
                "inter_token_p99_ms": 44.91, "hit_rate": 0.958,
                "prefill_tokens_saved": 1288, "chunk_dispatches": 25,
                "recompiles_after_warmup": 0,
            },
            "chunked": {
                "tokens_per_sec": 1389.77, "ttft_cold_p50_ms": 183.41,
                "ttft_warm_p50_ms": 44.02, "ttft_warm_p99_ms": 91.33,
                "inter_token_p99_ms": 21.08, "hit_rate": 0.958,
                "prefill_tokens_saved": 1288, "chunk_dispatches": 41,
                "recompiles_after_warmup": 0,
            },
            "warm_ttft_speedup": 4.15,
        },
        "tp": {
            "scenario": {
                "widths": [1, 2, 4], "devices": 8, "requests": 24,
                "seq": 64, "shared_prefix": 56, "max_new": 8, "n_slots": 8,
                "geometry": "paged+prefix, page_size 16",
            },
            "tp1": {
                "tp": 1, "tokens_per_sec": 1388.41, "ttft_p50_ms": 40.11,
                "ttft_p99_ms": 171.02, "inter_token_p99_ms": 22.18,
                "recompiles_after_warmup": 0, "kv_pages_per_device": 20,
                "mesh_devices": 1,
            },
            "tp2": {
                "tp": 2, "tokens_per_sec": 1101.33, "ttft_p50_ms": 51.72,
                "ttft_p99_ms": 201.44, "inter_token_p99_ms": 28.05,
                "recompiles_after_warmup": 0, "kv_pages_per_device": 20,
                "mesh_devices": 2, "outputs_identical_to_tp1": True,
                "speedup_vs_tp1": 0.79,
            },
            "tp4": {
                "tp": 4, "tokens_per_sec": 905.87, "ttft_p50_ms": 66.41,
                "ttft_p99_ms": 255.13, "inter_token_p99_ms": 35.92,
                "recompiles_after_warmup": 0, "kv_pages_per_device": 20,
                "mesh_devices": 4, "outputs_identical_to_tp1": True,
                "speedup_vs_tp1": 0.65,
            },
        },
        "replicas": {
            "scenario": {
                "requests": 128, "groups": 8, "seq": 64, "shared_prefix": 56,
                "max_new": 16, "n_slots_per_replica": 4, "host_cpus": 1,
                "geometry": "paged+prefix, page_size 16, 2 replicas",
            },
            "single": {
                "replicas": 1, "policy": "single", "tokens_per_sec": 440.68,
                "hit_rate": 0.938, "prefill_tokens_saved": 6720,
                "recompiles_after_warmup": 0,
            },
            "affinity": {
                "replicas": 2, "policy": "affinity", "tokens_per_sec": 348.29,
                "hit_rate": 0.914, "prefill_tokens_saved": 6552,
                "recompiles_after_warmup": 0,
                "routes": {"affinity": 113, "shed": 15, "fallback": 0,
                           "round_robin": 0},
            },
            "round_robin": {
                "replicas": 2, "policy": "round_robin",
                "tokens_per_sec": 323.53, "hit_rate": 0.844,
                "prefill_tokens_saved": 6048, "recompiles_after_warmup": 0,
                "routes": {"affinity": 0, "shed": 0, "fallback": 0,
                           "round_robin": 128},
            },
            "affinity_speedup_vs_single": 0.79,
            "serialized_host": True,
            "scale_floor_met": None,
            "affinity_hit_delta": -0.024,
            "outputs_identical": True,
        },
        "tree": {
            "scenario": {
                "requests": 24, "n_slots": 4, "seq": 32, "shared_prefix": 24,
                "max_new": 32, "model": "hidden 64 x 2L, vocab 256",
                "draft": "1L, KL-distilled in-leg (150 steps, resid_scale=1.0)",
                "spec_k": 4, "spec_tree": "2,2,1,1", "rtt_floor_ms": 100.0,
            },
            "distill": {
                "accept_proxy_before": 0.0664, "accept_proxy_after": 0.5352,
                "final_kl": 0.006,
            },
            "plain": {
                "dispatches": 207, "recompiles_after_warmup": 0,
                "tokens_per_sec_raw": 2157.1, "tokens_per_sec_rtt": 35.6,
            },
            "chain": {
                "dispatches": 106, "recompiles_after_warmup": 0,
                "accept_rate": 0.352, "tokens_per_ride": 2.37,
                "spec_dispatches": 85, "tokens_per_sec_raw": 1251.5,
                "tokens_per_sec_rtt": 58.8,
            },
            "tree": {
                "dispatches": 84, "recompiles_after_warmup": 0,
                "accept_rate": 0.568, "tokens_per_ride": 3.21,
                "spec_dispatches": 66, "tokens_per_sec_raw": 448.6,
                "tokens_per_sec_rtt": 63.4,
            },
            "fdistill": {
                "accept_proxy_before": 0.0, "accept_proxy_after": 0.5391,
                "final_kl": 0.012,
            },
            "ftree": {
                "dispatches": 78, "recompiles_after_warmup": 0,
                "accept_rate": 0.641, "tokens_per_ride": 3.52,
                "spec_dispatches": 61, "tokens_per_sec_raw": 402.1,
                "tokens_per_sec_rtt": 67.9,
            },
            "outputs_identical": True,
            "tokens_per_ride_vs_chain": 1.35,
            "rtt_speedup_vs_chain": 1.08,
            "ftree_ride_vs_tree": 1.1,
            "ftree_rtt_speedup_vs_tree": 1.07,
        },
        "tokens_per_sec_speedup": 2.64,
        "spec_tokens_per_sec_speedup": 1.71,
    }
    return {
        "metric": "resnet50_predictions_per_sec",
        "value": 12833.61,
        "unit": "preds/s",
        "vs_baseline": 10.2669,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "serving": {
            "gen": gen,
            "iris_chip": _leg(2950.44, 85.2, 870.13),
            "resnet50_chip": _leg(65.83, 453.11, 1870.42),
            "bert_base_chip": bert,
            "combiner_fused": fused,
            "full_dag": _leg(78.42, 190.7, 1234.56),
            "abtest": _leg(20885.97, 5.52, 8.54),
            "grpc": _leg(5831.07, 21.61, 35.92),
            "grpc_web": _leg(17536.0, 6.69, 13.96),
            "moe_cpu": _leg(9123.45, 6.78, 14.31),
            "pallas_long_seq": {
                "seq": 2048,
                "pallas_ms": 123.45,
                "blockwise_ms": 256.78,
                "speedup": 2.08,
                "causal_ms": 111.22,
                "blockwise_causal_ms": 278.99,
                "causal_speedup": 2.51,
            },
            "stack_ceiling_cpu": ceiling,
        },
    }


def test_compact_record_fits_driver_tail():
    bench = _load_bench()
    full = worst_case_full_record()
    line = json.dumps(bench.compact_record(full), separators=(",", ":"))
    # driver cap is 2,000 bytes of tail; require headroom (newline, rc
    # prefix variations, wider numbers on a different run)
    assert len(line) < 1800, f"compact record is {len(line)} bytes:\n{line}"
    # and it must round-trip as the driver parses it
    assert json.loads(line)["value"] == 12833.61


def test_compact_record_carries_every_headline():
    bench = _load_bench()
    c = bench.compact_record(worst_case_full_record())
    # driver contract
    assert c["metric"] == "resnet50_predictions_per_sec"
    assert c["unit"] == "preds/s"
    assert c["vs_baseline"] == 10.2669
    s = c["s"]
    # per-leg quartets [pps, p50, p99, errors]
    assert s["iris"] == [2950.44, 85.2, 870.13, 0]
    assert s["rn50"][0] == 65.83
    assert s["bert"][0] == 1234.56
    assert s["comb_fused"][0] == 68.21
    # 4-slot row like every other; the chip leg records no unfused p50
    assert s["comb_unfused"] == [33.42, None, 3870.22, 0]
    assert s["full_dag"][0] == 78.42
    assert s["abtest"][0] == 20885.97
    assert s["grpc"][0] == 5831.07
    assert s["grpc_web"][0] == 17536.0
    assert s["moe"][0] == 9123.45
    assert s["ceiling"] == [24141.53, 5.55, 10.85, 0]
    # cross-leg ratios and aggregates
    assert c["sweep_w1_w2"] == [24141.53, 23987.11]
    assert c["fusion_cpu"] == {"fused": 1234.56, "unfused": 592.81, "speedup": 2.08}
    assert c["wire"] == {"rest_npy": 2241.15, "grpc_bin": 1120.57}
    assert c["mt"]["agg"] == 18233.19
    assert c["mt"]["homo_agg"] == 21142.04
    assert c["mt"]["lag_max_ms"] == [73.61, 3.14]
    # per-tenant p99s (cited by README/PARITY) survive into the record
    assert c["mt"]["p99s"] == [88.16, 88.16, 88.16]
    assert c["mt"]["homo_p99s"] == [88.16, 88.16, 88.16]
    assert c["pallas"]["speedup"] == 2.08
    assert c["pallas"]["causal_speedup"] == 2.51
    # generative tier: scheduler-vs-scan tokens/s + latency contracts +
    # the speculative leg (delivered tokens/s, accept rate, amortization)
    assert c["gen"] == {
        "tok_s": 1690.42,
        "tok_s_scan": 261.63,
        "speedup": 2.64,
        "ttft_p50": 630.44,
        "ttft_p99": 1265.01,
        "itl_p99": 26.81,
        "scan_p50": 3279.11,
        "occ": 0.893,
        "recompiles": 0,
        # flight-recorder sub-leg, packed to fit the byte budget:
        # [bubble_fraction, occupancy, record_us] + the TOP gap-phase
        # fraction (host-bubble attribution; recorded, not gated; was
        # top-2 until the gen.ftree_* pack needed the bytes — the PR 14
        # trim also dropped the config-only slots/spec_k/paged_budget and
        # the ungated prefix_saved)
        "loop": [0.313, 0.891, 4.8],
        "loop_ph": {"admit": 0.132},
        # pipelined-vs-serial A/B, packed [tok_s_serial, bubble_serial,
        # overlap_of_gap] — the pipelined side IS gen.tok_s/gen.loop[0];
        # position 2 is --compare-gated (identity contract in the full
        # record)
        "pipe": [1573.1, 0.313, 0.232],
        "spec_tok_s": 2890.13,
        "accept_rate": 0.941,
        "tok_disp": 4.31,
        "spec_spd": 1.71,
        # prefix-cache sub-leg: cold/warm TTFT split, hit rate, tokens/s
        # + ITL with chunking off/on (short names since PR 11's
        # byte-budget trim; full names in the detail record)
        "prefix_cold": 171.33,
        "prefix_warm": 41.27,
        "prefix_spd": 4.15,
        "prefix_hit": 0.958,
        "prefix_tok_s": 1411.02,
        "prefix_tok_s_ck": 1389.77,
        "prefix_itl": 44.91,
        "prefix_itl_ck": 21.08,
        # tree-speculation sub-leg, [tree, chain] pairs: tokens/s under
        # the dispatch-RTT floor and per-slot accepted+bonus per verify
        # dispatch at the same 2-dispatch round shape (identity contract
        # + distilled-draft delta live in the full record / PARITY.md)
        "tree_tok_s": [63.4, 58.8],
        "tree_ride": [3.21, 2.37],
        "tree_spd": 1.08,
        # feature-draft twin (EAGLE-style head) at the same 2-dispatch
        # round: RTT tokens/s, per-slot ride, non-probe accept rate —
        # ftree_tok_s and ftree_ride are --compare-gated
        "ftree_tok_s": 67.9,
        "ftree_ride": 3.52,
        "ftree_acc": 0.641,
        # tensor-parallel sub-leg: tokens/s per width (width order), the
        # widest leg's speedup + identity contract, recompiles all-zero
        # tp_ttft/tp_itl (per-width latency rows, never gated) left with
        # PR 15's byte-budget trim paying for the gen.replica pack
        "tp_w": [1, 2, 4],
        "tp_tok_s": [1388.41, 1101.33, 905.87],
        "tp_speedup": 0.65,
        "tp_ident": True,
        "tp_rc": [0, 0, 0],
        # multi-replica scale-out sub-leg, packed [affinity tok/s,
        # speedup vs single, affinity hit rate, round-robin hit rate] —
        # first three --compare-gated, rr documents the collapse
        "replica": [348.29, 0.79, 0.914, 0.844],
    }
    assert c["bert_tflops"] == 35.21
    assert c["bert_mfu_pct"] == 61.77
    # every record names the device it was taken on
    assert c["device"] == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_bench_without_a_chip_fails_and_prints_no_record():
    """A measurement path that finds no chip fails: no CPU fallback model,
    no kernel-only record, no exit 0."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, _BENCH], env=env, capture_output=True, text=True, timeout=300
    )
    assert out.returncode != 0
    assert out.stdout == ""
    assert "found none" in out.stderr


def test_utilization_peak_is_keyed_by_device_kind():
    """mfu_pct divides by the PUBLISHED peak of the device the run is on;
    an unknown device is an error, never a default."""
    bench = _load_bench()
    assert bench.device_peak("TPU v5 lite", "bf16_tflops") == 197.0
    with pytest.raises(KeyError, match="no published peak"):
        bench.device_peak("TPU v9 imaginary", "bf16_tflops")


def test_cpu_child_failure_fails_the_run(monkeypatch):
    """A failed child leg raises (it used to return None and the record,
    minus the leg, still exited 0)."""
    bench = _load_bench()
    monkeypatch.setattr(
        bench.subprocess, "run",
        lambda *a, **k: subprocess.CompletedProcess(a, 3, stdout="", stderr="boom"),
    )
    with pytest.raises(RuntimeError, match="rc=3.*boom"):
        bench.stack_ceiling_subprocess()
