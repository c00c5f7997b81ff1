"""Prometheus metrics with the reference's exact metric names/tags so its
Grafana dashboard ports unchanged (SURVEY §5.5, C10/C27):

- seldon_api_ingress_server_requests_duration_seconds — server-side request
  histogram (reference api-frontend AuthorizedWebMvcTagsProvider)
- seldon_api_engine_client_requests_duration_seconds — per-unit-call histogram
  (reference SeldonRestTemplateExchangeTagsProvider.getTags/getModelMetrics)
- seldon_api_model_feedback / seldon_api_model_feedback_reward counters
  (reference PredictiveUnitBean.java:239-242)
- TPU additions: batch-size histogram, queue-wait histogram, compile counter.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

try:
    from prometheus_client import (
        CollectorRegistry,
        Counter,
        Gauge,
        Histogram,
        REGISTRY,
        generate_latest,
    )

    HAVE_PROMETHEUS = True
except Exception:  # noqa: BLE001 - prometheus_client optional
    HAVE_PROMETHEUS = False

_LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0
)


class NullMetrics:
    """No-op recorder (metrics disabled or prometheus_client absent)."""

    def ingress_request(
        self,
        deployment: str,
        method: str,
        duration_s: float,
        trace_id: str | None = None,
    ) -> None:
        """``trace_id``: the request's telemetry trace id; real recorders
        attach it as an exemplar so a slow histogram sample links to its
        trace (metrics -> trace correlation, docs/observability.md)."""
        pass

    def ingress_error(self, deployment: str, method: str, code: int) -> None:
        pass

    def unit_call(self, deployment: str, predictor: str, unit: str, method: str,
                  duration_s: float) -> None:
        pass

    def feedback(self, deployment: str, predictor: str, unit: str, reward: float) -> None:
        pass

    def batch(self, deployment: str, size: int, queue_waits_s) -> None:
        """``queue_waits_s``: the per-request waits of EVERY batch-mate (a
        float is accepted for a single request)."""
        pass

    def decode_step(self, deployment: str, active: int, slots: int) -> None:
        pass

    def decode_ttft(self, deployment: str, duration_s: float) -> None:
        pass

    def decode_inter_token(self, deployment: str, duration_s: float) -> None:
        pass

    def decode_spec(
        self,
        deployment: str,
        proposed: int,
        accepted: int,
        emitted: int,
        mode: str = "chain",
    ) -> None:
        """One speculative verify dispatch: ``proposed`` depth positions
        entered acceptance (draft tokens on a chain; path depths on a
        tree), ``accepted`` survived, ``emitted`` tokens (accepted + one
        bonus per active slot) were emitted. Accept rate = accepted_total
        / proposed_total. ``mode`` labels the per-dispatch amortization
        histogram "chain" | "tree" so the two round shapes compare
        directly at the same 2-dispatch cost."""
        pass

    def decode_spec_tree(self, deployment: str, nodes: int, path_len: int) -> None:
        """One slot's ride on a TREE verify dispatch: ``nodes`` candidate
        nodes were allowed by the slot's per-depth width mask (the
        adapt/tighten budget — the dispatch's static width is the
        deployment tree), ``path_len`` the accepted-path depth the walk
        reached. Wide nodes with short paths = wasted verify width (lower
        the branching or the floor); long paths at small node budgets =
        headroom (widen)."""
        pass

    def decode_prefix(self, deployment: str, hit: bool, tokens_saved: int) -> None:
        """One prefix-cache lookup at admission: ``hit`` whether a pool
        entry covered a reusable prefix, ``tokens_saved`` the prefill
        positions the gather replaced (0 on miss)."""
        pass

    def decode_prefix_evicted(self, deployment: str) -> None:
        pass

    def decode_ttft_split(self, deployment: str, duration_s: float, path: str) -> None:
        """TTFT again, split by ``path`` ("warm" = admitted over a prefix
        hit, "cold" = full prefill) — the latency contract the prefix
        cache exists to move. Only emitted when the cache is enabled."""
        pass

    # paged KV pool (serving/kv_pool.py): page occupancy by class, and the
    # three event streams that explain it — copy-free shares at admission,
    # copy-on-write page copies, and LRU reclaim of prefix pins
    def decode_kv_pool(self, deployment: str, free: int, live: int, prefix: int) -> None:
        """Pool occupancy gauges: ``free`` unallocated pages, ``live``
        pages referenced by at least one slot, ``prefix`` pages held only
        by prefix-cache pins (the reclaimable set)."""
        pass

    def decode_kv_window_pool(self, deployment: str, free: int, live: int, released: int) -> None:
        """A pool with a window page kind (a family with sliding-window
        layers; ``decode_kv_pool`` then reads the full kind): the window
        kind's ``free`` and ``live`` pages (gauges), and ``released`` more
        pages that slots gave back as they moved past them (a counter)."""
        pass

    def decode_kv_shared(self, deployment: str, pages: int) -> None:
        """One prefix-hit admission mapped ``pages`` pool pages copy-free."""
        pass

    def decode_kv_cow(self, deployment: str, copies: int) -> None:
        """One scheduler round dispatched ``copies`` copy-on-write page
        copies (first divergent writes into shared pages)."""
        pass

    def decode_kv_reclaimed(self, deployment: str, pins: int) -> None:
        """Pool pressure reclaimed ``pins`` LRU prefix pins."""
        pass

    def decode_kv_per_device(self, deployment: str, pages: int, tp: int) -> None:
        """Allocated (live + prefix) pool pages resident on EACH mesh
        device, labeled by the tensor-parallel width: the page axis is
        unsharded (heads shard instead), so the count is pool-wide while
        per-page bytes scale 1/tp — together they read as per-device KV
        HBM. tp=1 on single-device deployments."""
        pass

    # tiered prefix-page KV (serving/kv_host_tier.py): the demand-paged
    # device -> host -> store hierarchy — bytes resident per slow tier,
    # and the page flows between tiers the capacity multiple rides on
    def decode_kv_tier_bytes(self, deployment: str, tier: str, nbytes: int) -> None:
        """Bytes resident in one slow KV tier (``tier`` = host | store)."""
        pass

    def decode_kv_demotion(self, deployment: str, tier: str, n: int) -> None:
        """``n`` prefix entries demoted INTO ``tier`` (host = device
        eviction caught by the host pool, store = host-LRU spill)."""
        pass

    def decode_kv_promotion(self, deployment: str, tier: str, n: int) -> None:
        """``n`` prefix entries promoted to the device pool FROM ``tier``
        (host | store) — each one is a warm admission the device pool
        alone would have prefilled cold."""
        pass

    def decode_kv_sibling_pull(self, deployment: str, outcome: str) -> None:
        """One cross-replica prefix pull from the key's rendezvous home
        (``outcome`` = hit | miss | error — errors degrade to cold
        prefill, never fail the request)."""
        pass

    # decode-loop flight telemetry (telemetry/flight.py + the scheduler's
    # per-round commit point): round-level device-busy/host-gap split,
    # the bubble-fraction gauge, goodput tokens, and SLO attainment
    def decode_round(self, deployment: str, busy_s: float, gap_s: float) -> None:
        """One scheduler round: ``busy_s`` device-dispatch wall time,
        ``gap_s`` the host bubble around it (admission, emission, python)."""
        pass

    def decode_bubble(self, deployment: str, fraction: float) -> None:
        """Cumulative host-bubble fraction gap/(busy+gap) — refreshed every
        ~64 rounds off the flight recorder's O(1) totals."""
        pass

    def decode_goodput(self, deployment: str, tokens: int, met: bool) -> None:
        """One retirement: ``tokens`` delivered by a request that met
        (``met``) or breached its deadline budget — goodput counts only
        the met side."""
        pass

    def decode_slo(
        self, deployment: str, kind: str, ok: bool, trace_id: str | None = None
    ) -> None:
        """One SLO attainment sample (``kind`` = ttft | itl | deadline).
        On a breach, ``trace_id`` names the flight-ring auto-dump retained
        for it; real recorders attach it as an exemplar so the breach
        counter links to the rounds surrounding the breach."""
        pass

    # multi-replica decode router (serving/affinity_router.py): routing
    # decisions by reason, per-replica queue depth the router balanced on,
    # bandit arm estimates moved by Feedback-API rewards, fleet size, and
    # warm-scale-up preseed volume
    def router_route(self, deployment: str, policy: str, reason: str) -> None:
        """One routing decision (``reason`` = affinity | shed | fallback |
        round_robin)."""
        pass

    def router_queue_depth(self, deployment: str, replica: int, depth: int) -> None:
        pass

    def router_arm(self, deployment: str, replica: int, estimate: float) -> None:
        """Reward ingestion moved one arm: its current mean-reward
        estimate (the epsilon-greedy exploit ranking)."""
        pass

    def router_replicas(self, deployment: str, n: int) -> None:
        pass

    def router_preseed(self, deployment: str, pages: int) -> None:
        """One warm scale-up/boot: prefix-pool pages pre-seeded from a
        spill."""
        pass

    # fleet health / fault tolerance (serving/affinity_router.py): the
    # replica lifecycle funnel (up -> evicted -> up, up -> draining ->
    # down) plus the failure counters chaos runs assert on
    def replica_state(self, deployment: str, replica: int, state: str) -> None:
        """Lifecycle gauge: 0=up 1=draining 2=evicted 3=down."""
        pass

    def replica_eviction(self, deployment: str) -> None:
        pass

    def replica_recovery(self, deployment: str) -> None:
        pass

    def replica_drain(self, deployment: str) -> None:
        pass

    def replica_migration(self, deployment: str, n: int) -> None:
        """n in-flight generations migrated off a dead/draining replica."""
        pass

    def replica_boot_failure(self, deployment: str) -> None:
        pass

    def replica_spill_failure(self, deployment: str) -> None:
        pass

    def compile(self, deployment: str, bucket: int, duration_s: float) -> None:
        pass

    def shadow_compare(
        self, deployment: str, predictor: str, shadow_unit: str, agree: bool
    ) -> None:
        pass

    def loop_lag(self, lag_ms: float) -> None:
        pass

    # resilience layer (engine/resilience.py): retries, breaker state,
    # deadline exhaustion, degraded responses, injected faults
    def retry(self, deployment: str, unit: str) -> None:
        pass

    def breaker(self, deployment: str, endpoint: str, state: str) -> None:
        pass

    def deadline_exceeded(self, deployment: str, unit: str) -> None:
        pass

    def degraded(self, deployment: str, mode: str) -> None:
        pass

    def fault_injected(self, deployment: str, unit: str, kind: str) -> None:
        pass

    def export(self) -> bytes:
        return b""

    def export_openmetrics(self) -> bytes:
        return b""


class Metrics(NullMetrics):
    def __init__(self, registry=None):
        if registry is None:
            registry = CollectorRegistry()
        self.registry = registry
        self._ingress = Histogram(
            "seldon_api_ingress_server_requests_duration_seconds",
            "External API request latency",
            ["deployment_name", "method"],
            registry=registry,
            buckets=_LATENCY_BUCKETS,
        )
        self._unit = Histogram(
            "seldon_api_engine_client_requests_duration_seconds",
            "Graph unit call latency",
            ["deployment_name", "predictor_name", "model_name", "method"],
            registry=registry,
            buckets=_LATENCY_BUCKETS,
        )
        self._feedback = Counter(
            "seldon_api_model_feedback",
            "Feedback events per unit",
            ["deployment_name", "predictor_name", "model_name"],
            registry=registry,
        )
        # Gauge, not Counter: rewards may be negative (bandit penalties) and
        # prometheus Counters reject negative increments
        self._feedback_reward = Gauge(
            "seldon_api_model_feedback_reward",
            "Accumulated reward per unit",
            ["deployment_name", "predictor_name", "model_name"],
            registry=registry,
        )
        self._ingress_errors = Counter(
            "seldon_api_ingress_server_errors",
            "Failed external API requests by error code",
            ["deployment_name", "method", "code"],
            registry=registry,
        )
        self._batch_size = Histogram(
            "seldon_tpu_batch_size",
            "Micro-batch sizes submitted to the device",
            ["deployment_name"],
            registry=registry,
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024),
        )
        self._queue_wait = Histogram(
            "seldon_tpu_batch_queue_wait_seconds",
            "Time requests wait in the micro-batch queue",
            ["deployment_name"],
            registry=registry,
            buckets=_LATENCY_BUCKETS,
        )
        self._compile = Histogram(
            "seldon_tpu_xla_compile_seconds",
            "XLA compilation time per batch bucket",
            ["deployment_name", "bucket"],
            registry=registry,
            buckets=(0.1, 0.5, 1, 5, 10, 30, 60, 120),
        )
        # event-loop health: how late the serving loop runs its callbacks.
        # Loop stalls (measured dominant cause: gen-2 GC pauses — see
        # serving/gc_policy.py; secondary: a tenant's host-side compute)
        # show up here BEFORE they show up as cross-tenant p99 (VERDICT r4
        # Weak #6); the alert rule in deploy/monitoring fires on the gauge.
        self._loop_lag = Gauge(
            "seldon_tpu_event_loop_lag_ms",
            "Most recent event-loop scheduling lag sample (ms)",
            registry=registry,
        )
        self._loop_lag_max = Gauge(
            "seldon_tpu_event_loop_lag_max_ms",
            "Largest event-loop scheduling lag observed since boot (ms)",
            registry=registry,
        )
        self._loop_lag_max_val = 0.0
        # generative tier (serving/decode_scheduler.py): slot occupancy per
        # step, step counter, and the two latency contracts streaming
        # clients feel — time-to-first-token and inter-token latency
        self._decode_occupancy = Gauge(
            "seldon_tpu_decode_slot_occupancy",
            "Active decode slots / total slots at the last scheduler step",
            ["deployment_name"],
            registry=registry,
        )
        self._decode_steps = Counter(
            "seldon_tpu_decode_steps_total",
            "Decode scheduler steps executed",
            ["deployment_name"],
            registry=registry,
        )
        self._decode_ttft = Histogram(
            "seldon_tpu_decode_ttft_seconds",
            "Time from request arrival to its first generated token",
            ["deployment_name"],
            registry=registry,
            buckets=_LATENCY_BUCKETS,
        )
        self._decode_itl = Histogram(
            "seldon_tpu_decode_inter_token_seconds",
            "Latency between consecutive generated tokens of one sequence",
            ["deployment_name"],
            registry=registry,
            buckets=_LATENCY_BUCKETS,
        )
        # speculative decoding: accept rate = accepted_total/proposed_total;
        # the per-dispatch histogram is the amortization actually achieved
        # (how many tokens each target dispatch paid for)
        self._spec_proposed = Counter(
            "seldon_tpu_decode_spec_proposed_total",
            "Draft tokens proposed to speculative verification",
            ["deployment_name"],
            registry=registry,
        )
        self._spec_accepted = Counter(
            "seldon_tpu_decode_spec_accepted_total",
            "Draft tokens accepted by speculative verification",
            ["deployment_name"],
            registry=registry,
        )
        self._spec_emitted = Histogram(
            "seldon_tpu_decode_spec_tokens_per_dispatch",
            "Tokens emitted per speculative verify dispatch (accepted + "
            "bonus), by round shape (mode=chain|tree)",
            ["deployment_name", "mode"],
            registry=registry,
            buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0),
        )
        # tree speculation: per-slot allowed node budget vs the accepted
        # PATH depth the walk actually reached — together they read as
        # verify-width efficiency (wide trees with short paths waste the
        # widened dispatch; the adaptive floor trims exactly that)
        self._spec_tree_nodes = Histogram(
            "seldon_tpu_decode_spec_tree_nodes",
            "Allowed candidate tree nodes per slot per tree-verify dispatch",
            ["deployment_name"],
            registry=registry,
            buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0),
        )
        self._spec_tree_path = Histogram(
            "seldon_tpu_decode_spec_tree_accepted_path_len",
            "Accepted path depth per slot per tree-verify dispatch",
            ["deployment_name"],
            registry=registry,
            buckets=(0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0),
        )
        # prefix-cache KV reuse (decode scheduler): lookup outcomes, the
        # prefill compute the pool actually displaced, eviction churn
        # (sustained evictions = the pool is too small for the workload's
        # distinct-prefix set), and TTFT split by cold/warm path
        self._prefix_lookups = Counter(
            "seldon_tpu_decode_prefix_lookups_total",
            "Prefix-cache lookups at admission by outcome",
            ["deployment_name", "outcome"],
            registry=registry,
        )
        self._prefix_saved = Counter(
            "seldon_tpu_decode_prefill_tokens_saved_total",
            "Prompt positions served from the prefix pool instead of prefill",
            ["deployment_name"],
            registry=registry,
        )
        self._prefix_evictions = Counter(
            "seldon_tpu_decode_prefix_evictions_total",
            "Prefix pool rows recycled by LRU eviction",
            ["deployment_name"],
            registry=registry,
        )
        # paged KV pool: page occupancy by class + share/CoW/reclaim events
        self._kv_pages_free = Gauge(
            "seldon_tpu_decode_kv_pages_free",
            "Unallocated pages in the decode KV page pool",
            ["deployment_name"],
            registry=registry,
        )
        self._kv_pages_live = Gauge(
            "seldon_tpu_decode_kv_pages_live",
            "KV pool pages referenced by at least one live decode slot",
            ["deployment_name"],
            registry=registry,
        )
        self._kv_pages_prefix = Gauge(
            "seldon_tpu_decode_kv_pages_prefix",
            "KV pool pages held only by prefix-cache pins (reclaimable)",
            ["deployment_name"],
            registry=registry,
        )
        self._kv_win_free = Gauge(
            "seldon_tpu_decode_kv_window_pages_free",
            "Unallocated window-kind pages (sliding-window layers' pages) in the decode KV pool",
            ["deployment_name"],
            registry=registry,
        )
        self._kv_win_live = Gauge(
            "seldon_tpu_decode_kv_window_pages_live",
            "Window-kind KV pool pages mapped by at least one live decode slot",
            ["deployment_name"],
            registry=registry,
        )
        self._kv_win_released = Counter(
            "seldon_tpu_decode_kv_window_pages_released_total",
            "Window-kind pages slots gave back as they moved past them, before retiring",
            ["deployment_name"],
            registry=registry,
        )
        self._kv_shared = Counter(
            "seldon_tpu_decode_kv_pages_shared_total",
            "Pool pages mapped copy-free into admitted slots off prefix hits",
            ["deployment_name"],
            registry=registry,
        )
        self._kv_cow = Counter(
            "seldon_tpu_decode_kv_cow_copies_total",
            "Copy-on-write page copies (first divergent write into a shared page)",
            ["deployment_name"],
            registry=registry,
        )
        self._kv_reclaimed = Counter(
            "seldon_tpu_decode_kv_pins_reclaimed_total",
            "Prefix pins reclaimed LRU-first under pool allocation pressure",
            ["deployment_name"],
            registry=registry,
        )
        self._kv_per_device = Gauge(
            "seldon_tpu_decode_kv_pages_per_device",
            "Allocated KV pool pages resident per mesh device (page bytes "
            "scale 1/tp under tensor-parallel head sharding)",
            ["deployment_name", "tp"],
            registry=registry,
        )
        # tiered prefix-page KV (serving/kv_host_tier.py): slow-tier
        # residency and the inter-tier page flows
        self._kv_tier_bytes = Gauge(
            "seldon_tpu_decode_kv_tier_bytes",
            "Bytes of demoted prefix KV resident per slow tier (host|store)",
            ["deployment_name", "tier"],
            registry=registry,
        )
        self._kv_demotions = Counter(
            "seldon_tpu_decode_kv_demotions_total",
            "Prefix entries demoted into a slow KV tier (host|store)",
            ["deployment_name", "tier"],
            registry=registry,
        )
        self._kv_promotions = Counter(
            "seldon_tpu_decode_kv_promotions_total",
            "Prefix entries promoted to the device pool from a slow tier",
            ["deployment_name", "tier"],
            registry=registry,
        )
        self._kv_sibling_pulls = Counter(
            "seldon_tpu_decode_kv_sibling_pulls_total",
            "Cross-replica prefix pulls from the rendezvous home "
            "(outcome=hit|miss|error)",
            ["deployment_name", "outcome"],
            registry=registry,
        )
        # decode-loop flight telemetry: where each round's wall time went
        # (device busy vs host bubble), the cumulative bubble fraction, and
        # the goodput/SLO-attainment contract the ROADMAP's SLO-tiered
        # scheduling + reward-driven routing consume
        self._decode_round_busy = Histogram(
            "seldon_tpu_decode_round_device_seconds",
            "Device-dispatch wall time per decode scheduler round",
            ["deployment_name"],
            registry=registry,
            buckets=_LATENCY_BUCKETS,
        )
        self._decode_round_gap = Histogram(
            "seldon_tpu_decode_round_host_gap_seconds",
            "Host bubble per decode scheduler round: round wall minus the dispatch "
            "wall (host call to readback return). The dispatch wall holds the "
            "launch and return legs and is not device-busy time",
            ["deployment_name"],
            registry=registry,
            buckets=_LATENCY_BUCKETS,
        )
        self._decode_bubble = Gauge(
            "seldon_tpu_decode_bubble_fraction",
            "Cumulative host gap between dispatches over decode round wall time. The "
            "dispatch wall holds the launch and return legs, in which the device "
            "also idles: not the device's idle share",
            ["deployment_name"],
            registry=registry,
        )
        self._decode_goodput = Counter(
            "seldon_tpu_decode_goodput_tokens_total",
            "Generated tokens by whether the request met its deadline budget",
            ["deployment_name", "outcome"],
            registry=registry,
        )
        self._decode_slo = Counter(
            "seldon_tpu_decode_slo_attainment_total",
            "Decode SLO attainment samples (kind=ttft|itl|deadline); breach "
            "samples carry the flight-dump trace id as an exemplar",
            ["deployment_name", "kind", "outcome"],
            registry=registry,
        )
        self._decode_ttft_split = Histogram(
            "seldon_tpu_decode_ttft_split_seconds",
            "TTFT split by admission path (warm = prefix hit, cold = full prefill)",
            ["deployment_name", "path"],
            registry=registry,
            buckets=_LATENCY_BUCKETS,
        )
        # multi-replica decode router (serving/affinity_router.py)
        self._router_routes = Counter(
            "seldon_tpu_router_routes_total",
            "Decode-replica routing decisions "
            "(reason=affinity|shed|fallback|round_robin)",
            ["deployment_name", "policy", "reason"],
            registry=registry,
        )
        self._router_queue_depth = Gauge(
            "seldon_tpu_router_queue_depth",
            "Per-replica load (queue depth + active slots) the router "
            "last balanced on",
            ["deployment_name", "replica"],
            registry=registry,
        )
        self._router_arm = Gauge(
            "seldon_tpu_router_arm_estimate",
            "Per-replica bandit arm mean-reward estimate (moved by "
            "Feedback-API rewards / automatic SLO verdicts)",
            ["deployment_name", "replica"],
            registry=registry,
        )
        self._router_replicas = Gauge(
            "seldon_tpu_router_replicas",
            "Live decode replicas behind the router (autoscale moves it)",
            ["deployment_name"],
            registry=registry,
        )
        self._router_preseed = Counter(
            "seldon_tpu_router_preseeded_pages_total",
            "Prefix-pool pages pre-seeded into warm-booted replicas",
            ["deployment_name"],
            registry=registry,
        )
        # fleet health / fault tolerance (serving/affinity_router.py): the
        # replica lifecycle funnel plus the counters chaos runs assert on
        self._replica_state = Gauge(
            "seldon_tpu_replica_state",
            "Decode replica lifecycle state (0=up 1=draining 2=evicted 3=down)",
            ["deployment_name", "replica"],
            registry=registry,
        )
        self._replica_evictions = Counter(
            "seldon_tpu_replica_evictions_total",
            "Decode replicas evicted from routing (health breaker opened)",
            ["deployment_name"],
            registry=registry,
        )
        self._replica_recoveries = Counter(
            "seldon_tpu_replica_recoveries_total",
            "Evicted decode replicas readmitted via half-open probe",
            ["deployment_name"],
            registry=registry,
        )
        self._replica_drains = Counter(
            "seldon_tpu_replica_drains_total",
            "Decode replicas gracefully drained and released",
            ["deployment_name"],
            registry=registry,
        )
        self._replica_migrations = Counter(
            "seldon_tpu_replica_migrations_total",
            "In-flight generations migrated off dead/draining replicas",
            ["deployment_name"],
            registry=registry,
        )
        self._replica_boot_failures = Counter(
            "seldon_tpu_replica_boot_failures_total",
            "Scale-up replica boots that failed",
            ["deployment_name"],
            registry=registry,
        )
        self._replica_spill_failures = Counter(
            "seldon_tpu_replica_spill_failures_total",
            "Prefix-spill store/preseed round-trips that failed",
            ["deployment_name"],
            registry=registry,
        )
        # SHADOW router candidate validation: per-shadow-child prediction
        # agreement with the primary (argmax match on classifier outputs)
        self._shadow = Counter(
            "seldon_tpu_shadow_comparisons",
            "Shadow-vs-primary output comparisons",
            ["deployment_name", "predictor_name", "shadow_unit", "agree"],
            registry=registry,
        )
        # resilience layer (engine/resilience.py): these four are the
        # observable proof of the chaos acceptance test — retries absorbed,
        # breakers opening/half-open-recovering, budgets exhausted, and
        # requests served degraded instead of failed
        self._retries = Counter(
            "seldon_tpu_retries_total",
            "Unit-call retry attempts dispatched",
            ["deployment_name", "model_name"],
            registry=registry,
        )
        self._breaker_transitions = Counter(
            "seldon_tpu_breaker_transitions_total",
            "Circuit breaker state transitions per endpoint",
            ["deployment_name", "endpoint", "state"],
            registry=registry,
        )
        self._breaker_state = Gauge(
            "seldon_tpu_breaker_state",
            "Current breaker state per endpoint (0=closed 1=half_open 2=open)",
            ["deployment_name", "endpoint"],
            registry=registry,
        )
        self._deadline_exceeded = Counter(
            "seldon_tpu_deadline_exceeded_total",
            "Requests whose deadline budget ran out, by the unit reached",
            ["deployment_name", "model_name"],
            registry=registry,
        )
        self._degraded = Counter(
            "seldon_tpu_degraded_responses_total",
            "Responses served degraded (router_fallback | quorum)",
            ["deployment_name", "mode"],
            registry=registry,
        )
        self._faults = Counter(
            "seldon_tpu_faults_injected_total",
            "Faults injected by the chaos harness (engine/faults.py)",
            ["deployment_name", "model_name", "kind"],
            registry=registry,
        )

    def ingress_request(self, deployment, method, duration_s, trace_id=None):
        h = self._ingress.labels(deployment, method)
        if trace_id:
            # trace exemplar on the histogram bucket: OpenMetrics scrapes
            # (export_openmetrics / /metrics?format=openmetrics) surface it
            # so a dashboard's slow sample links straight to GET /traces/{id}
            try:
                h.observe(duration_s, exemplar={"trace_id": trace_id})
                return
            except (TypeError, ValueError):  # older client / invalid exemplar
                pass
        h.observe(duration_s)

    def ingress_error(self, deployment, method, code):
        self._ingress_errors.labels(deployment, method, str(code)).inc()

    def unit_call(self, deployment, predictor, unit, method, duration_s):
        self._unit.labels(deployment, predictor, unit, method).observe(duration_s)

    def feedback(self, deployment, predictor, unit, reward):
        self._feedback.labels(deployment, predictor, unit).inc()
        self._feedback_reward.labels(deployment, predictor, unit).inc(reward)

    def batch(self, deployment, size, queue_waits_s):
        self._batch_size.labels(deployment).observe(size)
        # the queue-wait histogram is PER REQUEST: every batch-mate's wait
        # is observed, not just the first item's (which under-reported the
        # wait of everyone coalesced behind it)
        if isinstance(queue_waits_s, (int, float)):
            queue_waits_s = (queue_waits_s,)
        h = self._queue_wait.labels(deployment)
        for w in queue_waits_s:
            h.observe(w)

    def decode_step(self, deployment, active, slots):
        self._decode_occupancy.labels(deployment).set(active / slots if slots else 0.0)
        self._decode_steps.labels(deployment).inc()

    def decode_ttft(self, deployment, duration_s):
        self._decode_ttft.labels(deployment).observe(duration_s)

    def decode_inter_token(self, deployment, duration_s):
        self._decode_itl.labels(deployment).observe(duration_s)

    def decode_spec(self, deployment, proposed, accepted, emitted, mode="chain"):
        self._spec_proposed.labels(deployment).inc(proposed)
        self._spec_accepted.labels(deployment).inc(accepted)
        self._spec_emitted.labels(deployment, mode).observe(emitted)

    def decode_spec_tree(self, deployment, nodes, path_len):
        self._spec_tree_nodes.labels(deployment).observe(nodes)
        self._spec_tree_path.labels(deployment).observe(path_len)

    def decode_prefix(self, deployment, hit, tokens_saved):
        self._prefix_lookups.labels(deployment, "hit" if hit else "miss").inc()
        if tokens_saved > 0:
            self._prefix_saved.labels(deployment).inc(tokens_saved)

    def decode_prefix_evicted(self, deployment):
        self._prefix_evictions.labels(deployment).inc()

    def decode_ttft_split(self, deployment, duration_s, path):
        self._decode_ttft_split.labels(deployment, path).observe(duration_s)

    def decode_kv_pool(self, deployment, free, live, prefix):
        self._kv_pages_free.labels(deployment).set(free)
        self._kv_pages_live.labels(deployment).set(live)
        self._kv_pages_prefix.labels(deployment).set(prefix)

    def decode_kv_window_pool(self, deployment, free, live, released):
        self._kv_win_free.labels(deployment).set(free)
        self._kv_win_live.labels(deployment).set(live)
        if released > 0:
            self._kv_win_released.labels(deployment).inc(released)

    def decode_kv_shared(self, deployment, pages):
        if pages > 0:
            self._kv_shared.labels(deployment).inc(pages)

    def decode_kv_cow(self, deployment, copies):
        if copies > 0:
            self._kv_cow.labels(deployment).inc(copies)

    def decode_kv_reclaimed(self, deployment, pins):
        if pins > 0:
            self._kv_reclaimed.labels(deployment).inc(pins)

    def decode_kv_per_device(self, deployment, pages, tp):
        self._kv_per_device.labels(deployment, str(tp)).set(pages)

    def decode_kv_tier_bytes(self, deployment, tier, nbytes):
        self._kv_tier_bytes.labels(deployment, tier).set(nbytes)

    def decode_kv_demotion(self, deployment, tier, n):
        if n > 0:
            self._kv_demotions.labels(deployment, tier).inc(n)

    def decode_kv_promotion(self, deployment, tier, n):
        if n > 0:
            self._kv_promotions.labels(deployment, tier).inc(n)

    def decode_kv_sibling_pull(self, deployment, outcome):
        self._kv_sibling_pulls.labels(deployment, outcome).inc()

    def decode_round(self, deployment, busy_s, gap_s):
        self._decode_round_busy.labels(deployment).observe(busy_s)
        self._decode_round_gap.labels(deployment).observe(gap_s)

    def decode_bubble(self, deployment, fraction):
        self._decode_bubble.labels(deployment).set(fraction)

    def decode_goodput(self, deployment, tokens, met):
        if tokens > 0:
            self._decode_goodput.labels(
                deployment, "met" if met else "breached"
            ).inc(tokens)

    def decode_slo(self, deployment, kind, ok, trace_id=None):
        c = self._decode_slo.labels(deployment, kind, "ok" if ok else "breach")
        if trace_id and not ok:
            # exemplar: the breach-adjacent flight-ring dump's trace id —
            # an OpenMetrics scrape links the breach straight to
            # GET /traces/{id} (same mechanism as the ingress histogram)
            try:
                c.inc(exemplar={"trace_id": trace_id})
                return
            except (TypeError, ValueError):  # older client / invalid exemplar
                pass
        c.inc()

    def router_route(self, deployment, policy, reason):
        self._router_routes.labels(deployment, policy, reason).inc()

    def router_queue_depth(self, deployment, replica, depth):
        self._router_queue_depth.labels(deployment, str(replica)).set(depth)

    def router_arm(self, deployment, replica, estimate):
        self._router_arm.labels(deployment, str(replica)).set(estimate)

    def router_replicas(self, deployment, n):
        self._router_replicas.labels(deployment).set(n)

    def router_preseed(self, deployment, pages):
        self._router_preseed.labels(deployment).inc(pages)

    def replica_state(self, deployment, replica, state):
        from seldon_core_tpu.serving.affinity_router import replica_state_value

        self._replica_state.labels(deployment, str(replica)).set(
            replica_state_value(state)
        )

    def replica_eviction(self, deployment):
        self._replica_evictions.labels(deployment).inc()

    def replica_recovery(self, deployment):
        self._replica_recoveries.labels(deployment).inc()

    def replica_drain(self, deployment):
        self._replica_drains.labels(deployment).inc()

    def replica_migration(self, deployment, n):
        if n > 0:
            self._replica_migrations.labels(deployment).inc(n)

    def replica_boot_failure(self, deployment):
        self._replica_boot_failures.labels(deployment).inc()

    def replica_spill_failure(self, deployment):
        self._replica_spill_failures.labels(deployment).inc()

    def compile(self, deployment, bucket, duration_s):
        self._compile.labels(deployment, str(bucket)).observe(duration_s)

    def shadow_compare(self, deployment, predictor, shadow_unit, agree):
        self._shadow.labels(
            deployment, predictor, shadow_unit, "true" if agree else "false"
        ).inc()

    def loop_lag(self, lag_ms):
        self._loop_lag.set(lag_ms)
        if lag_ms > self._loop_lag_max_val:
            self._loop_lag_max_val = lag_ms
            self._loop_lag_max.set(lag_ms)

    def retry(self, deployment, unit):
        self._retries.labels(deployment, unit).inc()

    def breaker(self, deployment, endpoint, state):
        from seldon_core_tpu.engine.resilience import breaker_state_value

        self._breaker_transitions.labels(deployment, endpoint, state).inc()
        self._breaker_state.labels(deployment, endpoint).set(breaker_state_value(state))

    def deadline_exceeded(self, deployment, unit):
        self._deadline_exceeded.labels(deployment, unit).inc()

    def degraded(self, deployment, mode):
        self._degraded.labels(deployment, mode).inc()

    def fault_injected(self, deployment, unit, kind):
        self._faults.labels(deployment, unit, kind).inc()

    def export(self) -> bytes:
        return generate_latest(self.registry)

    def export_openmetrics(self) -> bytes:
        """OpenMetrics text exposition — the format that carries exemplars
        (the classic Prometheus text format silently drops them). Falls
        back to the classic exposition if the client lacks the module."""
        try:
            from prometheus_client.openmetrics.exposition import (
                generate_latest as om_latest,
            )
        except Exception:  # noqa: BLE001 - optional in older clients
            return self.export()
        return om_latest(self.registry)


class MetricsResilienceEvents:
    """Adapter: the executor's ResilienceEvents contract -> the registry.
    Servers construct one per deployment and hand it to build_executor."""

    def __init__(self, metrics: NullMetrics, deployment: str):
        self._metrics = metrics
        self._deployment = deployment

    def retry(self, unit: str, attempt: int) -> None:
        self._metrics.retry(self._deployment, unit)

    def breaker_transition(self, endpoint: str, state: str) -> None:
        self._metrics.breaker(self._deployment, endpoint, state)

    def deadline_exceeded(self, unit: str) -> None:
        self._metrics.deadline_exceeded(self._deployment, unit)

    def degraded(self, unit: str, mode: str) -> None:
        self._metrics.degraded(self._deployment, mode)

    def fault_injected(self, unit: str, kind: str) -> None:
        self._metrics.fault_injected(self._deployment, unit, kind)


async def run_loop_lag_probe(
    metrics: NullMetrics, interval_s: float = 0.5, sample_s: float = 0.05
) -> None:
    """Sample event-loop scheduling lag forever: sleep ``sample_s`` and
    report how late the wakeup fired. Servers spawn this as a task and
    cancel it on stop. The lag a tiny sleep observes is exactly the delay
    every other coroutine (other tenants' requests) is experiencing."""
    import asyncio
    import time

    while True:
        t0 = time.perf_counter()
        await asyncio.sleep(sample_s)
        lag_ms = max(0.0, (time.perf_counter() - t0 - sample_s) * 1e3)
        metrics.loop_lag(lag_ms)
        await asyncio.sleep(interval_s)


def get_metrics(enabled: bool = True) -> NullMetrics:
    if enabled and HAVE_PROMETHEUS:
        return Metrics()
    return NullMetrics()
