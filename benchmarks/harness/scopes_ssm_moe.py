"""What the hybrid family's programs carry since PR 51 for a configuration
whose layers are single sublayers with expert layers among them, from the
same trace and the same frames: the Mamba-2 mixer's ``ssm_*`` scopes
(``harness/scopes_ssm.py`` reads them, whatever the configuration), the
expert layer's ``mlp/moe_*`` and ``mlp/shared_expert`` (``ops/moe.py``'s
names; ``harness/scopes_moe.py`` and ``harness/scopes_win.py`` read them
without asking for another family's scopes beside them), and the step
dispatch's own counts in the flight frames (``step_counts``, in the family's
``frame_counters`` order). The sizes come from THIS configuration's key names
(``hybrid_override_pattern``, ``mamba_num_heads``, ``n_groups``,
``moe_intermediate_size``, ...): a configuration without them, and a program
that counts no experts beside its state rows (the parent of PR 51, granite),
give None everywhere.
"""

from __future__ import annotations

from harness.scopes_conv import step_ctx_tokens  # noqa: F401  (the step readers' one estimate of the context)
from harness.scopes_moe import MOE
from harness.scopes_moe import nested_ms as moe_nested_ms
from harness.scopes_ssm import nested_ms as ssm_nested_ms  # noqa: F401  (the readers' one import)
from harness.scopes_win import nested_ms as shared_nested_ms

# the hybrid family's ``frame_counters`` with expert layers (models/hybrid_decoder.py)
COUNTED = ("moe_rows", "moe_experts_hit", "moe_load_max", "moe_local_picks", "moe_grouped_calls",
           "moe_compact_calls", "ssm_rows", "attn_run_pages")


def published(o: dict) -> dict | None:
    """The sizes the counts need, from the configuration's published keys
    (``n_routed_experts`` there is the experts HELD; ``published`` has the
    router's width); None for a configuration of other keys."""
    c, g = o["config"], o["geometry"]
    if "hybrid_override_pattern" not in c or "share" not in c:
        return None
    kinds = c["hybrid_override_pattern"][: g["layers"]]
    return {
        "hidden": g["hidden"], "vocab": g["vocab"],
        "ssm_layers": kinds.count("M"), "attn_layers": kinds.count("*"), "expert_layers": kinds.count("E"),
        "heads": int(c["num_attention_heads"]), "kv_heads": int(c["num_key_value_heads"]), "head_dim": int(c["head_dim"]),
        "ssm_heads": int(c["mamba_num_heads"]), "ssm_head_dim": int(c["mamba_head_dim"]),
        "ssm_state": int(c["ssm_state_size"]), "ssm_conv": int(c["conv_kernel"]), "ssm_groups": int(c["n_groups"]),
        "ffn": int(c["moe_intermediate_size"]), "shared_ffn": int(c["moe_shared_expert_intermediate_size"]),
        "experts": int(c["published"]["n_routed_experts"]), "held": int(c["n_routed_experts"]),
        "per_tok": int(c["num_experts_per_tok"]),
    }


def step_means(o: dict) -> dict | None:
    """{"rows", "experts_hit", "load_max", "local_picks"}: means over the
    window's fused STEPS of a program that counts held experts beside its
    state rows: a frame's ``step_counts`` (the step dispatch's own counts:
    most rounds of this cell ride a chunk too, whose counts the round's sums
    include). ``rows`` is the rows whose state the step advanced (the slots
    that generate); the expert counts are summed over the expert layers,
    over the experts HELD."""
    steps = [dict(zip(COUNTED, f.step_counts)) for f in o.get("frames") or []
             if f.mode == "plain" and f.busy_ns[1] > 0 and len(getattr(f, "step_counts", ())) == len(COUNTED)
             and getattr(f, "ssm_rows", 0) and getattr(f, "moe_rows", 0)]
    if not steps:
        return None
    mean = lambda k: sum(s[k] for s in steps) / len(steps)  # noqa: E731
    return {"rows": mean("ssm_rows"), "experts_hit": mean("moe_experts_hit"), "load_max": mean("moe_load_max"),
            "local_picks": mean("moe_local_picks")}


def moe_ms(o: dict, program: str) -> float | None:
    """Device time per ``program`` dispatch in the routed part of the expert
    layers (router, dispatch, the held experts' products, combine), ms; None
    for a configuration of other keys."""
    return moe_nested_ms(o, program, *MOE) if published(o) else None


def shared_ms(o: dict, program: str) -> float | None:
    """Device time per ``program`` dispatch under ``mlp/shared_expert``, ms."""
    return shared_nested_ms(o, program, "shared_expert") if published(o) else None
