"""What the hybrid family's programs carry since PR 57 for a configuration of
its third shape (a gated delta-rule or gated attention mixer AND an expert
layer in every layer), from the same trace and the same frames: the delta
rule's nested scopes (``qkv/gdn_in``, ``attn/gdn_conv``, ``attn/gdn_scan``
with the state rows' read and write, ``attn_out/gdn_norm``,
``attn_out/gdn_out``) and the gated attention's (``qkv/rope``,
``attn_out/attn_gate``), read here with the self-time rule of
``harness/scopes_ssm.py`` (whose loop this repeats with its own names); the
expert layer's ``mlp/moe_*`` and ``mlp/shared_expert`` (``ops/moe.py``'s
names: ``harness/scopes_moe.py`` and ``harness/scopes_win.py`` read them);
the step dispatch's own counts in the flight frames (``step_counts``, the
eight of ``harness/scopes_ssm_moe.COUNTED``: the family's ``frame_counters``
are the same with these expert layers as with Nemotron-H's) and a chunk
dispatch's entry (``chunk_rows`` x ``chunk_c``). The sizes come from THIS
configuration's key names (``full_attention_interval``,
``linear_num_value_heads``, ...): a configuration without them, and a
program without the scopes (the parent of PR 57, which cannot build this
configuration at all), give None everywhere.
"""

from __future__ import annotations

import bisect
import functools

from harness import scopes as sc
from harness.scopes_conv import step_ctx_tokens  # noqa: F401  (the step readers' one estimate of the context)
from harness.scopes_moe import MOE
from harness.scopes_moe import nested_ms as moe_nested_ms
from harness.scopes_ssm_moe import step_means  # noqa: F401  (the same eight counts a step)
from harness.scopes_win import nested_ms as shared_nested_ms
from harness.trace import TRACE_DIR, WINDOW, newest_xplane

GDN = ("gdn_in", "gdn_conv", "gdn_scan", "gdn_norm", "gdn_out", "attn_gate")
STEP_MARK, CHUNK_MARK = "fused_step", "fused_chunk"


def nested_key(op_name: str) -> str | None:
    """The ``gdn_*`` / ``attn_gate`` component of an op's path; None for an op under none."""
    return next((p for p in op_name.rstrip(":").split("/") if p in GDN), None)


def by_nested(events: dict, mark: str) -> dict | None:
    """Op self time inside whole ``mark`` dispatches of the slice, by nested
    key. None where the slice holds no such dispatch or no op of it carries
    a nested name. Keys: ``dispatches``, ``by`` {key: s}."""
    win = [e for e in events["host"] if e[0] == WINDOW]
    if not win:
        return None
    t0, t1 = win[0][1], win[0][1] + win[0][2]
    dispatches, by = 0, {}
    for plane in sorted(events["devices"]):
        dev = events["devices"][plane]
        mods = sorted((s, s + d) for name, s, d in dev["modules"] if mark in name and t0 <= s and s + d <= t1)
        begins = [a for a, _ in mods]
        inside: list[list] = [[] for _ in mods]
        for label, s, d, op_name in dev["ops"]:
            i = bisect.bisect_right(begins, s) - 1
            if i >= 0 and s + d <= mods[i][1] + 1e-9:
                inside[i].append((s, -d, d, op_name, label))
        if not any(inside):
            continue
        dispatches += len(mods)
        for ops in inside:
            for _start, own, op_name, _label in sc._self_times(ops):
                key = nested_key(op_name)
                if key is not None:
                    by[key] = by.get(key, 0.0) + own
    return {"dispatches": dispatches, "by": by} if dispatches and by else None


@functools.lru_cache(maxsize=1)
def _of_file(path: str) -> dict:
    events = sc.read_scoped(path)
    return {"step": by_nested(events, STEP_MARK), "chunk": by_nested(events, CHUNK_MARK)}


def nested_ms(o: dict, program: str, *keys: str) -> float | None:
    """Device time per ``program`` ("step" / "chunk") dispatch in ops under
    the given nested keys, ms; None on an untraced run or where none was found."""
    if not o.get("trace"):
        return None
    r = _of_file(newest_xplane(TRACE_DIR))[program]
    if not r or not any(k in r["by"] for k in keys):
        return None
    return 1e3 * sum(r["by"].get(k, 0.0) for k in keys) / r["dispatches"]


def published(o: dict) -> dict | None:
    """The sizes the counts need, from the configuration's published keys
    (``num_experts`` there is the experts HELD; ``published`` has the
    router's width); None for a configuration of other keys."""
    c, g = o["config"], o["geometry"]
    if "full_attention_interval" not in c or "linear_num_value_heads" not in c or "share" not in c:
        return None
    attn = g["layers"] // int(c["full_attention_interval"])
    return {
        "hidden": g["hidden"], "vocab": g["vocab"], "gdn_layers": g["layers"] - attn, "attn_layers": attn,
        "expert_layers": g["layers"],
        "heads": int(c["num_attention_heads"]), "kv_heads": int(c["num_key_value_heads"]), "head_dim": int(c["head_dim"]),
        "key_heads": int(c["linear_num_key_heads"]), "value_heads": int(c["linear_num_value_heads"]),
        "key_dim": int(c["linear_key_head_dim"]), "value_dim": int(c["linear_value_head_dim"]),
        "conv": int(c["linear_conv_kernel_dim"]),
        "ffn": int(c["moe_intermediate_size"]), "shared_ffn": int(c["shared_expert_intermediate_size"]),
        "experts": int(c["published"]["num_experts"]), "held": int(c["num_experts"]),
        "per_tok": int(c["num_experts_per_tok"]),
    }


def scan_sizes(p: dict) -> dict:
    """The keys of ``published`` that the delta rule's own counts take."""
    return {k: p[k] for k in ("gdn_layers", "key_heads", "value_heads", "key_dim", "value_dim", "conv")}


def chunk_entry_means(o: dict) -> dict | None:
    """{"rows", "tokens"}: the mean live rows of the window's chunk dispatches
    and the mean real tokens a dispatch (live rows x the entry's length: an
    upper bound where a row's last chunk is short; this cell's chunks are
    whole), from the frames' ``chunk_rows_live`` / ``chunk_c``. None for a
    program whose frames lack them."""
    fs = [f for f in o.get("frames") or [] if getattr(f, "chunk_rows", 0) and getattr(f, "chunk_c", 0)]
    if not fs:
        return None
    return {"rows": sum(f.chunk_rows_live for f in fs) / len(fs),
            "tokens": sum(f.chunk_rows_live * f.chunk_c for f in fs) / len(fs)}


def moe_ms(o: dict, program: str) -> float | None:
    """Device time per ``program`` dispatch in the routed part of the expert
    layers (router, dispatch, the held experts' products, combine), ms; None
    for a configuration of other keys."""
    return moe_nested_ms(o, program, *MOE) if published(o) else None


def shared_ms(o: dict, program: str) -> float | None:
    """Device time per ``program`` dispatch under ``mlp/shared_expert`` (the
    expert and its one-scalar gate), ms."""
    return shared_nested_ms(o, program, "shared_expert") if published(o) else None
