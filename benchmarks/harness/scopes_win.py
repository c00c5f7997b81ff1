"""What the sparse-expert family's programs carry since PR 47, from the same
trace and the same frames: the per-head output gate's scope
(``attn_out/gate``), the shared expert and the dense layer under ``mlp`` by
``ops/moe.py``'s names, and the window page kind's counts in the flight
frames (``kv_win_live`` / ``kv_win_released`` / ``kv_win_written``) and the
step dispatch's own routing counts (``step_counts``). The
``win/*``, ``full/*`` and ``mlp/moe_*`` scopes are ``harness/scopes_moe.py``'s
to read. A program without the names (the other families, the parent of
PR 47) gives None everywhere.
"""

from __future__ import annotations

import bisect
import functools

from harness import scopes as sc
from harness.trace import TRACE_DIR, WINDOW, newest_xplane

NAMES = ("gate", "shared_expert", "dense")
STEP_MARK, CHUNK_MARK = "fused_step", "fused_chunk"


def nested_key(op_name: str) -> str | None:
    """``gate`` for an op under ``attn_out/gate``, ``shared_expert`` / ``dense``
    under ``mlp``; None for an op under none of them."""
    parts = op_name.rstrip(":").split("/")
    for outer, inner in (("attn_out", "gate"), ("mlp", "shared_expert"), ("mlp", "dense")):
        if outer in parts and inner in parts[parts.index(outer) + 1:]:
            return inner
    return None


def by_nested(events: dict, mark: str) -> dict | None:
    """Op self time inside whole ``mark`` dispatches of the slice, by nested
    key (``harness/scopes_moe.by_nested`` with this file's names). Keys:
    ``dispatches``, ``by`` {key: s}."""
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
    the given keys, ms; None on an untraced run or where none was found."""
    if not o.get("trace"):
        return None
    r = _of_file(newest_xplane(TRACE_DIR))[program]
    if not r or not any(k in r["by"] for k in keys):
        return None
    return 1e3 * sum(r["by"].get(k, 0.0) for k in keys) / r["dispatches"]


# --------------------------------------------- what the program counted itself


def window_frames(o: dict) -> list:
    """The window's frames of a pool with the window page kind."""
    return [f for f in o.get("frames") or [] if getattr(f, "kv_win_live", None) is not None]


def step_means(o: dict) -> dict | None:
    """{"rows", "experts_hit", "load_max", "local_picks"}: means over the
    window's fused STEPS of a sparse-expert program: a frame's
    ``step_counts`` (the step dispatch's own counts, in the family's
    ``frame_counters`` order) where the program gives them, else the sums of
    a round that ran a step and no chunk (``harness/scopes_moe.step_frames``:
    all a tree before PR 47 has; a cell whose every round rides a chunk has
    no such round)."""
    steps = []
    for f in o.get("frames") or []:
        if f.mode != "plain" or not f.busy_ns[1] > 0 or not getattr(f, "moe_rows", 0):
            continue
        own = getattr(f, "step_counts", ())
        if own:
            steps.append(tuple(own) + (0,) * (4 - len(own)))
        elif f.busy_ns[0] == 0:
            steps.append((f.moe_rows, f.moe_experts_hit, f.moe_load_max, getattr(f, "moe_local_picks", 0)))
    if not steps:
        return None
    rows, hit, load, picks = (sum(s[i] for s in steps) / len(steps) for i in range(4))
    return {"rows": rows, "experts_hit": hit, "load_max": load, "local_picks": picks}


def held_share(o: dict) -> dict | None:
    """{"layers", "dense_layers", "held", "experts", "per_tok", ...}: the sizes
    the held-share counts need, from the configuration's published keys
    (``num_experts`` there is the experts HELD, ``published`` has the
    router's width); None for a configuration that holds all its experts."""
    c, g = o["config"], o["geometry"]
    if "share" not in c or "num_attention_heads_per_layer" not in c:
        return None
    n = g["layers"]
    kinds = c["layer_types"][:n]
    return {
        "hidden": g["hidden"], "layers": n, "ffn": g["ffn"], "vocab": g["vocab"],
        "heads_by_layer": [int(h) for h in c["num_attention_heads_per_layer"][:n]],
        "full_by_layer": [k == "full_attention" for k in kinds],
        "kv_heads": int(c["num_key_value_heads"]), "head_dim": int(c["head_dim"]),
        "dense_layers": len([i for i in c["mlp_only_layers"] if i < n]), "dense_ffn": int(c["intermediate_size"]),
        "experts": int(c["published"]["num_experts"]), "held": int(c["num_experts"]),
        "per_tok": int(c["num_experts_per_tok"]), "window": int(c["sliding_window"]),
        "gated": c.get("gating") == "per-head",
    }
