"""The latent-attention family's NESTED device scopes, from the same trace, and
what its programs counted themselves.

``harness/scopes.py`` gives a fused step's op time to the first component of
an op's HLO ``op_name`` that is one of its nine scopes. The latent-attention
decoder (``models/mla_decoder.py``, ``ops/mla.py``, ``ops/moe.py``) nests finer
names under those, so the old readers still see its time: ``qkv/mla_q``,
``qkv/mla_kv``, ``qkv/rope``, ``attn/mla_absorb`` (the Wuk and Wuv products),
``attn/mla_core`` (the walk over the latent rows: page fetches, scores,
softmax, context), ``attn/mla_expand`` (kv_b over a block of cached rows, in a
long chunk; it lies INSIDE the walk's loop, so an op's key is the INNERMOST of
these names on its path), ``mlp/moe_*`` as ``ops/moe.py`` has them,
``mlp/shared_expert``, ``mlp/dense``. This file reads the finer names, in
whole dispatches of the step or of the chunk, with the self-time rule of
``harness/scopes.py``. A program without them (the other families, the parent
of PR 37) gives None.
"""

from __future__ import annotations

import bisect
import functools

from harness import scopes as sc
from harness.trace import TRACE_DIR, WINDOW, newest_xplane

ATTN = ("mla_absorb", "mla_core", "mla_expand")
NAMES = ATTN + ("mla_q", "mla_kv", "rope", "shared_expert", "dense",
                "moe_router", "moe_dispatch", "moe_experts", "moe_combine")
STEP_MARK, CHUNK_MARK = "fused_step", "fused_chunk"


def nested_key(op_name: str) -> str | None:
    """The innermost of NAMES on an op's path; None for an op under none."""
    return next((p for p in reversed(op_name.rstrip(":").split("/")) if p in NAMES), None)


def by_nested(events: dict, mark: str) -> dict | None:
    """Op self time inside whole ``mark`` dispatches of the slice, by nested
    key. None where the slice holds no such dispatch or no op of it carries
    one of the ``mla_*`` names (the expert layer's alone are another
    family's). Keys: ``dispatches``, ``by`` {key: s}."""
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
    return {"dispatches": dispatches, "by": by} if dispatches and any(k in by for k in ATTN) else None


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


# --------------------------------------------- what the program counted itself


def step_frames(o: dict) -> list:
    """The window's rounds that ran a fused step and no chunk, with latent
    rows attended over: their ``mla_*`` / ``moe_*`` counts are one step's."""
    return [f for f in o.get("frames") or []
            if getattr(f, "mla_ctx_rows", 0) and f.mode == "plain" and f.busy_ns[0] == 0 and f.busy_ns[1] > 0]


def step_means(o: dict) -> dict | None:
    """{"rows", "ctx_rows", "experts_hit", "load_max", "local_picks"}: means
    over ``step_frames``. ``ctx_rows`` is one layer's (every layer reads as
    many), the expert counts are summed over the expert layers."""
    fs = step_frames(o)
    if not fs:
        return None
    n = len(fs)
    return {"rows": sum(f.moe_rows for f in fs) / n, "ctx_rows": sum(f.mla_ctx_rows for f in fs) / n,
            "experts_hit": sum(f.moe_experts_hit for f in fs) / n, "load_max": sum(f.moe_load_max for f in fs) / n,
            "local_picks": sum(f.moe_local_picks for f in fs) / n}


def published(o: dict) -> dict:
    """The sizes the counts need, from the configuration's published keys
    (``n_routed_experts`` there is the experts HELD; ``published`` has the
    router's width)."""
    c, g = o["config"], o["geometry"]
    return {
        "hidden": g["hidden"], "layers": g["layers"], "ffn": g["ffn"], "vocab": g["vocab"],
        "heads": int(c["num_attention_heads"]), "q_rank": int(c["q_lora_rank"]), "kv_rank": int(c["kv_lora_rank"]),
        "nope": int(c["qk_nope_head_dim"]), "rope": int(c["qk_rope_head_dim"]), "v_dim": int(c["v_head_dim"]),
        "dense_layers": int(c["first_k_dense_replace"]), "dense_ffn": int(c["intermediate_size"]),
        "experts": int(c["published"]["n_routed_experts"]), "held": int(c["n_routed_experts"]),
        "per_tok": int(c["num_experts_per_tok"]),
    }
