"""The short-convolution family's NESTED device scopes, from the same trace,
and what its programs counted themselves.

``harness/scopes.py`` gives a fused step's op time to the first component of
an op's HLO ``op_name`` that is one of its nine scopes. The short-convolution
decoder (``models/conv_decoder.py``, ``ops/moe.py``) nests finer names under
those, so the old readers still see its time: ``qkv/conv_in`` (the B | C | X
projection), ``attn/conv_mix`` (the two gates, the three taps, the state
rows' read and write), ``attn_out/conv_out``; ``qkv/qk_norm`` and
``qkv/rope`` in the attention layers; ``mlp/dense`` and ``mlp/moe_*`` as
``ops/moe.py`` has them. This file reads the finer names, in whole dispatches
of the step or of the chunk, with the self-time rule of ``harness/scopes.py``
(the loop of ``scopes_ssm.py`` and ``scopes_mla.py`` again, gated on its own
names: those files know their families') and, unlike them, with that file's
rule for the compiler's async waits: a wait without an op_name goes to the
nested name of the next scoped op of its dispatch, since a projection held to
the bytes of its weights has to own the wait for them. A program without the ``conv_*``
names (the other families, the parent of PR 41) gives None.
"""

from __future__ import annotations

import bisect
import functools

from harness import scopes as sc
from harness.trace import TRACE_DIR, WINDOW, newest_xplane

CONV = ("conv_in", "conv_mix", "conv_out")
MOE = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
NAMES = CONV + MOE + ("qk_norm", "rope", "dense")
STEP_MARK, CHUNK_MARK = "fused_step", "fused_chunk"


def nested_key(op_name: str) -> str | None:
    """The innermost of NAMES on an op's path; None for an op under none."""
    return next((p for p in reversed(op_name.rstrip(":").split("/")) if p in NAMES), None)


def by_nested(events: dict, mark: str) -> dict | None:
    """Op self time inside whole ``mark`` dispatches of the slice, by nested
    key. None where the slice holds no such dispatch or no op of it carries
    one of the ``conv_*`` names (the expert layer's alone are another
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
            nxt = None  # the nested key of the next scoped op of the dispatch
            for _start, own, op_name, label in sorted(sc._self_times(ops), reverse=True):
                if sc._scope_of(op_name) is not None:
                    key = nxt = nested_key(op_name)
                else:
                    # the compiler's async prefetch of a layer's weights carries no op_name: it is counted
                    # with the op it is for, the next scoped one (``scopes.step_by_scope`` ``waits_by_scope``):
                    # a projection's time without the wait for its weights leaves out the bytes it is held to
                    key = nxt if sc._is_wait(label) else None
                if key is not None:
                    by[key] = by.get(key, 0.0) + own
    return {"dispatches": dispatches, "by": by} if dispatches and any(k in by for k in CONV) else None


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
    """The window's rounds that ran a fused step and no chunk, with conv
    rows advanced: their ``conv_rows`` / ``moe_*`` counts are one step's."""
    return [f for f in o.get("frames") or []
            if getattr(f, "conv_rows", 0) and f.mode == "plain" and f.busy_ns[0] == 0 and f.busy_ns[1] > 0]


def step_means(o: dict) -> dict | None:
    """{"rows", "experts_hit", "load_max", "local_picks"}: means over
    ``step_frames``. ``rows`` is the rows whose conv state the step advanced
    (the slots that generate); the expert counts are summed over the expert
    layers, over the experts HELD."""
    fs = step_frames(o)
    if not fs:
        return None
    n = len(fs)
    return {"rows": sum(f.conv_rows for f in fs) / n, "experts_hit": sum(f.moe_experts_hit for f in fs) / n,
            "load_max": sum(f.moe_load_max for f in fs) / n, "local_picks": sum(f.moe_local_picks for f in fs) / n}


def step_ctx_tokens(o: dict, rows: float) -> float:
    """Cached positions the ``rows`` generating slots attend over, summed:
    each its prompt plus half its output, on average (the other families'
    ``step_roofline`` readers' estimate)."""
    done = [r["gen_len"] for r in o["requests"] if r.get("gen_len")]
    return rows * (int(o["traffic"]["prompt_len"]) + 0.5 * (sum(done) / len(done) if done else 0.0))


def published(o: dict) -> dict:
    """The sizes the counts need, from the configuration's published keys
    (``num_experts`` there is the experts HELD; ``published`` has the
    router's width)."""
    c, g = o["config"], o["geometry"]
    kinds = c["layer_types"][: g["layers"]]
    return {
        "hidden": g["hidden"], "layers": g["layers"], "ffn": g["ffn"], "vocab": g["vocab"],
        "attn_layers": sum(k == "full_attention" for k in kinds),
        "heads": int(c["num_attention_heads"]), "kv_heads": int(c["num_key_value_heads"]),
        "head_dim": g["hidden"] // int(c["num_attention_heads"]), "taps": int(c["conv_L_cache"]),
        "dense_layers": int(c["num_dense_layers"]), "dense_ffn": int(c["intermediate_size"]),
        "experts": int(c["published"]["num_experts"]), "held": int(c["num_experts"]),
        "per_tok": int(c["num_experts_per_tok"]),
    }
