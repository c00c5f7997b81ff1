"""The hybrid family's NESTED device scopes, from the same trace, and what
its programs counted themselves.

``harness/scopes.py`` gives a fused step's op time to the first component of
an op's HLO ``op_name`` that is one of its nine scopes. The hybrid decoder
(``models/hybrid_decoder.py``) nests the Mamba-2 mixer's names under those,
so the old readers still see its time: ``qkv/ssm_in``, ``attn/ssm_conv``,
``attn/ssm_scan`` (the recurrence or the chunked scan, with the state rows'
read and write), ``attn_out/ssm_norm``, ``attn_out/ssm_out``. This file
reads the finer names, in whole dispatches of the step or of the chunk, with
the same self-time rule as ``harness/scopes_moe.py`` (whose loop this
repeats with its own names: that file knows the sparse-expert family's). A
program without them (the other families, the parent of PR 34) gives None.
"""

from __future__ import annotations

import bisect
import functools

from harness import scopes as sc
from harness.trace import TRACE_DIR, WINDOW, newest_xplane

SSM = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_norm", "ssm_out")
STEP_MARK, CHUNK_MARK = "fused_step", "fused_chunk"


def nested_key(op_name: str) -> str | None:
    """The ``ssm_*`` component of an op's path; None for an op under none."""
    return next((p for p in op_name.rstrip(":").split("/") if p in SSM), None)


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


# --------------------------------------------- what the program counted itself


def step_rows(o: dict) -> float | None:
    """Mean rows whose state a fused step advanced (FlightFrame ``ssm_rows``:
    the slots that generate), over the window's rounds that ran a step and no
    chunk. None for a program whose frames lack the field."""
    fs = [f for f in o.get("frames") or []
          if getattr(f, "ssm_rows", 0) and f.mode == "plain" and f.busy_ns[0] == 0 and f.busy_ns[1] > 0]
    return sum(f.ssm_rows for f in fs) / len(fs) if fs else None


def restore_share(o: dict) -> float | None:
    """``state_restores`` over admissions, of the window's frames: the share
    of admissions that began from a cached prefix's snapshot row."""
    fs = [f for f in o.get("frames") or [] if hasattr(f, "state_restores")]
    admitted = sum(f.admitted for f in fs)
    return sum(f.state_restores for f in fs) / admitted if admitted else None


def published(o: dict) -> dict:
    """The sizes the counts need, from the configuration's published keys."""
    c, g = o["config"], o["geometry"]
    kinds = c["layer_types"][: g["layers"]]
    return {
        "hidden": g["hidden"], "layers": g["layers"], "ffn": g["ffn"], "vocab": g["vocab"],
        "attn_layers": sum(k == "attention" for k in kinds),
        "heads": int(c["num_attention_heads"]), "kv_heads": int(c["num_key_value_heads"]),
        "head_dim": g["hidden"] // int(c["num_attention_heads"]),
        "ssm_heads": int(c["mamba_n_heads"]), "ssm_head_dim": int(c["mamba_d_head"]),
        "ssm_state": int(c["mamba_d_state"]), "ssm_conv": int(c["mamba_d_conv"]),
    }
