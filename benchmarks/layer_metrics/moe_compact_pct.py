"""Share of the grouped-form calls of a held-expert layer (``ops/moe.py``
``moe_held_ffn`` above ``MASKED_MAX_ROWS``: the 256-token chunks' layers) that
ran COMPACT in one block: the picks that land on the chip fitted the
shape-derived capacity, a fraction of the assignments. The program's own
counts (FlightFrame ``moe_compact_calls`` over ``moe_grouped_calls``, a layer
call each, summed over the window's rounds). 100 where no dispatch's routing
overflowed the capacity; every call that did ran more blocks and dropped
nothing. None where no layer ran the grouped form over a SHARE of
its experts (a configuration that holds all of them, a window without a wide
chunk) and for a program that does not count (the parent of PR 49)."""


def read(o):
    fs = [f for f in o.get("frames") or [] if getattr(f, "moe_grouped_calls", 0)]
    if not fs:
        return None
    return 100.0 * sum(f.moe_compact_calls for f in fs) / sum(f.moe_grouped_calls for f in fs)
