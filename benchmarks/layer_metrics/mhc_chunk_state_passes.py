"""How many times a block of a prefill chunk dispatch moves the multi-stream
state: the bytes that the ops under the program's ``mhc_*`` scopes read +
wrote (each op's ``bytes_accessed`` in the device trace, the compiler's own
count) over rows x 4C x 2 bytes x blocks, in the dispatches of the slice's
most frequent ``(rows, c)`` entry (harness/scopes_mhc.py). The least is 2.5:
the state read once and written once, ``u`` written and ``o`` read (a quarter
of the state each). None where the trace's ops carry no byte counts."""


from harness.opsbytes_mhc import state_bytes
from harness.scopes_mhc import blocks, of_run, streams


def read(o):
    r = of_run(o, "chunk")
    if not r or not r["entry"] or not sum(r["bytes"].values()):
        return None
    rows, c = r["entry"]
    one_pass = state_bytes(rows=rows * c, streams=streams(o), hidden=o["geometry"]["hidden"], blocks=blocks(o))
    return sum(r["bytes"].values()) / r["dispatches"] / one_pass
