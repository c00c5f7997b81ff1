"""The least time the recurrence of one fused step could take on this chip
over the time it took: max(ops / peak FLOP/s, bytes / peak bytes/s) over the
op time under ``attn/ssm_scan``. Bytes are the float32 state of the rows
that the program's frames say generated (``ssm_rows``), read once and
written once, and their conv cache written (harness/opsbytes_ssm.ssm_scan,
harness/peaks.py). The bytes bind: 5 operations a state element against 8
bytes."""


from harness.opsbytes_ssm import least_seconds, ssm_scan
from harness.scopes_ssm import nested_ms, published, step_rows


def read(o):
    took_ms, rows = nested_ms(o, "step", "ssm_scan"), step_rows(o)
    if not took_ms or not rows:
        return None
    p = published(o)
    flops, nbytes = ssm_scan(rows=rows, ssm_layers=p["layers"] - p["attn_layers"], ssm_heads=p["ssm_heads"],
                             ssm_head_dim=p["ssm_head_dim"], ssm_state=p["ssm_state"], ssm_conv=p["ssm_conv"])
    return 100.0 * least_seconds(o["device"]["kind"], flops, nbytes) / (took_ms / 1e3)
