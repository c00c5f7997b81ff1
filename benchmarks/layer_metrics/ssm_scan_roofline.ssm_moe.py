"""The least time the recurrence of one fused step could take on this chip
over the time it took, for the configuration with EIGHT B/C groups:
max(ops / peak FLOP/s, bytes / peak bytes/s) over the op time under
``attn/ssm_scan``. Bytes are the float32 state of the rows that the program's
frames say generated (the step's own ``ssm_rows``), read once and written
once, and their conv cache written at this configuration's width (4096 + 2 x
8 x 128; harness/opsbytes_ssm_moe.ssm_scan, harness/peaks.py). The bytes
bind: 5 operations a state element against 8 bytes. ``ssm_scan_roofline``
reads granite's key names and one group's conv width."""


from harness.opsbytes_ssm_moe import least_seconds, ssm_scan
from harness.scopes_ssm_moe import published, ssm_nested_ms, step_means


def read(o):
    p = published(o)
    took_ms, m = ssm_nested_ms(o, "step", "ssm_scan"), step_means(o)
    if not p or not took_ms or not m:
        return None
    flops, nbytes = ssm_scan(rows=m["rows"], **{k: p[k] for k in (
        "ssm_layers", "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_conv", "ssm_groups")})
    return 100.0 * least_seconds(o["device"]["kind"], flops, nbytes) / (took_ms / 1e3)
