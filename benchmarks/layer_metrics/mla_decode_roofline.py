"""The least time the absorbed latent attention of one fused step could take
on this chip over the time it took: max(ops / peak FLOP/s, bytes / peak
bytes/s) over the op time under ``attn/mla_core`` + ``attn/mla_absorb``. Bytes
are the latent rows that the program's frames say the generating slots
attended over (``mla_ctx_rows``), 576 numbers of 2 bytes read once a layer
for all 64 heads, and ``kv_b`` once a layer; FLOPs 2 x 64 x (576 + 512) a row
(harness/opsbytes_mla.mla_decode, harness/peaks.py). 121 FLOP a byte against
the chip's 240: the bytes bind."""


from harness.opsbytes_mla import least_seconds, mla_decode
from harness.scopes_mla import nested_ms, published, step_means


def read(o):
    took_ms, m = nested_ms(o, "step", "mla_core", "mla_absorb"), step_means(o)
    if not took_ms or not m:
        return None
    p = published(o)
    flops, nbytes = mla_decode(ctx_rows=m["ctx_rows"], rows=m["rows"], layers=p["layers"], heads=p["heads"],
                               kv_rank=p["kv_rank"], rope=p["rope"], nope=p["nope"], v_dim=p["v_dim"])
    return 100.0 * least_seconds(o["device"]["kind"], flops, nbytes) / (took_ms / 1e3)
