"""The stream mix's precision canary: the largest |row or column sum - 1| of
any Sinkhorn-normalised H_res a fused step computed for its real rows, x 1e6,
as the program counted it (FlightFrame ``mhc_resid_ppm``), the largest over
the window's step-only rounds (one dispatch each). Float32 Sinkhorn reads
single digits (its epsilon is one), a bfloat16 one thousands. None for a
program without the field (``hc_mult`` 1, the other families, the parent of
PR 43)."""


from harness.scopes_mla import step_frames


def read(o):
    seen = [f.mhc_resid_ppm for f in step_frames(o) if getattr(f, "mhc_resid_ppm", 0)]
    return float(max(seen)) if seen else None
