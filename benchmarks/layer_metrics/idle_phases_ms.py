"""Device idle time per scheduler round inside the scheduler's host phases
(decode.phase.*: admit, prefix_match, alloc, scatter, emit_slo, accept_walk,
sampling, commit). What host_gap_ms counts on the host's clock, read where
the device actually waited."""


from harness.scopes import PHASE, idle_ms_per_round


def read(o):
    return idle_ms_per_round(o, PHASE)
