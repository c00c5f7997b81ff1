"""Prompt tokens served from cached pages over prompt tokens admitted in the
window (the scheduler's stat_prefix_tokens_saved and stat_admitted)."""


def read(o):
    admitted = o["after"]["admitted"] - o["before"]["admitted"]
    seq = int(o["traffic"]["prompt_len"])
    return 100.0 * (o["after"]["prefix_tokens_saved"] - o["before"]["prefix_tokens_saved"]) / (admitted * seq) if admitted else None
