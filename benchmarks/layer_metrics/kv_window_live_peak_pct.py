"""Peak share of the window-kind pages (the sliding layers' pages) that live
slots mapped, over the window's rounds: the flight frames' ``kv_win_live``
over that kind's pages less the junk page (``kv_live_peak_pct`` reads the full
kind). The kind's page count is not a deployment key: the program derives it
from the slots' rings and the prefix entries' last windows
(serving/kv_pool.py ``window_pool_pages``), and so does this. None for a pool
of one page kind, and on a tree without the kind."""


from harness.scopes_win import window_frames


def read(o):
    fs = window_frames(o)
    if not fs:
        return None
    try:
        from seldon_core_tpu.serving.kv_pool import window_pool_pages
    except ImportError:
        return None
    tpu = o["config"]["deployment"]["spec"]["predictors"][0]["tpu"]
    chunk = min(int(tpu.get("decode_prefill_chunk") or o["traffic"]["prompt_len"]), int(o["traffic"]["prompt_len"]))
    pages = window_pool_pages(int(tpu["decode_slots"]), int(tpu.get("decode_prefix_slots", 0)),
                              int(o["config"]["sliding_window"]), chunk, int(tpu["decode_kv_page_size"]))
    return 100.0 * max(f.kv_win_live for f in fs) / (pages - 1)
