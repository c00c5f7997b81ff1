"""Of the window-kind pages (serving/kv_pool.py ``WindowPages``: the sliding
layers' pages) that slots allocated during the window, the share they gave
back before they retired, because they had moved a window past them: the
flight frames' ``kv_win_released`` over ``kv_win_written``, summed over the
window's rounds. 0 where no context outgrows its window; the rest went back at
retirement. None for a pool of one page kind (the other families, the parent
of PR 47)."""


from harness.scopes_win import window_frames


def read(o):
    fs = window_frames(o)
    written = sum(f.kv_win_written for f in fs)
    if not fs or not written:
        return None
    return 100.0 * sum(f.kv_win_released for f in fs) / written
