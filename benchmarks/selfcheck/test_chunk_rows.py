"""``chunk_rows_live_pct`` reads the frames' own counters: None where the
program has none (PR 33's parent), else live rows over rows computed,
summed over the window's rounds that ran a chunk dispatch."""

import importlib.util
import os

import pytest
from conftest import BENCH


def _reader():
    path = os.path.join(BENCH, "layer_metrics", "chunk_rows_live_pct.py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Old:
    """A frame of a program without the counters."""

    tokens = 16


class Frame(Old):
    def __init__(self, rows, live):
        self.chunk_rows, self.chunk_rows_live = rows, live


@pytest.mark.parametrize("frames, want", [
    (None, None),
    ([], None),
    ([Old(), Old()], None),
    ([Frame(0, 0), Frame(0, 0)], None),  # step-only rounds
    ([Frame(16, 1), Frame(0, 0), Frame(16, 2)], 100 * 3 / 32),  # every chunk at full width
    ([Frame(2, 1), Frame(2, 2), Frame(16, 5), Frame(0, 0)], 100 * 8 / 20),
    ([Old(), Frame(2, 1)], 50.0),
], ids=["no_frames", "empty", "no_counters", "no_chunk_round", "full_width", "compact_and_a_wave", "mixed"])
def test_chunk_rows_live_pct(frames, want):
    got = _reader()({"frames": frames})
    assert got is None if want is None else got == pytest.approx(want)
