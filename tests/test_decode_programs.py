"""The decode scheduler's program set (serving/decode_programs.py): one
convention a round kind whatever the deployment, every handle warmed and
counted in one class, and a decoder family that is asked, not recognised.

The rounds that call the set are covered where they always were
(test_decode_scheduler, test_flight_recorder, test_feature_draft,
test_spec_tree, test_moe_decoder); here the set itself is driven with the
scheduler's argument shapes, one deployment of each convention."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import moe_decoder as md
from seldon_core_tpu.models.decoder import (
    FamilyNotServed,
    decoder_family,
    gpt2_family,
    init_decoder,
    init_feature_draft,
    require_served,
)
from seldon_core_tpu.serving.decode_scheduler import DecodeScheduler

SEQ, MAX_NEW, N = 8, 6, 2
MOE = md.moe_family(
    md.MoEDecoderConfig(
        vocab=96, hidden=64, layers=4, heads=4, kv_heads=2, head_dim=16, ffn=32, experts=4,
        experts_per_tok=2, window=8, period=4, rope_theta=10000.0, yarn_factor=4.0, yarn_original=16,
    )
)
# convention -> (mode the set reports, compile_counts keys)
CONVENTIONS = {
    "gpt2": ("", {"step", "chunk", "copy"}),
    "counting": ("", {"step", "chunk", "copy"}),
    "chain": ("chain", {"step", "chunk", "copy", "draft", "verify", "draft_admit"}),
    "tree": ("tree", {"step", "chunk", "copy", "draft_tree", "tree_verify", "draft_admit"}),
    "feature": ("feature", {"step_f", "chunk_f", "copy", "draft_feat", "ftree_verify"}),
}


@functools.lru_cache(maxsize=None)
def _sched(convention: str) -> DecodeScheduler:
    """One warmed scheduler a convention, shared by the module's cases."""
    kw = dict(seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=N, kv_page_size=4)
    if convention == "counting":
        params = md.init_moe_decoder(MOE.cfg, seed=0, dtype=jnp.float32)
        kw["family"] = MOE
    else:
        params = init_decoder(seed=3, vocab=128, hidden=64, layers=2, ffn=128, max_len=64)
    if convention in ("chain", "tree"):
        kw["draft_params"] = init_decoder(seed=5, vocab=128, hidden=64, layers=1, ffn=64, max_len=64)
    if convention == "feature":
        kw["draft_params"] = init_feature_draft(seed=3, vocab=128, hidden=64, ffn=128, max_len=64)
    if convention == "chain":
        kw["spec_k"] = 2
    if convention in ("tree", "feature"):
        kw["spec_tree"] = "2,1"
    s = DecodeScheduler(params, **kw)
    s.warmup()
    return s


def _inputs(s: DecodeScheduler):
    zi, zf = np.zeros(N, np.int32), np.zeros(N, np.float32)
    # all-zero block tables, counts 0, no generating row: junk page 0 only
    return s.pool.block_tables(), zi, zf, np.zeros(N, bool)


@pytest.mark.parametrize("kind", ["step", "chunk"])
@pytest.mark.parametrize("convention", list(CONVENTIONS))
def test_step_and_chunk_keep_one_contract_under_every_convention(convention, kind):
    """``step`` and ``chunk`` take the same arguments and return ``(out,
    read)`` with ``read() -> (tokens[n_slots], counts | None)`` whether the
    program also takes ``rows``, round-trips a feature buffer, or appends a
    counting family's counts to its readback — and compile nothing that
    ``warmup`` had not."""
    s = _sched(convention)
    p = s.programs
    mode, keys = CONVENTIONS[convention]
    assert p.mode == mode and set(p.compile_counts()) == keys
    base = p.compile_counts()
    bt, zi, zf, rows = _inputs(s)
    tick = np.int32(7)
    if kind == "step":
        out, read = p.step(bt, zi, zi, zf, zi, tick, rows)
    else:
        bucket = s.chunk_buckets[0]
        out, read = p.chunk(bt, np.zeros((N, bucket), np.int32), zi, zi, zf, zi, tick)
    jax.block_until_ready(out)  # what a sync-timing run blocks on
    toks, counts = read()
    assert toks.shape == (N,) and toks.dtype == np.int32
    if convention == "counting":
        assert out.shape == (N + len(MOE.frame_counters),)
        assert counts.shape == (len(MOE.frame_counters),)
        assert int(counts[0]) == 0  # moe_rows: no row was real
    else:
        assert counts is None and out.shape == (N,)
    assert p.compile_counts() == base
    assert s.recompiles_since_warmup() == 0


@pytest.mark.parametrize("convention", ["chain", "tree", "feature"])
def test_draft_and_verify_keep_one_contract_under_every_convention(convention):
    """A speculative pair is ``draft`` then ``verify`` whatever proposes
    (k-chain, token tree, feature head) and reads back ``(out_tokens
    [n, depth + 1], n_accepted [n])``; the draft cache and the feature
    carry stay the set's."""
    s = _sched(convention)
    p = s.programs
    base = p.compile_counts()
    bt, zi, zf, rows = _inputs(s)
    tick = np.int32(9)
    wlimits = None if s.spec_tree is None else np.zeros((N, s.spec_tree.depth), np.int32)
    proposal = p.draft(zi, zi, zf, zi, tick)
    jax.block_until_ready(proposal)
    (out_dev, acc_dev), read = p.verify(bt, zi, proposal, zi, zf, zi, zi, wlimits, rows, tick)
    out_t, acc = read()
    assert out_t.shape == (N, s.spec_k + 1) and acc.shape == (N,)
    assert not acc.any()  # limits 0: nothing may be accepted
    assert not p.dck.is_deleted() and not s.pool.state[0].is_deleted()
    assert (p.feat is not None) == (convention == "feature")
    assert p.compile_counts() == base
    assert bool(p.admit_buckets) == (convention != "feature")  # the head's prompt K/V rides the chunk ladder


@pytest.mark.parametrize("mechanism, message", [
    ("speculation", "speculative decoding (draft, tree, feature head) is not served for the 'moe' decoder family"),
    ("decode_mesh", "tensor-parallel decode (parallel/tp.py) is not served for the 'moe' decoder family"),
])
@pytest.mark.parametrize("family", ["gpt2", "moe"])
def test_a_family_answers_what_it_serves(family, mechanism, message):
    """Asked, not recognised: the GPT-2 family serves both; the
    sparse-expert family refuses each by name, in the scheduler's build too."""
    if family == "gpt2":
        assert decoder_family(None) is gpt2_family
        require_served(gpt2_family, mechanism)
        assert "attn_kernel" in gpt2_family.serves and gpt2_family.frame_counters == ()
        return
    with pytest.raises(FamilyNotServed) as e:
        require_served(MOE, mechanism)
    assert str(e.value) == message
    assert decoder_family(MOE) is MOE and not MOE.serves
    params = md.init_moe_decoder(MOE.cfg, seed=0, dtype=jnp.float32)
    kw = {"spec_tree": "2,1"} if mechanism == "speculation" else {"mesh_axes": {"model": 2}}
    with pytest.raises(FamilyNotServed) as e:
        DecodeScheduler(params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=N, family=MOE, **kw)
    assert str(e.value) == message
