"""Tensor-parallel partitioning for the generative decode tier.

The decode scheduler (serving/decode_scheduler.py) runs every fused
program — prefill chunk ladder, decode step, verify, draft, paged
copy/CoW — as ONE jit dispatch. This module supplies the shardings that
turn those dispatches into SPMD programs over a named device mesh
(``tpu.decode_mesh_axes``, e.g. ``{"tp": 4}``), following the
low-latency decode partitioning of Pope et al., *Efficiently Scaling
Transformer Inference* (2022):

- **attention sharded on the head axis**: the paged KV pool of token
  rows ``[L, n_pages, page_size, h*hd]`` (its last axis split into
  ``tp`` contiguous blocks is ``h/tp`` whole heads each), the draft's
  flat slot cache ``[L, n_slots, h, ctx, hd]``, and every per-head
  attention tensor carry ``h`` split over the mesh axis — each device
  runs its heads' scores/softmax/context entirely locally (per-head
  attention has no cross-head reduction);
- **FFN sharded on the hidden axis**: ``mlp_in`` column-parallel
  (output ``ffn`` axis), ``mlp_out`` row-parallel (input ``ffn`` axis);
- **row-parallel output projections**: ``attn_out``'s input axis is
  sharded head-aligned (the merged ``h*hd`` activation axis is sharded
  by its head factor), so each residual branch ends in ONE fused
  all-reduce — two per layer (attention + FFN), the canonical
  Megatron/Pope pattern, inserted by GSPMD inside the already-fused
  step program (no extra dispatches);
- **everything else replicated**: layer norms, embeddings, the
  weight-tied lm head, and the packed ``qkv`` projection. ``qkv.w``
  stays replicated because its ``[hidden, 3*hidden]`` layout interleaves
  q/k/v at boundaries a contiguous shard cannot respect (slicing a
  sharded axis mid-shard would cost a reshard per layer); its redundant
  FLOPs are 3h^2 of the ~12h^2 per-token weight FLOPs, while the
  sharded tensors carry the attention + FFN majority AND the KV bytes —
  the HBM axis that actually caps decode concurrency.

int8 paged KV: the per-page-row (scale, zero-point) planes
``[L, n_pages, page_size]`` have no head axis and stay replicated —
quantization reduces over ``(h, hd)`` of REPLICATED fresh K/V rows, so
every device derives identical scales and the dequant fused into each
device's head-shard gather reads its local copy.

Host-side structures — block tables, the ``PageAllocator``, the radix
``PrefixIndex`` — are device-count-agnostic: a block table maps logical
to physical PAGES, and a page is itself head-sharded, so admission,
copy-on-write, and reclaim logic never see the mesh.

Greedy output stays token-identical to the single-device scheduler at
any width (asserted by tests/test_tp_decode.py and the ``gen.tp_*``
bench sub-leg): the partitioning only reorders floating-point
reductions inside the row-parallel matmuls, which the argmax margins of
the decode contract absorb.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from seldon_core_tpu.parallel.mesh import mesh_from_spec


def tp_width(mesh_axes) -> int:
    """The tensor-parallel width a ``decode_mesh_axes`` mapping asks for
    (1 when unset/empty — single-device)."""
    if not mesh_axes:
        return 1
    w = 1
    for size in mesh_axes.values():
        w *= int(size)
    return w


def decode_mesh_problems(mesh_axes, params=None, draft_params=None) -> list[str]:
    """Everything wrong with a ``decode_mesh_axes`` request, as a list of
    problems (empty = servable). Pure host checks: axis shape, device
    budget, and — when the decoder params are at hand — the divisibility
    rules head/FFN sharding needs. ``decode_tp_mesh`` raises these;
    ``scheduler_for_executor`` warn-and-disables on them (the spec-mode
    precedent for unservable opt-in configs)."""
    problems: list[str] = []
    if not mesh_axes:
        return problems
    if len(mesh_axes) != 1:
        problems.append(
            f"decode_mesh_axes supports exactly ONE tensor-parallel axis, "
            f"got {dict(mesh_axes)!r}"
        )
    for name, size in mesh_axes.items():
        if int(size) < 1:
            problems.append(f"decode_mesh_axes axis '{name}' must be >= 1, got {size}")
    tp = tp_width(mesh_axes)
    n_dev = len(jax.devices())
    if tp > n_dev:
        problems.append(
            f"decode_mesh_axes={dict(mesh_axes)} needs {tp} devices, have {n_dev}"
        )
    for what, p in (("decoder", params), ("draft", draft_params)):
        if p is None or tp <= 1:
            continue
        from seldon_core_tpu.models.decoder import decoder_dims

        dims = decoder_dims(p)
        ffn = p["layers"][0]["mlp_in"]["w"].shape[1]
        if dims["heads"] % tp:
            problems.append(
                f"{what} n_heads={dims['heads']} not divisible by tp width {tp} "
                "(attention is sharded on the head axis)"
            )
        if ffn % tp:
            problems.append(
                f"{what} ffn={ffn} not divisible by tp width {tp} "
                "(the FFN is sharded on its hidden axis)"
            )
    return problems


def decode_tp_mesh(mesh_axes, params=None, draft_params=None):
    """Build the decode mesh: ``(mesh, axis_name, tp_width)``.

    Returns ``(None, None, 1)`` for an unset/width-1 request (plain jit
    beats a 1-device mesh). Raises ValueError listing every problem —
    the scheduler's contract when handed mesh axes directly; the serving
    builder pre-checks with ``decode_mesh_problems`` and warn-disables
    instead, so a deployment degrades to single-device rather than
    failing to boot."""
    problems = decode_mesh_problems(mesh_axes, params, draft_params)
    if problems:
        raise ValueError("; ".join(problems))
    if tp_width(mesh_axes) <= 1:
        return None, None, 1
    mesh = mesh_from_spec(dict(mesh_axes))
    if mesh is None:
        return None, None, 1
    axis = mesh.axis_names[0]
    return mesh, axis, mesh.shape[axis]


def decoder_param_pspecs(params: dict, axis: str):
    """PartitionSpec pytree for the models/decoder.py param layout (see
    the module docstring for the partitioning rationale)."""

    def _ln(p):
        return {k: P() for k in p}

    def _layer(lp):
        return {
            "ln1": _ln(lp["ln1"]),
            # packed q/k/v boundaries don't align with contiguous shards
            "qkv": {"w": P(), "b": P()},
            # row-parallel: input axis sharded head-aligned, bias applied
            # to the all-reduced (replicated) output
            "attn_out": {"w": P(axis, None), "b": P()},
            "ln2": _ln(lp["ln2"]),
            # column-parallel: output ffn axis sharded, bias rides the shard
            "mlp_in": {"w": P(None, axis), "b": P(axis)},
            "mlp_out": {"w": P(axis, None), "b": P()},
        }

    out = {
        "tok_emb": P(),
        "pos_emb": P(),
        "layers": [_layer(lp) for lp in params["layers"]],
        "ln_f": _ln(params["ln_f"]),
    }
    if "fc" in params:
        # feature-draft head (models/decoder.init_feature_draft): the
        # [2*hidden -> hidden] feature+embedding fuse replicates — its
        # input is the replicated feat buffer + embedding, and its output
        # feeds the head's qkv which is replicated too
        out["fc"] = {"w": P(), "b": P()}
    return out


def decoder_param_shardings(params: dict, mesh: Mesh, axis: str):
    """NamedSharding pytree matching ``params``' structure."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        decoder_param_pspecs(params, axis),
        is_leaf=lambda x: isinstance(x, P),
    )


def kv_sharding(mesh: Mesh, axis: str, arr) -> NamedSharding:
    """Sharding for one KV-cache buffer, told apart by rank: the 4-D paged
    payload ``[L, n_pages, page_size, h*hd]`` shards its last axis (a
    token row split into ``tp`` contiguous blocks is ``h/tp`` whole heads
    each — ``decode_mesh_problems`` rejects ``h % tp != 0``); the 5-D flat
    slot cache ``[L, n_slots, h, ctx, hd]`` carries heads at axis 2 and
    shards there; everything else (the 3-D int8 scale/zero-point planes,
    which have no head axis) replicates."""
    ndim = getattr(arr, "ndim", 0)
    if ndim == 4:
        return NamedSharding(mesh, P(None, None, None, axis))
    if ndim == 5:
        return NamedSharding(mesh, P(None, None, axis, None, None))
    return NamedSharding(mesh, P())


def tree_node_sharding(mesh: Mesh, axis: str) -> NamedSharding:
    """Sharding for the tree-speculation round's IN-REGISTER node K/V
    ``[L, n_slots, h, n_tree, hd]`` (models/decoder.draft_propose_tree /
    paged_tree_verify outputs, alive only between the round's two
    dispatches): heads shard at axis 2 exactly like the persistent KV
    buffers; the TREE axis is replicated. Widening the verify to a token
    tree therefore adds NO collective — per-head scores/softmax over the
    tree's queries stay device-local and each residual branch still ends
    in the one fused all-reduce, so the tree composes with any mesh width
    the head/FFN divisibility rules admit (no tree-width divisibility
    constraint exists, by construction)."""
    return NamedSharding(mesh, P(None, None, axis, None, None))
