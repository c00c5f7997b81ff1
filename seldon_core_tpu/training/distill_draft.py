"""Distill a speculative DRAFT against its serving target.

The ``zoo://draft`` entry ships as a seed-shared layer truncation of its
target — the untrained-weights analogue of a distilled draft (PR 4). Its
accept rate comes entirely from the shared residual prefix; nothing ever
LEARNS the target's conditionals. This module closes that gap with the
idle training machinery (training/steps.py): teacher-forced target logits
at every position (models/decoder.sequence_logits) -> KL into the draft,
on a mix of ON-POLICY sequences (prompt + the target's own greedy
continuation — the distribution verify rounds actually score the draft
on, since context during decode IS the target's accepted chain) and
uniform-random sequences (so the draft doesn't collapse off-path).

Run:

    python -m seldon_core_tpu.training.distill_draft \
        --hidden 256 --layers 4 --ffn 1024 --draft-layers 1 \
        --steps 300 --out /tmp/draft_distilled.npz

and serve the result via the checkpoint-loading draft variant:

    tpu.decode_draft_model: "zoo://draft?layers=1&...&distilled=/tmp/draft_distilled.npz"

``--features`` trains the EAGLE-style FEATURE HEAD instead
(models/decoder.init_feature_draft): the teacher supplies per-position
hidden states beside its logits (sequence_hidden), the head runs
teacher-forced on them, and the loss adds feature-regression MSE
(--feat-weight) and input-feature noise (--feat-noise) — the two
augmentations that keep the head's serving-time feature AUTOREGRESSION
(deeper tree nodes feed on its own output) from collapsing. Serve via

    tpu.decode_draft_model: "zoo://draft?features=1&distilled=/tmp/draft_feat.npz"

The report prints the greedy accept-rate proxy (draft/target argmax
agreement along target-greedy trajectories — exactly the per-position
acceptance probability of the chain/tree walk; the feature variant runs
the head on the TRUE teacher features, the serving root's conditioning)
before and after, plus the KL trajectory.
"""

from __future__ import annotations

import argparse
import json

import numpy as np


# ------------------------------------------------------- checkpoint format
# A flat .npz keyed by dotted tree paths ("layers.0.qkv.w", "ln_f.g", ...):
# readable with plain numpy, no pickle, geometry checked on load against
# the receiving build's own init (a distilled checkpoint can only REFILL a
# draft of the same architecture, never change it).


def flatten_params(params) -> dict:
    flat: dict = {}

    def walk(p, prefix):
        if isinstance(p, dict):
            for k, v in p.items():
                walk(v, f"{prefix}{k}.")
        elif isinstance(p, (list, tuple)):
            for i, v in enumerate(p):
                walk(v, f"{prefix}{i}.")
        else:
            flat[prefix[:-1]] = np.asarray(p)

    walk(params, "")
    return flat


def save_draft_checkpoint(path: str, params) -> None:
    np.savez(path, **flatten_params(params))


def load_draft_checkpoint(path: str, like):
    """Rebuild ``like``'s tree structure from the checkpoint, raising on
    any missing key or shape mismatch (the load is an architecture
    assertion, not a best-effort merge)."""
    data = np.load(path)

    def walk(p, prefix):
        if isinstance(p, dict):
            return {k: walk(v, f"{prefix}{k}.") for k, v in p.items()}
        if isinstance(p, (list, tuple)):
            return [walk(v, f"{prefix}{i}.") for i, v in enumerate(p)]
        key = prefix[:-1]
        if key not in data:
            raise ValueError(f"distilled checkpoint {path!r} is missing {key!r}")
        arr = data[key]
        want = np.shape(p)
        if tuple(arr.shape) != tuple(want):
            raise ValueError(
                f"distilled checkpoint {path!r} {key!r} has shape "
                f"{tuple(arr.shape)}, the draft build wants {tuple(want)} — "
                "the checkpoint was trained for a different geometry"
            )
        return arr.astype(np.asarray(p).dtype)

    return walk(like, "")


# ------------------------------------------------------------- the recipe


def greedy_accept_proxy(target, draft, prompts: np.ndarray, max_new: int) -> float:
    """Per-position greedy acceptance probability: along the TARGET's own
    greedy continuation of each prompt, the fraction of generated
    positions where the draft's argmax equals the target's. This is
    exactly what the chain walk accepts per depth (and a lower bound per
    depth for a top-b tree), so it converts directly into expected
    accepted-tokens-per-dispatch."""
    import jax.numpy as jnp

    from seldon_core_tpu.models.decoder import generate, sequence_logits

    full = np.asarray(generate(target, jnp.asarray(prompts), max_new))
    # position j's logits row predicts token j+1 — compare predictions
    # for the GENERATED span only (the prompt is given, not predicted)
    tl = np.asarray(sequence_logits(target, jnp.asarray(full[:, :-1])))
    dl = np.asarray(sequence_logits(draft, jnp.asarray(full[:, :-1])))
    gen = slice(prompts.shape[1] - 1, full.shape[1] - 1)
    return float(
        np.mean(np.argmax(tl[:, gen], -1) == np.argmax(dl[:, gen], -1))
    )


def greedy_accept_proxy_features(
    target, head, prompts: np.ndarray, max_new: int
) -> float:
    """``greedy_accept_proxy`` for a FEATURE draft head: the head runs
    teacher-forced on the target's own hidden states along the target's
    greedy continuation — exactly the serving ROOT step's conditioning
    (the root always consumes the TRUE previous feature; deeper tree
    nodes autoregress on the head's own output, for which this is the
    per-depth upper-bound analogue of the chain proxy)."""
    import jax.numpy as jnp

    from seldon_core_tpu.models.decoder import (
        feature_sequence_logits, generate, sequence_hidden,
    )

    full = np.asarray(generate(target, jnp.asarray(prompts), max_new))
    tl, tf = sequence_hidden(target, jnp.asarray(full[:, :-1]))
    dl, _ = feature_sequence_logits(head, jnp.asarray(full[:, :-1]), tf)
    tl, dl = np.asarray(tl), np.asarray(dl)
    gen = slice(prompts.shape[1] - 1, full.shape[1] - 1)
    return float(
        np.mean(np.argmax(tl[:, gen], -1) == np.argmax(dl[:, gen], -1))
    )


def distill(
    *,
    seed: int = 0,
    vocab: int = 512,
    hidden: int = 256,
    layers: int = 4,
    ffn: int = 1024,
    max_len: int = 80,
    resid_scale: float = 1.0,
    draft_layers: int = 1,
    seq: int = 16,
    horizon: int = 48,
    batch: int = 16,
    steps: int = 300,
    lr: float = 1e-3,
    teacher_temp: float = 0.5,
    on_policy_frac: float = 0.5,
    eval_prompts: int = 16,
    out: str = "",
    log_every: int = 50,
    data_seed: int = 1234,
    features: bool = False,
    feat_weight: float = 1.0,
    feat_noise: float = 0.2,
    self_cond: float = 0.0,
    draft_ffn: int = 0,
) -> dict:
    """Distill a draft against its target; returns the report dict (accept
    proxy before/after, final KL) and writes the checkpoint to ``out``
    when set.

    ``features=False`` (default) trains the seed-shared layer-truncation
    draft (PR 8's recipe). ``features=True`` trains the EAGLE-style
    feature HEAD instead (models/decoder.init_feature_draft): the teacher
    supplies per-position hidden states beside its logits
    (``sequence_hidden``), the head runs teacher-forced on them, and the
    loss adds ``feat_weight`` x feature-regression MSE to the KL
    (training/steps.make_feature_distill_step) so the head's feature
    autoregression stays anchored; ``feat_noise`` perturbs the input
    features during training (the EAGLE augmentation for serving-time
    feature drift at depth — measured: without it deep-node accept
    collapses and the tree ride LOSES to the token draft). ``draft_ffn``
    sizes the head's FFN (0 = the target's ``ffn``)."""
    import jax.numpy as jnp
    import optax

    from seldon_core_tpu.models.decoder import (
        generate, init_decoder, init_feature_draft, sequence_hidden,
        sequence_logits,
    )
    from seldon_core_tpu.training.steps import (
        init_state, make_distill_step, make_feature_distill_step,
    )

    target = init_decoder(
        seed, vocab=vocab, hidden=hidden, layers=layers, ffn=ffn,
        max_len=max_len, resid_scale=resid_scale,
    )
    if features:
        draft = init_feature_draft(
            seed, vocab=vocab, hidden=hidden, ffn=draft_ffn or ffn, max_len=max_len
        )
        proxy = greedy_accept_proxy_features
    else:
        draft = init_decoder(
            seed, vocab=vocab, hidden=hidden, layers=draft_layers, ffn=ffn,
            max_len=max_len, resid_scale=resid_scale,
        )
        proxy = greedy_accept_proxy

    rng = np.random.default_rng(data_seed)
    eval_ids = rng.integers(0, vocab, (eval_prompts, seq)).astype(np.int32)
    accept_before = proxy(target, draft, eval_ids, horizon - seq)

    import jax

    opt = optax.adam(lr)
    if features:
        teacher = jax.jit(lambda ids: sequence_hidden(target, ids))
        step = jax.jit(
            make_feature_distill_step(
                opt, teacher_temp, feat_weight, feat_noise, self_cond
            )
        )
    else:
        teacher = jax.jit(lambda ids: (sequence_logits(target, ids), None))
        step = jax.jit(make_distill_step(sequence_logits, opt, teacher_temp))
    state = init_state(draft, opt)

    # on-policy pool: target-greedy continuations of random prompts,
    # regenerated sparsely (they are the expensive half of the data).
    # The teacher is FROZEN, so pool rows' logits (and, in feature mode,
    # hidden states) are computed once per refresh and gathered per step —
    # recomputing them every step would spend ~half the teacher forward
    # cost on targets that cannot change.
    def _teach(ids):
        t, f = teacher(jnp.asarray(ids))
        return np.asarray(t), (np.asarray(f) if f is not None else None)

    def on_policy_batch(n):
        p = rng.integers(0, vocab, (n, seq)).astype(np.int32)
        ids = np.asarray(generate(target, jnp.asarray(p), horizon - seq))
        return (ids,) + _teach(ids)

    pool, pool_t, pool_f = on_policy_batch(max(batch * 4, 32))
    kl = agree = fmse = float("nan")
    history = []
    for i in range(steps):
        n_on = int(round(batch * on_policy_frac))
        idx = rng.integers(0, len(pool), n_on) if n_on else None
        rand = rng.integers(0, vocab, (batch - n_on, horizon)).astype(np.int32)
        rand_t, rand_f = _teach(rand) if len(rand) else (None, None)
        if idx is not None:
            ids = np.concatenate([pool[idx], rand])
            t = (
                np.concatenate([pool_t[idx], rand_t])
                if rand_t is not None
                else pool_t[idx]
            )
            f = None
            if features:
                f = (
                    np.concatenate([pool_f[idx], rand_f])
                    if rand_f is not None
                    else pool_f[idx]
                )
        else:
            ids, t, f = rand, rand_t, rand_f
        batch_d = {"x": jnp.asarray(ids), "t": jnp.asarray(t)}
        if features:
            batch_d["f"] = jnp.asarray(f)
        state, m = step(state, batch_d)
        kl, agree = float(m["kl"]), float(m["top1_agreement"])
        if features:
            fmse = float(m["feat_mse"])
        if log_every and (i + 1) % log_every == 0:
            row = {"step": i + 1, "kl": round(kl, 4), "top1": round(agree, 4)}
            line = f"step {i+1:5d}  kl {kl:.4f}  top1 {agree:.4f}"
            if features:
                row["feat_mse"] = round(fmse, 4)
                line += f"  fmse {fmse:.4f}"
            history.append(row)
            print(line, flush=True)
        if (i + 1) % max(1, steps // 4) == 0:
            # refresh as the draft moves
            pool, pool_t, pool_f = on_policy_batch(len(pool))

    distilled = jax.tree.map(np.asarray, state.params)
    accept_after = proxy(target, distilled, eval_ids, horizon - seq)
    if out:
        save_draft_checkpoint(out, distilled)
    report = {
        "accept_proxy_before": round(accept_before, 4),
        "accept_proxy_after": round(accept_after, 4),
        "final_kl": round(kl, 4),
        "final_top1": round(agree, 4),
        "steps": steps,
        "features": bool(features),
        "history": history,
        "checkpoint": out or None,
        "geometry": {
            "seed": seed, "vocab": vocab, "hidden": hidden, "layers": layers,
            "ffn": ffn, "max_len": max_len, "resid_scale": resid_scale,
            "draft_layers": draft_layers,
        },
    }
    if features:
        report["final_feat_mse"] = round(fmse, 4)
        report["geometry"]["draft_ffn"] = draft_ffn or ffn
    return report


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4, help="TARGET layers")
    ap.add_argument("--ffn", type=int, default=1024)
    ap.add_argument("--max-len", type=int, default=80)
    ap.add_argument("--resid-scale", type=float, default=1.0)
    ap.add_argument("--draft-layers", type=int, default=1)
    ap.add_argument("--seq", type=int, default=16, help="prompt length")
    ap.add_argument(
        "--horizon", type=int, default=48,
        help="full training-sequence length (prompt + on-policy span)",
    )
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument(
        "--teacher-temp", type=float, default=0.5,
        help="sharpen the teacher before the KL (<1 weights its argmax; "
        "1.0 is pure distribution-matching)",
    )
    ap.add_argument(
        "--on-policy-frac", type=float, default=0.5,
        help="fraction of each batch drawn from target-greedy continuations",
    )
    ap.add_argument("--out", default="", help="checkpoint path (.npz)")
    ap.add_argument("--log-every", type=int, default=50)
    ap.add_argument(
        "--features", action="store_true",
        help="train the EAGLE-style feature draft HEAD (target-hidden + "
        "token-embedding input) instead of the layer-truncation draft; "
        "serve via zoo://draft?features=1&distilled=...",
    )
    ap.add_argument(
        "--feat-weight", type=float, default=1.0,
        help="feature-regression MSE weight beside the KL (features mode)",
    )
    ap.add_argument(
        "--feat-noise", type=float, default=0.2,
        help="input-feature noise std fraction during training (features "
        "mode) — the EAGLE drift augmentation; 0 disables",
    )
    ap.add_argument(
        "--self-cond", type=float, default=0.0,
        help="weight of a self-conditioned second pass (features mode) — "
        "scheduled sampling in feature space. Ships DISABLED: on the "
        "tiny test pair it traded away depth-1 accuracy for less "
        "deep-drift than the noise augmentation already buys",
    )
    ap.add_argument(
        "--draft-ffn", type=int, default=0,
        help="feature head FFN width (0 = the target's --ffn)",
    )
    args = ap.parse_args(argv)
    report = distill(
        seed=args.seed, vocab=args.vocab, hidden=args.hidden, layers=args.layers,
        ffn=args.ffn, max_len=args.max_len, resid_scale=args.resid_scale,
        draft_layers=args.draft_layers, seq=args.seq, horizon=args.horizon,
        batch=args.batch, steps=args.steps, lr=args.lr,
        teacher_temp=args.teacher_temp,
        on_policy_frac=args.on_policy_frac, out=args.out,
        log_every=args.log_every,
        features=args.features, feat_weight=args.feat_weight,
        feat_noise=args.feat_noise, self_cond=args.self_cond,
        draft_ffn=args.draft_ffn,
    )
    print(json.dumps(report))


if __name__ == "__main__":
    main()
