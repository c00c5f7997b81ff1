"""ResNet-50 in pure JAX (NHWC) — the flagship image model of the zoo.

Parity role: the reference's benchmark configs call for "Average Combiner
ensemble: 3x ResNet50 image models" (BASELINE.json) served as CUDA/TF
containers behind per-request RPC. Here ResNet50 is a params-pytree + pure
apply function loaded straight into TPU HBM by ModelRuntime.

TPU design notes:
- NHWC layout with HWIO kernels — the layout XLA's TPU conv emitter expects;
  channels land on the 128-wide lane dimension of the MXU.
- BatchNorm is inference-mode (running stats are parameters) and is FOLDED
  into the preceding conv's weights at model-build time (fold_batchnorm) —
  each conv+BN pair serves as conv+bias, removing the per-channel
  scale/shift chain and the BN stats from HBM. The functional training path
  (batch stats computed in-graph) lives in seldon_core_tpu/training/steps.py
  so serving apply stays a single pure fn.
- All FLOPs are convs/matmuls; elementwise (BN, relu, add) fuses into the
  preceding conv under XLA. bfloat16 params/activations are one dtype flag
  away (ModelRuntime dtype policy).
"""

from __future__ import annotations

from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.zoo import ModelSpec, register_model

# stage depths for the resnet family
_DEPTHS = {18: (2, 2, 2, 2), 34: (3, 4, 6, 3), 50: (3, 4, 6, 3), 101: (3, 4, 23, 3)}
_BOTTLENECK = {50: True, 101: True, 18: False, 34: False}


# Param init is HOST-side numpy on purpose: jax.random on the device pays one
# compiled rng program per tensor; numpy init + one device_put does not.
# Determinism comes from the seeded rng.


def _conv_init(rng: np.random.Generator, h, w, c_in, c_out):
    fan_in = h * w * c_in
    scale = (2.0 / fan_in) ** 0.5
    return (rng.standard_normal((h, w, c_in, c_out)) * scale).astype(np.float32)


def _bn_init(c):
    return {
        "scale": np.ones((c,), np.float32),
        "bias": np.zeros((c,), np.float32),
        "mean": np.zeros((c,), np.float32),
        "var": np.ones((c,), np.float32),
    }


def _conv(x, kernel, stride=1):
    return jax.lax.conv_general_dilated(
        x,
        kernel.astype(x.dtype),
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _bn(x, p, eps=1e-5):
    # inference-mode batchnorm; folds to scale*x+shift, fused by XLA
    inv = jax.lax.rsqrt(p["var"].astype(x.dtype) + jnp.asarray(eps, x.dtype))
    scale = p["scale"].astype(x.dtype) * inv
    shift = p["bias"].astype(x.dtype) - p["mean"].astype(x.dtype) * scale
    return x * scale + shift


def _norm(x, p, bn_key, bias_key):
    """Post-conv normalisation: BN when unfolded, plain bias when folded.

    Which branch runs is decided by pytree structure at trace time, so both
    folded and unfolded params share the same jitted apply code.
    """
    if bn_key in p:
        return _bn(x, p[bn_key])
    return x + p[bias_key].astype(x.dtype)


# (conv key, unfolded bn key, folded bias key) triples for one block
_FOLD_KEYS = (
    ("conv1", "bn1", "bias1"),
    ("conv2", "bn2", "bias2"),
    ("conv3", "bn3", "bias3"),
    ("proj", "bn_proj", "bias_proj"),
)


def fold_batchnorm(params: dict, eps: float = 1e-5) -> dict:
    """Fold inference-mode BN into the preceding conv's weights (host-side).

    conv(x, W)*s + t  ==  conv(x, W*s) + t  for the per-output-channel BN
    affine s = scale/sqrt(var+eps), t = bias - mean*s, so each conv+BN pair
    becomes conv + bias — one fewer elementwise chain per conv at serving
    time and no BN stats in HBM. Equivalent to the unfolded path up to
    float rounding (folding is computed in float64 and cast to float32).
    Idempotent: already-folded params pass through unchanged.
    """

    def fold(kernel, bn):
        inv = np.asarray(bn["scale"], np.float64) / np.sqrt(
            np.asarray(bn["var"], np.float64) + eps
        )
        w = (np.asarray(kernel, np.float64) * inv).astype(np.float32)
        b = (
            np.asarray(bn["bias"], np.float64)
            - np.asarray(bn["mean"], np.float64) * inv
        ).astype(np.float32)
        return w, b

    out: dict[str, Any] = {"head": params["head"]}
    stem = params["stem"]
    if "bn" in stem:
        w, b = fold(stem["conv"], stem["bn"])
        out["stem"] = {"conv": w, "bias": b}
    else:
        out["stem"] = stem
    stage = 0
    while f"stage{stage}" in params:
        blocks = []
        for bp in params[f"stage{stage}"]:
            nb: dict[str, Any] = {}
            for conv_key, bn_key, bias_key in _FOLD_KEYS:
                if conv_key not in bp:
                    continue
                if bn_key in bp:
                    nb[conv_key], nb[bias_key] = fold(bp[conv_key], bp[bn_key])
                else:  # already folded
                    nb[conv_key] = bp[conv_key]
                    nb[bias_key] = bp[bias_key]
            blocks.append(nb)
        out[f"stage{stage}"] = blocks
        stage += 1
    return out


def _bottleneck_init(rng, c_in, c_mid, stride):
    c_out = c_mid * 4
    p = {
        "conv1": _conv_init(rng, 1, 1, c_in, c_mid),
        "bn1": _bn_init(c_mid),
        "conv2": _conv_init(rng, 3, 3, c_mid, c_mid),
        "bn2": _bn_init(c_mid),
        "conv3": _conv_init(rng, 1, 1, c_mid, c_out),
        "bn3": _bn_init(c_out),
    }
    if stride != 1 or c_in != c_out:
        p["proj"] = _conv_init(rng, 1, 1, c_in, c_out)
        p["bn_proj"] = _bn_init(c_out)
    return p


def _bottleneck_apply(p, x, stride):
    y = jax.nn.relu(_norm(_conv(x, p["conv1"]), p, "bn1", "bias1"))
    y = jax.nn.relu(_norm(_conv(y, p["conv2"], stride), p, "bn2", "bias2"))
    y = _norm(_conv(y, p["conv3"]), p, "bn3", "bias3")
    if "proj" in p:
        x = _norm(_conv(x, p["proj"], stride), p, "bn_proj", "bias_proj")
    return jax.nn.relu(x + y)


def _basic_init(rng, c_in, c_out, stride):
    p = {
        "conv1": _conv_init(rng, 3, 3, c_in, c_out),
        "bn1": _bn_init(c_out),
        "conv2": _conv_init(rng, 3, 3, c_out, c_out),
        "bn2": _bn_init(c_out),
    }
    if stride != 1 or c_in != c_out:
        p["proj"] = _conv_init(rng, 1, 1, c_in, c_out)
        p["bn_proj"] = _bn_init(c_out)
    return p


def _basic_apply(p, x, stride):
    y = jax.nn.relu(_norm(_conv(x, p["conv1"], stride), p, "bn1", "bias1"))
    y = _norm(_conv(y, p["conv2"]), p, "bn2", "bias2")
    if "proj" in p:
        x = _norm(_conv(x, p["proj"], stride), p, "bn_proj", "bias_proj")
    return jax.nn.relu(x + y)


def init_resnet(
    seed: int = 0,
    depth: int = 50,
    num_classes: int = 1000,
    width: int = 64,
    image_size: int = 224,
) -> dict:
    rng = np.random.default_rng(seed)
    depths = _DEPTHS[depth]
    bottleneck = _BOTTLENECK[depth]
    expansion = 4 if bottleneck else 1
    block_init = _bottleneck_init if bottleneck else _basic_init

    params: dict[str, Any] = {
        "stem": {"conv": _conv_init(rng, 7, 7, 3, width), "bn": _bn_init(width)},
    }
    c_in = width
    for stage, n_blocks in enumerate(depths):
        c_mid = width * (2**stage)
        stride = 1 if stage == 0 else 2
        blocks = []
        for b in range(n_blocks):
            blocks.append(block_init(rng, c_in, c_mid, stride if b == 0 else 1))
            c_in = c_mid * expansion
        params[f"stage{stage}"] = blocks
    scale = (1.0 / c_in) ** 0.5
    params["head"] = {
        "w": (rng.standard_normal((c_in, num_classes)) * scale).astype(np.float32),
        "b": np.zeros((num_classes,), np.float32),
    }
    return params


def space_to_depth_stem(params: dict) -> dict:
    """Re-express the 7x7/stride-2 stem conv as 4x4/stride-1 on a
    space-to-depth input (host-side, one-time, exact).

    The stem conv reads a 3-channel image — 3 of the MXU's 128 lanes do
    work, so the op is ~2% efficient and dominates wall time. Folding a
    2x2 space-to-depth into the weights turns it into a 12-channel conv:
      y[i,j,o] = sum_{p,q,c} w[p,q,c,o] x[2i+p-2, 2j+q-2, c]
    with x[2I+a, 2J+b, c] = X[I, J, (a,b,c)] becomes a 4x4 conv over X
    where w'[P,Q,(a,b,c),o] = w[2P+a, 2Q+b, c, o] (zero where 2P+a > 6)
    and explicit padding (1,2) replaces SAME's pixel-space (2,3).
    apply_resnet performs the matching input reshape at trace time when it
    sees a 12-channel stem kernel. Requires a folded stem (run
    fold_batchnorm first); no-op if already transformed.
    """
    stem = params["stem"]
    if "bn" in stem:
        raise ValueError("space_to_depth_stem requires fold_batchnorm first")
    w = np.asarray(stem["conv"], np.float32)
    if w.shape[:3] == (4, 4, 12):  # already transformed
        return params
    if w.shape[:3] != (7, 7, 3):
        raise ValueError(f"unexpected stem kernel shape {w.shape}")
    c_out = w.shape[3]
    w2 = np.zeros((4, 4, 12, c_out), np.float32)
    for big_p in range(4):
        for big_q in range(4):
            for a in range(2):
                for b in range(2):
                    p, q = 2 * big_p + a, 2 * big_q + b
                    if p > 6 or q > 6:
                        continue
                    for c in range(3):
                        w2[big_p, big_q, a * 6 + b * 3 + c] = w[p, q, c]
    out = dict(params)
    out["stem"] = {"conv": w2, "bias": stem["bias"]}
    return out


def _space_to_depth(x):
    """[N, 2H, 2W, C] -> [N, H, W, 4C] matching space_to_depth_stem's
    (a, b, c) channel order. Even H and W required — the transformed stem's
    explicit (1,2) block padding equals SAME's (2,3) pixel padding only
    then (shapes are static under jit, so this raises at trace time)."""
    n, h, w, c = x.shape
    if h % 2 or w % 2:
        raise ValueError(
            f"space-to-depth stem requires even spatial dims, got {h}x{w}; "
            "build the model with space_to_depth=False for odd image sizes"
        )
    x = x.reshape(n, h // 2, 2, w // 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // 2, w // 2, 4 * c)


def resnet_logits(params: dict, x: jax.Array) -> jax.Array:
    """x: [batch, H, W, 3] float -> logits [batch, num_classes]."""
    # pytree structure (not traced values) decides the block type, so this
    # branch is resolved at trace time — no dynamic control flow under jit
    bottleneck = "conv3" in params["stage0"][0]
    block_apply = _bottleneck_apply if bottleneck else _basic_apply

    stem_kernel = params["stem"]["conv"]
    if stem_kernel.shape[2] == 12:  # space-to-depth stem (trace-time branch)
        h = jax.lax.conv_general_dilated(
            _space_to_depth(x),
            stem_kernel.astype(x.dtype),
            window_strides=(1, 1),
            padding=((1, 2), (1, 2)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
    else:
        h = _conv(x, stem_kernel, stride=2)
    h = jax.nn.relu(_norm(h, params["stem"], "bn", "bias"))
    h = jax.lax.reduce_window(
        h, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )
    stage = 0
    while f"stage{stage}" in params:
        for b, bp in enumerate(params[f"stage{stage}"]):
            stride = 2 if (stage > 0 and b == 0) else 1
            h = block_apply(bp, h, stride)
        stage += 1
    h = jnp.mean(h, axis=(1, 2))  # global average pool
    return h @ params["head"]["w"].astype(h.dtype) + params["head"]["b"].astype(h.dtype)


def apply_resnet(params: dict, x: jax.Array) -> jax.Array:
    """Serving entrypoint: softmax probabilities."""
    return jax.nn.softmax(resnet_logits(params, x), axis=-1)


@register_model("resnet50")
def build_resnet50(
    seed: int = 0,
    num_classes: int = 1000,
    depth: int = 50,
    width: int = 64,
    image_size: int = 224,
    fold_bn: bool = True,
    space_to_depth: bool = False,
    **_,
) -> ModelSpec:
    params = init_resnet(seed, depth=depth, num_classes=num_classes, width=width)
    if fold_bn:
        params = fold_batchnorm(params)
    if space_to_depth:
        params = space_to_depth_stem(params)
    return ModelSpec(
        apply_resnet,
        params,
        (image_size, image_size, 3),
        tuple(f"class_{i}" for i in range(num_classes)),
        param_pspecs=None,  # resnet serves data-parallel; weights replicate
    )


@register_model("resnet_tiny")
def build_resnet_tiny(
    seed: int = 0,
    num_classes: int = 10,
    fold_bn: bool = True,
    space_to_depth: bool = False,
    **_,
) -> ModelSpec:
    """Small resnet (depth-18, width-16, 32x32) for tests and CI."""
    params = init_resnet(seed, depth=18, num_classes=num_classes, width=16)
    if fold_bn:
        params = fold_batchnorm(params)
    if space_to_depth:
        params = space_to_depth_stem(params)
    return ModelSpec(
        apply_resnet,
        params,
        (32, 32, 3),
        tuple(f"class_{i}" for i in range(num_classes)),
    )
