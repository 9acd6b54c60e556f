"""Plain float32 ResNet (He et al. 2015, basic blocks) with its training step.

This is the yardstick the program's train step is held to. It is written
from the published description in straightforward ``jax.numpy`` and shares
no code with the program: the initialisation draws the same random numbers
in the same order as the program's does, so that one seed gives both the
same weights without the reference taking any array from the program.

Departures from the paper, each matching the system under test: BatchNorm
in training mode normalises with the batch's own statistics (biased
variance, eps 1e-5); "SAME" padding; the 1x1 projection shortcut only
where the shape changes; AdamW with global-norm clipping and a linear
warm-up into a cosine schedule.

``dtype`` is the compute precision: float32 is the reference; bfloat16 is
the lower-precision control that the comparison must reject.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

Tree = Any

MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)


def init_params(key, blocks, width: int, num_classes: int) -> Tree:
    """He-normal convolutions, unit BatchNorm, a small fully connected head.
    Keys are drawn one per tensor, in the order stem, blocks (conv1, conv2,
    projection), head."""
    ks = iter(jax.random.split(key, 64))

    def conv(kh, kw, cin, cout):
        std = jnp.sqrt(2.0 / (kh * kw * cin))
        return jax.random.normal(next(ks), (kh, kw, cin, cout), jnp.float32) * std

    def bn(c):
        return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}

    params: Dict[str, Any] = {"stem": {"conv/w": conv(7, 7, 3, width), "bn": bn(width)}}
    cin = width
    for si, n in enumerate(blocks):
        cout = width * 2**si
        stage = []
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            b = {"conv1/w": conv(3, 3, cin, cout), "bn1": bn(cout),
                 "conv2/w": conv(3, 3, cout, cout), "bn2": bn(cout)}
            if stride != 1 or cin != cout:
                b["proj/w"] = conv(1, 1, cin, cout)
                b["bn_proj"] = bn(cout)
            stage.append(b)
            cin = cout
        params[f"stage{si}"] = stage
    params["fc"] = {
        "w": jax.random.normal(next(ks), (cin, num_classes), jnp.float32) * 0.01,
        "b": jnp.zeros((num_classes,), jnp.float32),
    }
    return params


def _conv(x, w, stride, dtype):
    return jax.lax.conv_general_dilated(
        x.astype(dtype), w.astype(dtype), (stride, stride), "SAME",
        dimension_numbers=("NCHW", "HWIO", "NCHW"))


def _bn(x, p, dtype):
    mean = x.mean((0, 2, 3), keepdims=True)
    var = jnp.square(x - mean).mean((0, 2, 3), keepdims=True)
    y = (x - mean) / jnp.sqrt(var + 1e-5)
    return (y * p["scale"].astype(dtype)[None, :, None, None]
            + p["bias"].astype(dtype)[None, :, None, None])


def forward(params: Tree, x, blocks, dtype=jnp.float32):
    """x: (B, 3, H, W) normalised images -> (B, classes) logits."""
    h = jax.nn.relu(_bn(_conv(x, params["stem"]["conv/w"], 2, dtype),
                        params["stem"]["bn"], dtype))
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                              (1, 1, 3, 3), (1, 1, 2, 2), "SAME")
    for si, n in enumerate(blocks):
        for bi in range(n):
            b = params[f"stage{si}"][bi]
            stride = 2 if (si > 0 and bi == 0) else 1
            y = jax.nn.relu(_bn(_conv(h, b["conv1/w"], stride, dtype), b["bn1"], dtype))
            y = _bn(_conv(y, b["conv2/w"], 1, dtype), b["bn2"], dtype)
            r = h
            if "proj/w" in b:
                r = _bn(_conv(h, b["proj/w"], stride, dtype), b["bn_proj"], dtype)
            h = jax.nn.relu(y + r)
    h = h.mean((2, 3))
    return h @ params["fc"]["w"].astype(dtype) + params["fc"]["b"].astype(dtype)


def loss_fn(params: Tree, images, labels, blocks, dtype=jnp.float32):
    """Mean softmax cross-entropy, accumulated in float32."""
    logits = forward(params, images, blocks, dtype).astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def learning_rate(step, train: Dict[str, Any]):
    """Linear warm-up, then cosine decay to zero at ``total_steps``."""
    base, warm, total = train["learning_rate"], train["warmup_steps"], train["total_steps"]
    s = step.astype(jnp.float32)
    lr = base * jnp.minimum(1.0, (s + 1) / max(warm, 1))
    frac = jnp.clip((s - warm) / max(total - warm, 1), 0.0, 1.0)
    return lr * 0.5 * (1.0 + jnp.cos(math.pi * frac))


def adamw_step(params, mu, nu, grads, step, train: Dict[str, Any]):
    """One AdamW update after clipping the gradient's global norm.
    Returns (params, mu, nu, clipped gradient)."""
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, train["grad_clip"] / jnp.maximum(norm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = train["beta1"], train["beta2"]
    t = step.astype(jnp.float32) + 1.0
    lr = learning_rate(step, train)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def upd(p, m, v):
        mhat = m / (1.0 - b1**t)
        vhat = v / (1.0 - b2**t)
        return p - lr * (mhat / (jnp.sqrt(vhat) + train["eps"]) + train["weight_decay"] * p)

    return jax.tree.map(upd, params, mu, nu), mu, nu, grads


def make_step(blocks, train: Dict[str, Any], dtype=jnp.float32, rows=None):
    """The jitted reference step (params, mu, nu, step, images, labels) ->
    (params, mu, nu, loss, clipped gradient). ``rows`` keeps only the first
    rows of the batch: the half-batch fault that the comparison must catch."""

    def step_fn(params, mu, nu, step, images, labels):
        if rows is not None:
            images, labels = images[:rows], labels[:rows]
        loss, grads = jax.value_and_grad(loss_fn)(params, images, labels, blocks, dtype)
        params, mu, nu, g = adamw_step(params, mu, nu, grads, step, train)
        return params, mu, nu, loss, g

    return jax.jit(step_fn)


def normalize(images_u8, dtype=jnp.float32):
    """(B, H, W, 3) uint8 -> (B, 3, H, W) ImageNet-normalised, in ``dtype``."""
    x = images_u8.astype(dtype) / jnp.asarray(255.0, dtype)
    x = (x - jnp.asarray(MEAN, dtype)) / jnp.asarray(STD, dtype)
    return x.transpose(0, 3, 1, 2)
