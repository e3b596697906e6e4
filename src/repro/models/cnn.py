"""ResNet-family CNN for the paper-faithful pFedSOP reproduction.

The paper trains ResNet-18 (CIFAR-10) / ResNet-9 (CIFAR-100, TinyImageNet)
with categorical cross-entropy.  BatchNorm is replaced with GroupNorm:
under vmap'd FL simulation, batch statistics leak across clients and are a
known confounder in FL reproductions (documented in DESIGN.md §8).

Pure JAX (lax.conv_general_dilated), params as nested dicts, f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.reduce import ordered_axis_sum


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    std = np.sqrt(2.0 / fan_in)
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * std


def conv2d(x, w, stride=1):
    """x: (B,H,W,C), w: (kh,kw,Cin,Cout), SAME padding."""
    return jax.lax.conv_general_dilated(
        x, w,
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def groupnorm(p, x, groups=8, eps=1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(b, h, w, g, c // g)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + eps)
    x = xg.reshape(b, h, w, c)
    return x * p["scale"][None, None, None, :] + p["bias"][None, None, None, :]


def _gn_init(c):
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def _block_init(key, cin, cout):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "conv1": _conv_init(k1, 3, 3, cin, cout),
        "gn1": _gn_init(cout),
        "conv2": _conv_init(k2, 3, 3, cout, cout),
        "gn2": _gn_init(cout),
    }
    if cin != cout:
        p["proj"] = _conv_init(k3, 1, 1, cin, cout)
    return p


def _block_apply(p, x, stride):
    h = jax.nn.relu(groupnorm(p["gn1"], conv2d(x, p["conv1"], stride)))
    h = groupnorm(p["gn2"], conv2d(h, p["conv2"]))
    if "proj" in p:
        x = conv2d(x, p["proj"], stride)
    elif stride != 1:
        x = x[:, ::stride, ::stride, :]
    return jax.nn.relu(h + x)


def init_params(key, cfg):
    """cfg: ModelConfig with cnn_channels / cnn_in_channels / n_classes."""
    chans = cfg.cnn_channels
    keys = jax.random.split(key, len(chans) + 2)
    params = {
        "stem": _conv_init(keys[0], 3, 3, cfg.cnn_in_channels, chans[0]),
        "stem_gn": _gn_init(chans[0]),
        "blocks": [],
    }
    cin = chans[0]
    for i, cout in enumerate(chans):
        params["blocks"].append(_block_init(keys[i + 1], cin, cout))
        cin = cout
    params["blocks"] = tuple(params["blocks"])
    params["fc_w"] = (
        jax.random.normal(keys[-1], (cin, cfg.n_classes), jnp.float32)
        / np.sqrt(cin)
    )
    params["fc_b"] = jnp.zeros((cfg.n_classes,), jnp.float32)
    return params


def apply(params, cfg, images):
    """images: (B,H,W,C) f32 -> logits (B, n_classes)."""
    x = jax.nn.relu(groupnorm(params["stem_gn"], conv2d(images, params["stem"])))
    for i, bp in enumerate(params["blocks"]):
        stride = 1 if i == 0 else 2
        x = _block_apply(bp, x, stride)
    x = jnp.mean(x, axis=(1, 2))  # global average pool
    return x @ params["fc_w"] + params["fc_b"]


def loss_fn(params, cfg, batch):
    """Categorical cross-entropy (the paper's probabilistic objective)."""
    logits = apply(params, cfg, batch["images"]).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)[:, 0]
    # halving-tree batch mean: ``jnp.mean`` lets XLA pick the association,
    # and its pick follows the vmapped client count, so a client's loss
    # would depend on how many clients share its device (DESIGN.md §11)
    return ordered_axis_sum(logz - gold) / logz.shape[0]


def accuracy(params, cfg, batch):
    logits = apply(params, cfg, batch["images"])
    return jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32))
