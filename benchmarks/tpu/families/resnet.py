"""The ResNet family: the program's CNN (``repro.models.cnn``) with
GroupNorm, one basic block per stage, on a synthetic image bank.

What the harness needs of a model family, found by the configuration
file's ``"family"`` key (``spec.load_family``):

- ``program(config) -> (loss_fn, acc_fn)``: the program's loss and eval
  functions, as ``repro.fl.Federation`` takes them;
- ``generate(config, traffic, seed) -> (bank, parts)``: the sample bank,
  a dict of arrays with one leading sample axis, and each client's
  sample indices;
- ``federated_data(bank, parts, config, seed)``: the program's data;
- ``make_weights(seed, config)``: the initial weights, on the device;
- ``forward_flops(config)``, ``param_count(config)``: one sample's
  forward FLOPs and the parameter count, from the configuration's shapes;
- ``reference(config) -> (loss, correct)``: the plain reference's mean
  loss over a batch of bank rows, ``loss(params, batch)``, and its count
  of right answers over the rows a mask keeps,
  ``correct(params, batch, mask)``; plain ``jax.numpy``, importing
  nothing of the program.
"""
from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from tpubench import spec, traffic as traffic_gen

SHIFTS = np.arange(-2, 3)


# -- the program ------------------------------------------------------------------


def program_model(config: dict):
    """The program's ModelConfig at the sizes the configuration file
    states: the named program config with its channels, image size, input
    channels and classes replaced.  The program's ResNet has one basic
    block per stage and no option for more, so a file that states another
    count is refused."""
    mod, attr = config["program_config"].split(":")
    base = getattr(importlib.import_module(mod), attr)
    model = config["model"]
    if model.get("blocks_per_stage", 1) != 1:
        raise spec.SpecError(f"{config['name']} states blocks_per_stage "
                             f"{model['blocks_per_stage']}; the program's ResNet has "
                             "one block per stage")
    return dataclasses.replace(
        base, name=config["name"], cnn_channels=tuple(model["channels"]),
        cnn_image_size=model["image_size"], cnn_in_channels=model["in_channels"],
        n_classes=model["n_classes"])


def program(config: dict):
    """(loss_fn, acc_fn) of the program's ResNet: ``cnn.loss_fn`` and the
    masked eval of ``cnn.apply``."""
    from repro.fl.runtime import masked_accuracy
    from repro.models import cnn

    model_cfg = program_model(config)
    return (lambda p, b: cnn.loss_fn(p, model_cfg, b),
            masked_accuracy(lambda p, t: cnn.apply(p, model_cfg, t["images"])))


def federated_data(bank: dict, parts, config: dict, seed: int):
    from repro.data import FederatedData

    return FederatedData.from_partition(bank["images"], bank["labels"], parts,
                                        train_frac=config["data"]["train_frac"],
                                        seed=seed)


# -- the image bank ----------------------------------------------------------------
# Copied from the program's generator (``repro.data.synthetic``), with the
# image loop vectorised.  The seed sets the images and the labels' order.


def class_images(n_samples: int, n_classes: int, image_size: int,
                 channels: int, noise: float, seed: int):
    """(images (N,H,W,C) f32, labels (N,) int32): per class a smooth
    template of four random cosines, per sample a shift of -2..2 pixels
    on each axis and Gaussian noise; classes balanced."""
    rng = np.random.RandomState(seed)
    h = w = image_size
    yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
    templates = np.zeros((n_classes, h, w, channels), np.float32)
    for c in range(n_classes):
        for _ in range(4):
            fy, fx = rng.uniform(0.5, 3.0, 2)
            phase = rng.uniform(0, 2 * np.pi, 2)
            amp = rng.uniform(0.4, 1.0)
            ch_w = rng.uniform(-1, 1, channels)
            base = amp * np.cos(2 * np.pi * fy * yy / h + phase[0]) * np.cos(
                2 * np.pi * fx * xx / w + phase[1])
            templates[c] += base[:, :, None] * ch_w[None, None, :]
    templates /= np.abs(templates).max(axis=(1, 2, 3), keepdims=True) + 1e-6

    counts = np.full(n_classes, n_samples // n_classes)
    counts[: n_samples % n_classes] += 1
    labels = np.repeat(np.arange(n_classes), counts).astype(np.int32)
    rng.shuffle(labels)

    # every template under every shift, then one gather for the bank
    rolled = np.stack([np.roll(templates, (sy, sx), axis=(1, 2))
                       for sy in SHIFTS for sx in SHIFTS])
    shift = rng.randint(0, len(SHIFTS) ** 2, n_samples)
    images = rolled[shift, labels]
    gen = np.random.Generator(np.random.PCG64(seed))
    images += np.float32(noise) * gen.standard_normal(images.shape, np.float32)
    return images, labels


def generate(config: dict, traffic: dict, seed: int):
    """({"images", "labels"}, per-client index arrays) for one run.  The
    bank holds the configuration's ``data.samples`` images, or the
    traffic's own ``samples`` where the mix gives its clients fewer."""
    data = config["data"]
    images, labels = class_images(
        traffic.get("samples", data["samples"]), config["model"]["n_classes"],
        config["model"]["image_size"], config["model"]["in_channels"],
        data["noise"], seed)
    return {"images": images, "labels": labels}, traffic_gen.partition(labels, traffic)


# -- weights ------------------------------------------------------------------------


def _conv_init(key, kh, kw, cin, cout):
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * np.sqrt(
        2.0 / (kh * kw * cin))


def _gn_init(c):
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def init_params(key, model: dict):
    """He-normal convs, unit GroupNorm, a 1/sqrt(fan-in) head: the layout
    the program's ResNet reads (stem, blocks, fc)."""
    chans = model["channels"]
    keys = jax.random.split(key, len(chans) + 2)
    params = {"stem": _conv_init(keys[0], 3, 3, model["in_channels"], chans[0]),
              "stem_gn": _gn_init(chans[0])}
    blocks, cin = [], chans[0]
    for i, cout in enumerate(chans):
        k1, k2, k3 = jax.random.split(keys[i + 1], 3)
        blk = {"conv1": _conv_init(k1, 3, 3, cin, cout), "gn1": _gn_init(cout),
               "conv2": _conv_init(k2, 3, 3, cout, cout), "gn2": _gn_init(cout)}
        if cin != cout:
            blk["proj"] = _conv_init(k3, 1, 1, cin, cout)
        blocks.append(blk)
        cin = cout
    params["blocks"] = tuple(blocks)
    params["fc_w"] = jax.random.normal(keys[-1], (cin, model["n_classes"]),
                                       jnp.float32) / np.sqrt(cin)
    params["fc_b"] = jnp.zeros((model["n_classes"],), jnp.float32)
    return params


def make_weights(seed: int, config: dict):
    """The initial weights, made on the device in one jitted call."""
    return jax.jit(lambda key: init_params(key, config["model"]))(jax.random.PRNGKey(seed))


# -- work counts --------------------------------------------------------------------


def _conv_macs(h: int, w: int, k: int, cin: int, cout: int) -> int:
    return h * w * k * k * cin * cout


def forward_flops(config: dict) -> int:
    """FLOPs of one sample's forward pass: convolutions and the head, as
    2 x multiply-accumulates (norms, activations and the pool are not
    counted).  SAME padding; stride 2 halves each side, rounding up."""
    model = config["model"]
    s, chans = model["image_size"], model["channels"]
    macs = _conv_macs(s, s, 3, model["in_channels"], chans[0])
    cin = chans[0]
    for i, cout in enumerate(chans):
        if i:
            s = -(-s // 2)
        macs += _conv_macs(s, s, 3, cin, cout) + _conv_macs(s, s, 3, cout, cout)
        if cin != cout:
            macs += _conv_macs(s, s, 1, cin, cout)
        cin = cout
    macs += cin * model["n_classes"]
    return 2 * macs


def param_count(config: dict) -> int:
    """Parameters of the ResNet (convs, GroupNorm scale and bias, head)."""
    model = config["model"]
    chans = model["channels"]
    n = 9 * model["in_channels"] * chans[0] + 2 * chans[0]
    cin = chans[0]
    for cout in chans:
        n += 9 * cin * cout + 9 * cout * cout + 4 * cout
        if cin != cout:
            n += cin * cout
        cin = cout
    return n + cin * model["n_classes"] + model["n_classes"]


# -- the plain reference --------------------------------------------------------------


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _group_norm(p, x, groups=8, eps=1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(b, h, w, g, c // g)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    x = ((xg - mean) / jnp.sqrt(var + eps)).reshape(b, h, w, c)
    return x * p["scale"] + p["bias"]


def forward(params, images):
    """ResNet with GroupNorm: stem, one basic block per stage (stride 2
    after the first, 1x1 projection where the width changes), global
    average pool, linear head."""
    x = jax.nn.relu(_group_norm(params["stem_gn"], _conv(images, params["stem"])))
    for i, blk in enumerate(params["blocks"]):
        stride = 1 if i == 0 else 2
        h = jax.nn.relu(_group_norm(blk["gn1"], _conv(x, blk["conv1"], stride)))
        h = _group_norm(blk["gn2"], _conv(h, blk["conv2"]))
        if "proj" in blk:
            x = _conv(x, blk["proj"], stride)
        elif stride != 1:
            x = x[:, ::stride, ::stride, :]
        x = jax.nn.relu(h + x)
    return jnp.mean(x, axis=(1, 2)) @ params["fc_w"] + params["fc_b"]


def cross_entropy(params, images, labels):
    logits = forward(params, images)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def _correct_rows(params, images, labels, mask):
    hit = jnp.argmax(forward(params, images), -1) == labels
    return jnp.sum(jnp.where(mask, hit, False))


def reference(config: dict):
    """(loss, correct) of the plain ResNet, over ``{"images", "labels"}``
    rows of the bank."""
    return (lambda params, batch: cross_entropy(params, batch["images"], batch["labels"]),
            lambda params, batch, mask: _correct_rows(params, batch["images"],
                                                      batch["labels"], mask))
