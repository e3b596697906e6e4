"""The one traffic generator: a synthetic image bank and its partition
over clients, from a traffic file's parameters and the run's seed.

Copied from the program's generators (``repro.data.synthetic`` and
``repro.data.partition``), with the image loop vectorised.  The seed sets
the images, the labels' order and each client's split; the partition's
own seed (``partition.seed`` in the traffic file) fixes every client's
size, so every seed gives the same shapes and the same work per round.
"""
from __future__ import annotations

import numpy as np

SHIFTS = np.arange(-2, 3)


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


def dirichlet_partition(labels, n_clients: int, alpha: float, seed: int):
    """Each class split over the clients by proportions from Dir(alpha)."""
    rng = np.random.RandomState(seed)
    n_classes = int(labels.max()) + 1
    client_idx = [[] for _ in range(n_clients)]
    for c in range(n_classes):
        idx = np.where(labels == c)[0]
        rng.shuffle(idx)
        props = rng.dirichlet(np.full(n_clients, alpha))
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for i, part in enumerate(np.split(idx, cuts)):
            client_idx[i].extend(part.tolist())
    out = []
    for i in range(n_clients):
        arr = np.asarray(client_idx[i], np.int64)
        rng.shuffle(arr)
        out.append(arr)
    return out


def pathological_partition(labels, n_clients: int, shard_size: int, seed: int):
    """Samples sorted by label, cut into shards, equal shard counts per client."""
    rng = np.random.RandomState(seed)
    order = np.argsort(labels, kind="stable")
    n_shards = len(labels) // shard_size
    shards = order[: n_shards * shard_size].reshape(n_shards, shard_size)
    perm = rng.permutation(n_shards)
    b = n_shards // n_clients
    out = []
    for i in range(n_clients):
        idx = shards[perm[i * b:(i + 1) * b]].reshape(-1).copy()
        rng.shuffle(idx)
        out.append(idx.astype(np.int64))
    return out


def generate(config: dict, traffic: dict, seed: int):
    """(images, labels, per-client index arrays) for one run.  The bank
    holds the configuration's ``data.samples`` images, or the traffic's
    own ``samples`` where the mix gives its clients fewer."""
    data = config["data"]
    images, labels = class_images(
        traffic.get("samples", data["samples"]), config["model"]["n_classes"],
        config["model"]["image_size"], config["model"]["in_channels"],
        data["noise"], seed)
    part = traffic["partition"]
    if part["kind"] == "dirichlet":
        parts = dirichlet_partition(labels, traffic["clients"], part["alpha"],
                                    part["seed"])
    elif part["kind"] == "pathological":
        parts = pathological_partition(labels, traffic["clients"],
                                       part["shard_size"], part["seed"])
    else:
        raise ValueError(f"unknown partition kind {part['kind']!r}")
    return images, labels, parts
