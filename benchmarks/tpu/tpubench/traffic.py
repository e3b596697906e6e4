"""The one partition of a sample bank over clients, from a traffic
file's ``clients`` and ``partition`` parameters.  A model family makes
its bank from the run's seed (``families/<family>.py``, ``generate``) and
hands its labels here.

Copied from the program's generator (``repro.data.partition``).  The
partition's own seed (``partition.seed`` in the traffic file) fixes every
client's size, so every run seed gives the same shapes and the same work
per round.
"""
from __future__ import annotations

import numpy as np


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


def partition(labels, traffic: dict):
    """Per-client index arrays into a bank with ``labels``, as the
    traffic file's ``partition`` states."""
    part = traffic["partition"]
    if part["kind"] == "dirichlet":
        return dirichlet_partition(labels, traffic["clients"], part["alpha"], part["seed"])
    if part["kind"] == "pathological":
        return pathological_partition(labels, traffic["clients"], part["shard_size"],
                                      part["seed"])
    raise ValueError(f"unknown partition kind {part['kind']!r}")
