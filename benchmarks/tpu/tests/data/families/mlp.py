"""A toy model family for the harness's tests: a two-layer MLP on a
seeded bank of class-conditional feature vectors.  It is found by name
as any family is (``spec.load_family("mlp", bench_dir=<tests/data>)``)
and runs through ``run.run_cell`` with no edit to the harness.

Its configuration's ``model`` gives ``features``, ``hidden`` and
``n_classes``; ``data`` gives ``samples``, ``train_frac`` and ``noise``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tpubench import traffic as traffic_gen


def _forward(params, x):
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def _cross_entropy(params, x, labels):
    logits = _forward(params, x)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def program(config: dict):
    """The federation's loss and masked eval; the program's data calls
    the features ``images``."""
    from repro.fl.runtime import masked_accuracy

    return (lambda p, b: _cross_entropy(p, b["images"], b["labels"]),
            masked_accuracy(lambda p, t: _forward(p, t["images"])))


def generate(config: dict, traffic: dict, seed: int):
    """Per class a mean drawn from the seed, per sample Gaussian noise
    around it; classes balanced, their order shuffled."""
    model, data = config["model"], config["data"]
    rng = np.random.RandomState(seed)
    n, k = data["samples"], model["n_classes"]
    means = rng.normal(size=(k, model["features"])).astype(np.float32)
    labels = np.arange(n, dtype=np.int32) % k
    rng.shuffle(labels)
    noise = rng.normal(size=(n, model["features"])).astype(np.float32)
    features = means[labels] + np.float32(data["noise"]) * noise
    return ({"features": features, "labels": labels},
            traffic_gen.partition(labels, traffic))


def federated_data(bank: dict, parts, config: dict, seed: int):
    from repro.data import FederatedData

    return FederatedData.from_partition(bank["features"], bank["labels"], parts,
                                        train_frac=config["data"]["train_frac"], seed=seed)


def make_weights(seed: int, config: dict):
    m = config["model"]

    def init(key):
        k1, k2 = jax.random.split(key)
        return {"w1": jax.random.normal(k1, (m["features"], m["hidden"])) / np.sqrt(m["features"]),
                "b1": jnp.zeros((m["hidden"],)),
                "w2": jax.random.normal(k2, (m["hidden"], m["n_classes"])) / np.sqrt(m["hidden"]),
                "b2": jnp.zeros((m["n_classes"],))}

    return jax.jit(init)(jax.random.PRNGKey(seed))


def forward_flops(config: dict) -> int:
    m = config["model"]
    return 2 * (m["features"] * m["hidden"] + m["hidden"] * m["n_classes"])


def param_count(config: dict) -> int:
    m = config["model"]
    return (m["features"] + 1) * m["hidden"] + (m["hidden"] + 1) * m["n_classes"]


def reference(config: dict):
    def correct(params, batch, mask):
        hit = jnp.argmax(_forward(params, batch["features"]), -1) == batch["labels"]
        return jnp.sum(jnp.where(mask, hit, False))

    return (lambda params, batch: _cross_entropy(params, batch["features"], batch["labels"]),
            correct)
