"""Plain reference of a pFedSOP federation over a ResNet: the model, the
weights made from the seed, the clients' splits and per-round sampling,
local SGD, the Gompertz blend and Sherman-Morrison step, the masked eval
and the Eq. 13 mean.  Written from the paper's equations in plain
``jax.numpy``; it imports nothing of the program.

``dtype=float32`` under ``precision="highest"`` is the reference; the
same code in ``bfloat16`` is the control that ``correct`` has to reject.
The client splits and the sampling copy ``repro.data.federated`` call
for call, so the reference sees the rows the program trained on.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

EVAL_ROWS = 256  # rows per eval block


# -- weights ------------------------------------------------------------------


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


def make_weights(seed: int, model: dict):
    """The initial weights, made on the device in one jitted call."""
    return jax.jit(lambda key: init_params(key, model))(jax.random.PRNGKey(seed))


# -- model --------------------------------------------------------------------


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


# -- clients' data -------------------------------------------------------------


class ClientSplit:
    """Each client's 80/20 train/test split and the per-round draws, made
    exactly as the program's ``FederatedData`` makes them."""

    def __init__(self, parts, train_frac: float, seed: int):
        rng = np.random.RandomState(seed)
        self.train, self.test = [], []
        for idx in parts:
            idx = np.array(idx, np.int64)
            rng.shuffle(idx)
            k = max(1, int(round(train_frac * len(idx)))) if len(idx) else 0
            self.train.append(idx[:k])
            self.test.append(idx[k:] if len(idx) - k > 0 else idx[:1])
        self.train_counts = np.array([len(t) for t in self.train], np.int64)

    def local_iters(self, batch: int) -> int:
        return max(1, int(np.ceil(max(1.0, float(self.train_counts.mean())) / batch)))

    def sample_round(self, rng, kprime: int, local_iters: int, batch: int):
        """(client ids, (K', T, B) sample indices)."""
        ids = rng.choice(len(self.train), kprime, replace=False)
        slots = rng.randint(0, np.maximum(1, self.train_counts[ids])[:, None, None],
                            size=(kprime, local_iters, batch))
        rows = np.stack([self.train[i][s] if len(self.train[i]) else np.zeros_like(s)
                         for i, s in zip(ids, slots)])
        return ids, rows


# -- one federation -------------------------------------------------------------


@dataclass(frozen=True)
class Hyper:
    eta1: float
    eta2: float
    rho: float
    lam: float
    eps: float = 1e-12


def _leaves_dot(a, b):
    return sum(jnp.sum(x * y) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _client_round(hp: Hyper, params, delta, has_delta, global_delta, global_has,
                  images, labels):
    """Algorithm 1 (personalize, when both updates exist) then T steps of
    SGD; returns (final params, new local update, mean loss)."""
    dot = _leaves_dot(delta, global_delta)
    denom = jnp.sqrt(_leaves_dot(delta, delta)) * jnp.sqrt(
        _leaves_dot(global_delta, global_delta))
    ok = denom > hp.eps
    sim = jnp.clip(jnp.where(ok, dot / jnp.where(ok, denom, 1), 0), -1, 1)
    beta = 1 - jnp.exp(-jnp.exp(-hp.lam * (jnp.arccos(sim) - 1)))   # Eq. 14
    dp = jax.tree.map(lambda d, g: (1 - beta) * d + beta * g, delta, global_delta)
    sq = _leaves_dot(dp, dp)
    coeff = 1 / hp.rho - sq / (hp.rho ** 2 + hp.rho * sq)             # Eq. 18
    can = jnp.logical_and(has_delta, global_has)
    start = jax.tree.map(lambda x, d: jnp.where(can, x - hp.eta1 * coeff * d, x),
                         params, dp)                                  # Eq. 19

    def step(p, batch):
        loss, g = jax.value_and_grad(cross_entropy)(p, *batch)
        return jax.tree.map(lambda x, gi: x - hp.eta2 * gi, p, g), loss

    final, losses = jax.lax.scan(step, start, (images, labels))
    new_delta = jax.tree.map(lambda a, b: (a - b) / hp.eta2, start, final)
    return final, new_delta, jnp.mean(losses)


def _correct_rows(params, images, labels, mask):
    hit = jnp.argmax(forward(params, images), -1) == labels
    return jnp.sum(jnp.where(mask, hit, False))


class ReferenceFederation:
    """Replays a federation's rounds from the seed in plain JAX.

    States live in a dict, one entry per client that has trained; the
    others hold the initial weights and a zero update."""

    def __init__(self, images, labels, split: ClientSplit, init, hp: Hyper,
                 kprime: int, local_iters: int, batch: int, seed: int,
                 dtype=jnp.float32):
        self.images, self.labels, self.split = images, labels, split
        self.dtype = dtype
        self.init = jax.tree.map(lambda x: jnp.asarray(x, dtype), init)
        self.hp, self.kprime, self.T, self.B = hp, kprime, local_iters, batch
        self.rng = np.random.RandomState(seed)
        self.states = {}          # client -> (params, delta, rounds seen)
        self.global_delta = jax.tree.map(jnp.zeros_like, self.init)
        self.global_has = False
        self._train = jax.jit(jax.vmap(
            lambda p, d, h, gd, gh, x, y: _client_round(hp, p, d, h, gd, gh, x, y),
            in_axes=(0, 0, 0, None, None, 0, 0)))
        self._hits = jax.jit(_correct_rows)
        self._mean = jax.jit(lambda t: jax.tree.map(lambda x: jnp.mean(x, 0), t))

    def _state(self, c):
        zero = jax.tree.map(jnp.zeros_like, self.init)
        return self.states.get(int(c), (self.init, zero, 0))

    def _accuracy(self, params, c) -> float:
        rows = self.split.test[c]
        if len(rows) == 0:
            return 0.0
        hits = 0
        for lo in range(0, len(rows), EVAL_ROWS):
            blk = rows[lo:lo + EVAL_ROWS]
            pad = np.zeros(EVAL_ROWS, np.int64)
            pad[:len(blk)] = blk
            mask = np.arange(EVAL_ROWS) < len(blk)
            hits += int(self._hits(params, jnp.asarray(self.images[pad], self.dtype),
                                   jnp.asarray(self.labels[pad]), jnp.asarray(mask)))
        return hits / len(rows)

    def run_round(self) -> dict:
        ids, rows = self.split.sample_round(self.rng, self.kprime, self.T, self.B)
        states = [self._state(c) for c in ids]
        stack = lambda i: jax.tree.map(lambda *xs: jnp.stack(xs), *[s[i] for s in states])
        has = jnp.asarray([s[2] > 0 for s in states])
        final, deltas, losses = self._train(
            stack(0), stack(1), has, self.global_delta, jnp.asarray(self.global_has),
            jnp.asarray(self.images[rows], self.dtype), jnp.asarray(self.labels[rows]))
        accs = []
        for j, c in enumerate(ids):
            p = jax.tree.map(lambda x: x[j], final)
            self.states[int(c)] = (p, jax.tree.map(lambda x: x[j], deltas),
                                   states[j][2] + 1)
            accs.append(self._accuracy(p, int(c)))
        self.global_delta = self._mean(deltas)
        self.global_has = True
        return {"loss": float(np.mean(np.asarray(losses, np.float64))),
                "acc": float(np.mean(accs)), "ids": ids}

    def state_norms(self, n_clients: int) -> dict:
        """Per-leaf norms over all clients of the params' change from the
        initial weights and of the stored updates, and each client's
        round count."""
        sq_p = {k: 0.0 for k in leaf_names(self.init)}
        sq_d = dict(sq_p)
        seen = np.zeros(n_clients, np.int64)
        for c, (p, d, n) in self.states.items():
            seen[c] = n
            dp, dd = _sq_change(p, self.init, d)
            for k, v in leaf_items(dp):
                sq_p[k] += float(v)
            for k, v in leaf_items(dd):
                sq_d[k] += float(v)
        return {"params": {k: float(np.sqrt(v)) for k, v in sq_p.items()},
                "delta": {k: float(np.sqrt(v)) for k, v in sq_d.items()},
                "rounds_seen": seen}


@jax.jit
def _sq_change(params, init, delta):
    sq = lambda x: jnp.sum(jnp.square(x.astype(jnp.float32)))
    return (jax.tree.map(lambda a, b: sq(a.astype(jnp.float32) - b.astype(jnp.float32)),
                         params, init),
            jax.tree.map(sq, delta))


# -- leaf naming shared with the program's readings ------------------------------


def leaf_items(tree):
    return [(jax.tree_util.keystr(path), leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_names(tree):
    return [k for k, _ in leaf_items(tree)]


def leaf_norms(tree) -> dict:
    """{leaf path: L2 norm} of a pytree (f32 on the device)."""
    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))(tree)
    return {k: float(v) for k, v in leaf_items(norms)}
