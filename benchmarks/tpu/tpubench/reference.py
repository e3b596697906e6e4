"""Plain reference of a pFedSOP federation: the clients' splits and
per-round sampling, local SGD, the Gompertz blend and Sherman-Morrison
step, the masked eval and the Eq. 13 mean.  Written from the paper's
equations in plain ``jax.numpy``; it imports nothing of the program.  The
model is the family's (``families/<family>.py``, ``reference``): its mean
loss over a batch of bank rows and its count of right answers.

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


def _client_round(hp: Hyper, loss, params, delta, has_delta, global_delta, global_has,
                  batches):
    """Algorithm 1 (personalize, when both updates exist) then T steps of
    SGD over ``batches`` (T batches of bank rows); returns (final params,
    new local update, mean loss)."""
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
        value, g = jax.value_and_grad(loss)(p, batch)
        return jax.tree.map(lambda x, gi: x - hp.eta2 * gi, p, g), value

    final, losses = jax.lax.scan(step, start, batches)
    new_delta = jax.tree.map(lambda a, b: (a - b) / hp.eta2, start, final)
    return final, new_delta, jnp.mean(losses)


class ReferenceFederation:
    """Replays a federation's rounds from the seed in plain JAX.

    ``bank`` is the family's sample bank (a dict of arrays with one
    leading sample axis; floating arrays are read in ``dtype``), ``loss``
    and ``correct`` its reference model (``families/<family>.py``).
    States live in a dict, one entry per client that has trained; the
    others hold the initial weights and a zero update."""

    def __init__(self, bank: dict, split: ClientSplit, init, hp: Hyper,
                 kprime: int, local_iters: int, batch: int, seed: int,
                 loss, correct, dtype=jnp.float32):
        self.bank, self.split = bank, split
        self.dtype = dtype
        self.init = jax.tree.map(lambda x: jnp.asarray(x, dtype), init)
        self.hp, self.kprime, self.T, self.B = hp, kprime, local_iters, batch
        self.rng = np.random.RandomState(seed)
        self.states = {}          # client -> (params, delta, rounds seen)
        self.global_delta = jax.tree.map(jnp.zeros_like, self.init)
        self.global_has = False
        self._train = jax.jit(jax.vmap(
            lambda p, d, h, gd, gh, b: _client_round(hp, loss, p, d, h, gd, gh, b),
            in_axes=(0, 0, 0, None, None, 0)))
        self._hits = jax.jit(correct)
        self._mean = jax.jit(lambda t: jax.tree.map(lambda x: jnp.mean(x, 0), t))

    def _rows(self, idx):
        """The bank's rows ``idx`` (any shape) on the device."""
        return {k: jnp.asarray(v[idx], self.dtype if np.issubdtype(v.dtype, np.floating)
                               else None) for k, v in self.bank.items()}

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
            hits += int(self._hits(params, self._rows(pad), jnp.asarray(mask)))
        return hits / len(rows)

    def run_round(self) -> dict:
        ids, rows = self.split.sample_round(self.rng, self.kprime, self.T, self.B)
        states = [self._state(c) for c in ids]
        stack = lambda i: jax.tree.map(lambda *xs: jnp.stack(xs), *[s[i] for s in states])
        has = jnp.asarray([s[2] > 0 for s in states])
        final, deltas, losses = self._train(
            stack(0), stack(1), has, self.global_delta, jnp.asarray(self.global_has),
            self._rows(rows))
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
