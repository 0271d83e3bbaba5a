"""What every family's plain reference shares: a key from the seed, Adam,
the first steps of training in blocks of rows, leaf norms, and the
served-token comparison.

This file imports nothing of ``tpu_dist`` and knows no model family. A
family module (``harness/cells.py`` states the interface) hands in its
``loss_sum`` and its ``forward``; everything here works on the flat dict
of weights its ``make_params`` returns, in which a leaf named
``h.<leaf>`` holds every layer on a leading axis.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


def leaf_norms(tree: dict) -> dict:
    """L2 norm of every leaf; a stacked leaf gives one norm per layer,
    named ``h<i>.<leaf>``."""
    out = {}
    for name, a in tree.items():
        if name.startswith("h."):
            per = jnp.sqrt(jnp.sum(
                jnp.square(a.astype(jnp.float32)),
                axis=tuple(range(1, a.ndim))))
            for i in range(a.shape[0]):
                out[f"h{i}.{name[2:]}"] = per[i]
        else:
            out[name] = norm(a)
    return out


def adam_update(params, grads, mu, nu, step, *, lr, b1=0.9, b2=0.999,
                eps=1e-7):
    """Adam as Keras states it (epsilon outside the root, bias correction
    folded into the step size); ``step`` counts from 1."""
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g,
                                nu, grads)
    scale = lr * math.sqrt(1 - b2 ** step) / (1 - b1 ** step)
    params = jax.tree_util.tree_map(
        lambda p, m, n: p - scale * m / (jnp.sqrt(n) + eps), params, mu, nu)
    return params, mu, nu


class TrainReference:
    """The first steps of training, block of rows by block of rows so that
    the float32 activations fit beside the parameters and Adam's state.

    ``loss_sum(params, x, y, cfg, quant)`` is the family's summed
    next-token loss. ``keep_rows`` plants the faults the controls read: a
    fraction of every batch is left out and the mean taken over the rest.
    """

    def __init__(self, loss_sum, cfg: dict, *, lr: float, quant=None,
                 rows_per_block=2, keep_rows: float = 1.0,
                 freeze: bool = False, devices=None):
        self.cfg, self.lr, self.quant = cfg, float(lr), quant
        self.rows_per_block = int(rows_per_block)
        self.keep_rows = float(keep_rows)
        self.freeze = bool(freeze)
        #: Blocks of rows go round the cell's chips, each summing its own;
        #: the state and the update stay on the first.
        self.devices = list(devices or jax.devices()[:1])
        self._grad = jax.jit(jax.value_and_grad(
            functools.partial(loss_sum, cfg=cfg, quant=quant)))
        self._add = jax.jit(
            lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
            donate_argnums=(0,))
        self._norms = jax.jit(leaf_norms)
        self._delta_norms = jax.jit(lambda a, b: leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, a, b)))

    def gradient(self, params, x, y):
        """(mean loss, gradient of the mean loss) over the rows kept."""
        rows = max(1, int(round(x.shape[0] * self.keep_rows)))
        x, y = x[:rows], y[:rows]
        devs = self.devices
        copies = [params] + [jax.device_put(params, d) for d in devs[1:]]
        parts, sums = [], [None] * len(devs)
        for j, i in enumerate(range(0, rows, self.rows_per_block)):
            k = j % len(devs)
            xb = jax.device_put(x[i:i + self.rows_per_block], devs[k])
            yb = jax.device_put(y[i:i + self.rows_per_block], devs[k])
            part, g = self._grad(copies[k], xb, yb)
            parts.append(part)
            sums[k] = g if sums[k] is None else self._add(sums[k], g)
        acc = sums[0]
        for other in sums[1:]:
            if other is not None:
                acc = self._add(acc, jax.device_put(other, devs[0]))
        n = rows * x.shape[1]
        total = sum(float(p) for p in parts)
        return total / n, jax.tree_util.tree_map(lambda g: g / n, acc)

    def run(self, params, batches) -> dict:
        """Follow ``batches`` (a list of (x, y) host arrays). Returns the
        numbers the comparison reads, as host floats: ``losses``, the
        first gradient's leaf norms ``grad_norms`` and the leaf norms of
        the parameters' change after the last step ``delta_norms``."""
        start = params
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        # Gradients and both moments are donated: beside the start and the
        # current parameters there is one copy of each, not two.
        update = jax.jit(functools.partial(adam_update, lr=self.lr),
                         static_argnames=("step",), donate_argnums=(1, 2, 3))
        losses, grad_norms = [], None
        for step, (x, y) in enumerate(batches, start=1):
            loss, grads = self.gradient(params, x, y)
            losses.append(loss)
            if grad_norms is None:
                grad_norms = jax.device_get(self._norms(grads))
            if not self.freeze:
                params, mu, nu = update(params, grads, mu, nu, step=step)
            del grads
        delta = jax.device_get(self._delta_norms(params, start))
        return {"losses": [float(v) for v in losses],
                "grad_norms": {k: float(v) for k, v in grad_norms.items()},
                "delta_norms": {k: float(v) for k, v in delta.items()}}


def served_rows(forward_fn, params, prompt, served, pad_to: int):
    """One full-sequence forward over ``prompt + served`` (teacher forced,
    padded to ``pad_to``); returns the logits rows from which each served
    token was picked: served token j comes from position
    ``len(prompt) - 1 + j``."""
    seq = list(prompt) + list(served)
    x = np.zeros((1, pad_to), np.int32)
    x[0, :len(seq)] = seq
    logits = forward_fn(params, jnp.asarray(x))[0]
    return np.asarray(
        logits[len(prompt) - 1:len(prompt) - 1 + len(served)], np.float32)


def gap_in_sigmas(ref_rows, tokens):
    """(reference max - reference logit of ``tokens``) / sigma, per row."""
    ref_rows = np.asarray(ref_rows, np.float32)
    tokens = np.asarray(tokens)
    best = ref_rows.max(axis=-1)
    chosen = ref_rows[np.arange(len(tokens)), tokens]
    return (best - chosen) / ref_rows.std(axis=-1)
