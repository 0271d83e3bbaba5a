"""Plain GPT-2 for the benchmark: weights from a seed, forward, loss,
gradients and Adam, in straightforward ``jax.numpy`` and float32.

This file imports nothing of ``tpu_dist`` and takes nothing that the
program has made. It follows OpenAI's GPT-2 (pre-LN blocks, learned
positions, tanh-GELU, LayerNorm eps 1e-5) with the departures that
``PERF.md`` section 4 states for the repo's block: separate biased
``wq/wk/wv`` projections instead of one ``c_attn``, and an untied, biased
vocabulary head.

Sizes (``cfg``) use the published key names: ``n_vocab, n_ctx, n_embd,
n_head, n_layer`` and ``n_inner`` (4 * n_embd).

Layer parameters are stacked on a leading ``n_layer`` axis, so a leaf is
named ``wte`` or ``h.wq`` and ``h.*`` leaves hold every layer. ``quant``
selects the precision of every matrix multiplication's operands:

* ``None``   — float32, and the caller sets ``highest`` matmul precision;
* ``"fp8"``  — operands rounded to float8 e4m3 with one scale per tensor
  (the control: the nearest precision below the bf16 the cells state);
* ``"bf16"`` — operands rounded to bfloat16 (a witness, never a limit).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5
#: Stacked (per-layer) leaves: name -> (shape builder, kind).
_BLOCK_LEAVES = (
    ("ln1_g", lambda d, f: (d,), "ones"), ("ln1_b", lambda d, f: (d,), "bias"),
    ("wq", lambda d, f: (d, d), "matrix"), ("bq", lambda d, f: (d,), "bias"),
    ("wk", lambda d, f: (d, d), "matrix"), ("bk", lambda d, f: (d,), "bias"),
    ("wv", lambda d, f: (d, d), "matrix"), ("bv", lambda d, f: (d,), "bias"),
    ("wo", lambda d, f: (d, d), "matrix"), ("bo", lambda d, f: (d,), "bias"),
    ("ln2_g", lambda d, f: (d,), "ones"), ("ln2_b", lambda d, f: (d,), "bias"),
    ("w1", lambda d, f: (d, f), "matrix"), ("b1", lambda d, f: (f,), "bias"),
    ("w2", lambda d, f: (f, d), "matrix"), ("b2", lambda d, f: (d,), "bias"),
)
BLOCK_LEAF_NAMES = tuple(n for n, _, _ in _BLOCK_LEAVES)


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _leaf(key, shape, kind):
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind in ("bias", "embed"):
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    # Glorot-uniform matrices, the family the repo's layers draw from.
    limit = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def make_params(key, cfg: dict) -> dict:
    """Every weight of the model from one key; jit this whole."""
    d, f, v = cfg["n_embd"], cfg["n_inner"], cfg["n_vocab"]
    n_layer = cfg["n_layer"]
    names = ["wte", "wpe", "lnf_b", "head_w", "head_b"] + [
        "h." + n for n in BLOCK_LEAF_NAMES]
    keys = dict(zip(names, jax.random.split(key, len(names))))
    params = {
        "wte": _leaf(keys["wte"], (v, d), "embed"),
        "wpe": _leaf(keys["wpe"], (cfg["n_ctx"], d), "embed"),
        "lnf_g": jnp.ones((d,), jnp.float32),
        "lnf_b": _leaf(keys["lnf_b"], (d,), "bias"),
        "head_w": _leaf(keys["head_w"], (d, v), "matrix"),
        "head_b": _leaf(keys["head_b"], (v,), "bias"),
    }
    for name, shape_of, kind in _BLOCK_LEAVES:
        params["h." + name] = _leaf(keys["h." + name],
                                    (n_layer, *shape_of(d, f)), kind)
    return params


# -- forward --------------------------------------------------------------


def _round_operand(x, quant):
    """``x`` rounded to the lower precision, straight through: the
    backward pass sees the rounded operands but is itself not rounded (an
    unscaled float8 cotangent would flush to zero, which is a crash and
    not a lower precision)."""
    if quant is None:
        return x
    if quant == "bf16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif quant == "fp8":
        amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
        scale = jnp.where(amax > 0, amax / 448.0, 1.0)
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(f"unknown quant {quant!r}")
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, quant):
    return jnp.matmul(_round_operand(a, quant), _round_operand(b, quant))


def _layer_norm(x, g, b):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, n_head, quant):
    """One pre-LN block on ``x`` [B, L, D]; ``p`` holds one layer."""
    b, ln, d = x.shape
    dk = d // n_head
    h = _layer_norm(x, p["ln1_g"], p["ln1_b"])

    def heads(w, bias):
        y = _mm(h, w, quant) + bias
        return y.reshape(b, ln, n_head, dk).transpose(0, 2, 1, 3)

    q, k, v = (heads(p["wq"], p["bq"]), heads(p["wk"], p["bk"]),
               heads(p["wv"], p["bv"]))
    s = _mm(q, k.transpose(0, 1, 3, 2), quant) / math.sqrt(dk)
    mask = jnp.tril(jnp.ones((ln, ln), bool))
    s = jnp.where(mask, s, -jnp.inf)
    a = _mm(jax.nn.softmax(s, axis=-1), v, quant)
    a = a.transpose(0, 2, 1, 3).reshape(b, ln, d)
    x = x + _mm(a, p["wo"], quant) + p["bo"]
    h = _layer_norm(x, p["ln2_g"], p["ln2_b"])
    h = _gelu(_mm(h, p["w1"], quant) + p["b1"])
    return x + _mm(h, p["w2"], quant) + p["b2"]


def forward(params: dict, tokens, cfg: dict, *, quant=None, remat=False):
    """Logits [B, L, n_vocab] of int tokens [B, L]: the full-sequence
    causal forward, no cache, no kernel."""
    ln = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][:ln]
    stacked = {n: params["h." + n] for n in BLOCK_LEAF_NAMES}
    block = functools.partial(_block, n_head=cfg["n_head"], quant=quant)
    if remat:
        block = jax.checkpoint(block)

    def body(x, p):
        return block(x, p), None

    x, _ = jax.lax.scan(body, x, stacked)
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"])
    return _mm(x, params["head_w"], quant) + params["head_b"]


# -- training -------------------------------------------------------------


def loss_sum(params, x, y, cfg, quant=None):
    """Summed next-token cross-entropy of rows ``x`` against ``y``."""
    logits = forward(params, x, cfg, quant=quant, remat=True)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], axis=-1).sum()


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
            out[name] = jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
    return out


class TrainReference:
    """The first steps of training, block of rows by block of rows so that
    the float32 activations fit beside the parameters and Adam's state.

    ``keep_rows`` plants the faults the controls read: a fraction of every
    batch is left out and the mean taken over the rest.
    """

    def __init__(self, cfg: dict, *, lr: float, quant=None, rows_per_block=2,
                 keep_rows: float = 1.0, freeze: bool = False, devices=None):
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


# -- serving --------------------------------------------------------------


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
