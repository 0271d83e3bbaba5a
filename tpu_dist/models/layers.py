"""Neural-net layers: pure-functional, shape-inferring, Keras-surface-compatible.

Covers the layer vocabulary the reference model needs (SURVEY.md R5:
Conv2D / MaxPooling2D / Flatten / Dense with relu activations,
tf_dist_example.py:40-49) plus BatchNormalization / pooling / Dropout for the
ResNet benchmark models (BASELINE.md configs 4-5).

Design (the idiom shift from Keras, SURVEY.md D4/D17): a layer is an immutable
*description*; parameters and mutable state (BatchNorm running stats) live in
plain pytrees owned by the caller:

    params, state, out_shape = layer.init(key, in_shape)   # shapes sans batch
    y, new_state = layer.apply(params, state, x, training=True)

Everything is jit-traceable; there are no Python-side variables to mirror —
replication is a sharding annotation on the pytrees (tpu_dist.parallel.mesh).
TPU notes: convs/matmuls use NHWC / HWIO layouts which XLA maps onto the MXU;
``compute_dtype=bfloat16`` (via models.Policy) casts activations while keeping
params and BN statistics in float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from tpu_dist.ops import initializers
from tpu_dist.utils import profiler

Params = Any
State = Any
Shape = tuple[int, ...]

_ACTIVATIONS = {
    None: lambda x: x,
    "linear": lambda x: x,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    "softmax": lambda x: jax.nn.softmax(x, axis=-1),
}


def _activation(name) -> Callable:
    if callable(name):
        return name
    if name not in _ACTIVATIONS:
        raise ValueError(
            f"unknown activation {name!r}; available: "
            f"{sorted(k for k in _ACTIVATIONS if k)}")
    return _ACTIVATIONS[name]


def _pair(v) -> tuple[int, int]:
    if isinstance(v, int):
        return (v, v)
    a, b = v
    return (int(a), int(b))


class Layer:
    """Stateless layer description."""

    #: The kind of device scope the layer's work runs under
    #: (``profiler.LAYER_SCOPES``; a point layer's unless a kind says
    #: otherwise); ``None`` for a container, whose layers carry their own.
    scope_kind = "point"

    def init(self, key, in_shape: Shape) -> tuple[Params, State, Shape]:
        """Returns (params, state, out_shape); shapes exclude the batch dim."""
        raise NotImplementedError

    def apply(self, params: Params, state: State, x, *,
              training: bool = False, rng=None) -> tuple[Any, State]:
        raise NotImplementedError

    @property
    def kind(self) -> str:
        return type(self).__name__.lower()

    def __repr__(self):
        fields = getattr(self, "__dataclass_fields__", {})
        attrs = ", ".join(f"{k}={getattr(self, k)!r}" for k in fields)
        return f"{type(self).__name__}({attrs})"


@dataclasses.dataclass(frozen=True, repr=False)
class Conv2D(Layer):
    """2-D convolution, NHWC. Reference uses Conv2D(32|64, 3, relu)
    (tf_dist_example.py:42, 44)."""

    filters: int
    kernel_size: int | tuple[int, int]
    strides: int | tuple[int, int] = 1
    padding: str = "valid"  # Keras Conv2D default
    activation: Optional[str] = None
    use_bias: bool = True
    kernel_initializer: str = "glorot_uniform"

    def init(self, key, in_shape):
        h, w, cin = in_shape
        kh, kw = _pair(self.kernel_size)
        kernel = initializers.get(self.kernel_initializer)(
            key, (kh, kw, cin, self.filters))
        params = {"kernel": kernel}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.filters,), jnp.float32)
        sh, sw = _pair(self.strides)
        if self.padding.upper() == "SAME":
            oh, ow = -(-h // sh), -(-w // sw)
        else:
            oh, ow = (h - kh) // sh + 1, (w - kw) // sw + 1
        return params, {}, (oh, ow, self.filters)

    def apply(self, params, state, x, *, training=False, rng=None):
        kernel = params["kernel"].astype(x.dtype)
        if self._use_im2col(x):
            y = _conv_im2col(x, kernel)
        else:
            y = jax.lax.conv_general_dilated(
                x, kernel,
                window_strides=_pair(self.strides),
                padding=self.padding.upper(),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
        if self.use_bias:
            y = y + params["bias"].astype(y.dtype)
        return _activation(self.activation)(y), state

    def _use_im2col(self, x):
        """CPU stem fast path: XLA:CPU's conv GRADIENTS are naive loops
        (r3 audit: the 1-channel 28x28 stem's fwd+bwd took 61 ms at batch
        128 vs 17 ms as slice-concat patches + one matmul, whose backward
        is matmuls + static pads). Only worth it while the patch blowup
        (kh*kw*cin columns) stays small — wide-channel convs lose to the
        native path. TPU always takes lax conv (MXU-native)."""
        if jax.default_backend() != "cpu":
            return False
        kh, kw = _pair(self.kernel_size)
        return (_pair(self.strides) == (1, 1)
                and self.padding.upper() == "VALID"
                and kh * kw * x.shape[-1] <= 64)


def _conv_im2col(x, w):
    """VALID stride-1 conv as slice-concat patches + one matmul — same
    contraction, CPU-friendly gradients (see Conv2D._use_im2col)."""
    kh, kw, cin, cout = w.shape
    b, h, ww_, _ = x.shape
    oh, ow = h - kh + 1, ww_ - kw + 1
    cols = [x[:, i:i + oh, j:j + ow, :]
            for i in range(kh) for j in range(kw)]
    patches = jnp.concatenate(cols, axis=-1)       # [B, oh, ow, kh*kw*cin]
    out = patches.reshape(b * oh * ow, kh * kw * cin) @ w.reshape(
        kh * kw * cin, cout)
    return out.reshape(b, oh, ow, cout)


def _nonoverlap_maxpool(xw):
    """Max over the window axes of a [B, OH, WH, OW, WW, C] view.

    DOCUMENTED gradient divergence on tied window maxima (common
    post-ReLU): plain ``jnp.max``'s VJP SPLITS the cotangent across ties,
    while TPU's reduce_window routes it to one element. Both are valid
    subgradients; r4 implemented the exact one-hot routing three ways
    (argmax-forward, cumsum-mask backward, static slice-loop backward)
    and every custom_vjp formulation cost 30-45 % of the WHOLE CPU train
    step — custom_vjp is a fusion barrier right between the conv stacks,
    and this fast path exists purely for CPU speed (the reference's own
    silicon). The split-tie gradient is kept and pinned in
    tests/test_models.py::test_pool_tie_gradient_splits; expected loss is
    unaffected (both subgradients are members of the subdifferential),
    only per-element credit assignment under exact ties differs."""
    return jnp.max(xw, axis=(2, 4))


def _pool(x, window, strides, padding, init_val, op):
    wh, ww = _pair(window)
    sh, sw = _pair(strides)
    if ((sh, sw) == (wh, ww) and padding.upper() == "VALID"
            and op in (jax.lax.max, jax.lax.add)
            and jax.default_backend() == "cpu"):
        # Non-overlapping windows (the reference's pool_size=2 default):
        # reshape + axis-reduce is exactly reduce_window VALID forward
        # (both crop trailing rows/cols). CPU-only: XLA:CPU lowers
        # select_and_scatter to a ~200 ms/step scatter loop at the
        # reference's batch (pools were 2/3 of the whole step); TPU keeps
        # reduce_window (MXU/VPU-native). Tie-gradient semantics: see
        # _nonoverlap_maxpool (documented split-tie divergence).
        b, h, w, c = x.shape
        oh, ow = h // wh, w // ww
        x = x[:, :oh * wh, :ow * ww, :]
        x = x.reshape(b, oh, wh, ow, ww, c)
        if op is jax.lax.max:
            return _nonoverlap_maxpool(x)
        return jnp.sum(x, axis=(2, 4))
    return jax.lax.reduce_window(
        x, init_val, op,
        window_dimensions=(1, wh, ww, 1),
        window_strides=(1, sh, sw, 1),
        padding=padding.upper(),
    )


@dataclasses.dataclass(frozen=True, repr=False)
class MaxPooling2D(Layer):
    """Max pool — reference default pool_size=2 (tf_dist_example.py:43, 45)."""

    pool_size: int | tuple[int, int] = 2
    strides: Optional[int | tuple[int, int]] = None
    padding: str = "valid"

    def _strides(self):
        return self.strides if self.strides is not None else self.pool_size

    def init(self, key, in_shape):
        h, w, c = in_shape
        ph, pw = _pair(self.pool_size)
        sh, sw = _pair(self._strides())
        if self.padding.upper() == "SAME":
            oh, ow = -(-h // sh), -(-w // sw)
        else:
            oh, ow = (h - ph) // sh + 1, (w - pw) // sw + 1
        return {}, {}, (oh, ow, c)

    def apply(self, params, state, x, *, training=False, rng=None):
        return _pool(x, self.pool_size, self._strides(), self.padding,
                     -jnp.inf, jax.lax.max), state


@dataclasses.dataclass(frozen=True, repr=False)
class AveragePooling2D(MaxPooling2D):
    def apply(self, params, state, x, *, training=False, rng=None):
        summed = _pool(x, self.pool_size, self._strides(), self.padding,
                       jnp.array(0, x.dtype), jax.lax.add)
        if self.padding.upper() == "SAME":
            # Keras averages over VALID window elements only — divide by the
            # per-position count, not the full window size.
            counts = _pool(jnp.ones(x.shape[1:3], x.dtype)[None, :, :, None],
                           self.pool_size, self._strides(), self.padding,
                           jnp.array(0, x.dtype), jax.lax.add)
            return summed / counts, state
        ph, pw = _pair(self.pool_size)
        return summed / jnp.array(ph * pw, x.dtype), state


@dataclasses.dataclass(frozen=True, repr=False)
class GlobalAveragePooling2D(Layer):
    def init(self, key, in_shape):
        h, w, c = in_shape
        return {}, {}, (c,)

    def apply(self, params, state, x, *, training=False, rng=None):
        return jnp.mean(x, axis=(1, 2)), state


@dataclasses.dataclass(frozen=True, repr=False)
class Flatten(Layer):
    """tf_dist_example.py:46."""

    def init(self, key, in_shape):
        return {}, {}, (math.prod(in_shape),)

    def apply(self, params, state, x, *, training=False, rng=None):
        return x.reshape(x.shape[0], -1), state


@dataclasses.dataclass(frozen=True, repr=False)
class Dense(Layer):
    """Fully connected — reference uses Dense(128, relu) and Dense(10)
    (tf_dist_example.py:47-48)."""

    units: int
    activation: Optional[str] = None
    use_bias: bool = True
    kernel_initializer: str = "glorot_uniform"

    def init(self, key, in_shape):
        # Applies to the LAST axis (Keras Dense semantics): a (L, D) input
        # (transformer token stream) maps to (L, units), a (D,) input to
        # (units,).
        din = in_shape[-1]
        params = {"kernel": initializers.get(self.kernel_initializer)(
            key, (din, self.units))}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.units,), jnp.float32)
        return params, {}, (*in_shape[:-1], self.units)

    def apply(self, params, state, x, *, training=False, rng=None):
        y = x @ params["kernel"].astype(x.dtype)
        if self.use_bias:
            y = y + params["bias"].astype(y.dtype)
        return _activation(self.activation)(y), state


@dataclasses.dataclass(frozen=True, repr=False)
class Activation(Layer):
    activation: str = "relu"

    def init(self, key, in_shape):
        return {}, {}, in_shape

    def apply(self, params, state, x, *, training=False, rng=None):
        return _activation(self.activation)(x), state


ReLU = lambda: Activation("relu")


@dataclasses.dataclass(frozen=True, repr=False)
class BatchNormalization(Layer):
    """Batch norm over the channel axis with running statistics.

    Running mean/var live in ``state`` (float32 always); in a distributed step
    the batch statistics are computed over the *global* batch automatically —
    the batch axis is sharded, so XLA all-reduces the moment sums (sync-BN for
    free; contrast TF where SyncBatchNormalization is a separate layer).
    """

    momentum: float = 0.99
    epsilon: float = 1e-3
    center: bool = True
    scale: bool = True

    def init(self, key, in_shape):
        c = in_shape[-1]
        params = {}
        if self.scale:
            params["gamma"] = jnp.ones((c,), jnp.float32)
        if self.center:
            params["beta"] = jnp.zeros((c,), jnp.float32)
        state = {"mean": jnp.zeros((c,), jnp.float32),
                 "var": jnp.ones((c,), jnp.float32)}
        return params, state, in_shape

    def apply(self, params, state, x, *, training=False, rng=None):
        reduce_axes = tuple(range(x.ndim - 1))
        if training:
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=reduce_axes)
            var = jnp.var(xf, axis=reduce_axes)
            m = self.momentum
            new_state = {"mean": m * state["mean"] + (1 - m) * mean,
                         "var": m * state["var"] + (1 - m) * var}
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = jax.lax.rsqrt(var + self.epsilon)
        y = (x.astype(jnp.float32) - mean) * inv
        if self.scale:
            y = y * params["gamma"]
        if self.center:
            y = y + params["beta"]
        return y.astype(x.dtype), new_state


def unique_layer_names(layers: Sequence[Layer]) -> list[str]:
    """kind, kind_1, kind_2, ... — stable param-tree keys for a layer list."""
    import collections

    counts: collections.Counter = collections.Counter()
    names = []
    for layer in layers:
        k = layer.kind
        names.append(k if counts[k] == 0 else f"{k}_{counts[k]}")
        counts[k] += 1
    return names


def init_chain(layers: Sequence[Layer], names: Sequence[str], key, in_shape):
    """Initialize a layer chain; returns (params, state, out_shape)."""
    params: dict = {}
    state: dict = {}
    shape = tuple(in_shape)
    keys = jax.random.split(key, max(len(layers), 1))
    for layer, name, k in zip(layers, names, keys):
        p, s, shape = layer.init(k, shape)
        if p:
            params[name] = p
        if s:
            state[name] = s
    return params, state, shape


def apply_chain(layers: Sequence[Layer], names: Sequence[str], params, state,
                x, *, training: bool, rng, head: bool = False):
    """Apply a layer chain; returns (y, new_state). Dropout layers receive
    per-layer keys folded from ``rng``. Each layer runs under its device
    scope (``scope_kind``); with ``head`` the last one is the model's
    head."""
    new_state = dict(state) if state else {}
    for i, (layer, name) in enumerate(zip(layers, names)):
        p = params.get(name, {}) if params else {}
        s = state.get(name, {}) if state else {}
        # Every layer gets a per-position key (containers thread it down to
        # nested Dropouts); layers that don't use randomness ignore it.
        layer_rng = jax.random.fold_in(rng, i) if rng is not None else None
        kind = ("head" if head and i == len(layers) - 1
                else layer.scope_kind)
        with profiler.layer_scope(kind):
            x, s_new = layer.apply(p, s, x, training=training, rng=layer_rng)
        if s_new:
            new_state[name] = s_new
    return x, new_state


@dataclasses.dataclass(frozen=True, repr=False)
class Block(Layer):
    """A named sub-stack of layers — composable container for deep models
    (ResNet stages, BASELINE.md configs 4-5). Params/state nest under the
    sublayer names."""

    layers: tuple = ()
    scope_kind = None

    def __post_init__(self):
        object.__setattr__(self, "layers", tuple(self.layers))
        object.__setattr__(self, "_names",
                           tuple(unique_layer_names(self.layers)))

    def init(self, key, in_shape):
        return init_chain(self.layers, self._names, key, in_shape)

    def apply(self, params, state, x, *, training=False, rng=None):
        return apply_chain(self.layers, self._names, params, state, x,
                           training=training, rng=rng)


@dataclasses.dataclass(frozen=True, repr=False)
class Residual(Layer):
    """``activation(main(x) + shortcut(x))`` — the residual connection.

    ``shortcut=()`` is the identity skip; a projection (1x1 conv + BN) goes
    there when shapes change. The building block of the ResNet benchmark
    models; XLA fuses the add into the preceding conv/BN epilogue on TPU.
    """

    main: tuple = ()
    shortcut: tuple = ()
    activation: Optional[str] = "relu"
    scope_kind = None

    def __post_init__(self):
        object.__setattr__(self, "main", tuple(self.main))
        object.__setattr__(self, "shortcut", tuple(self.shortcut))
        object.__setattr__(self, "_main_names",
                           tuple(unique_layer_names(self.main)))
        object.__setattr__(self, "_short_names",
                           tuple(unique_layer_names(self.shortcut)))

    def init(self, key, in_shape):
        k_main, k_short = jax.random.split(key)
        p_main, s_main, out_main = init_chain(self.main, self._main_names,
                                              k_main, in_shape)
        p_short, s_short, out_short = init_chain(
            self.shortcut, self._short_names, k_short, in_shape)
        if out_main != out_short:
            raise ValueError(
                f"residual branches disagree: main -> {out_main}, "
                f"shortcut -> {out_short}")
        params = {"main": p_main}
        state = {}
        if p_short:
            params["shortcut"] = p_short
        if s_main:
            state["main"] = s_main
        if s_short:
            state["shortcut"] = s_short
        return params, state, out_main

    def apply(self, params, state, x, *, training=False, rng=None):
        # Distinct rng per branch: a Dropout at position i of each branch must
        # not draw the same fold_in(rng, i) key (correlated masks otherwise).
        rng_main = rng_short = None
        if rng is not None:
            rng_main, rng_short = jax.random.split(rng)
        y, s_main = apply_chain(
            self.main, self._main_names, params.get("main", {}),
            state.get("main", {}) if state else {}, x,
            training=training, rng=rng_main)
        sc, s_short = apply_chain(
            self.shortcut, self._short_names, params.get("shortcut", {}),
            state.get("shortcut", {}) if state else {}, x,
            training=training, rng=rng_short)
        new_state = {}
        if s_main:
            new_state["main"] = s_main
        if s_short:
            new_state["shortcut"] = s_short
        return _activation(self.activation)(y + sc), new_state


@dataclasses.dataclass(frozen=True, repr=False)
class Dropout(Layer):
    rate: float = 0.5

    def init(self, key, in_shape):
        if not 0.0 <= self.rate < 1.0:
            raise ValueError(f"dropout rate must be in [0, 1), got {self.rate}")
        return {}, {}, in_shape

    def apply(self, params, state, x, *, training=False, rng=None):
        if not training or self.rate == 0.0:
            return x, state
        if rng is None:
            raise ValueError("Dropout needs an rng during training; "
                             "fit() threads one automatically")
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype), state
