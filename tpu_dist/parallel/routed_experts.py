"""Sparse experts held by share: a sigmoid router over ALL the routed
experts, group-limited top-k, no capacity and nothing dropped, and a
grouped product over the experts THIS chip holds.

``parallel/expert.py``'s ``MixtureOfExperts`` is the GShard shape (softmax
gate, k of 1 or 2, dense ``[G, n, E, C]`` dispatch, overflow dropped by a
capacity factor). This layer is what expert parallelism asks of a chip
when the experts outnumber the chips: it is **told which experts it
holds** (``experts_held = (first, count)``), routes every token over the
published router width, computes the chosen experts that lie in its range,
adds the shared expert, and **leaves out what absent experts would add**.
On one chip there is no exchange, and no code stands in for the absent
chips or their traffic; the parts that all the shares give, with the
shared expert counted once, add up to the uncut layer (tests pin it).

The product: the ``tokens x top_k`` assignments are sorted by expert, the
rows of held experts first and in expert order, and three grouped products
run over the groups (``ops/grouped_matmul.grouped_dot``: on a TPU a kernel
that visits only the row tiles of ``ROW_TILE`` sorted rows which hold a
row with an expert, and reads each touched expert's matrix once a tile;
elsewhere ``jax.lax.ragged_dot``). Whatever the router does, nothing is
dropped and no bound is assumed: if every choice falls on a held expert,
every row tile is visited. Router, scores and weights are float32, the
router's product at ``highest``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from tpu_dist.models.layers import Layer
from tpu_dist.ops import initializers
from tpu_dist.ops.grouped_matmul import ROW_TILE, grouped_dot

def route(scores, bias, *, top_k: int, n_group: int, topk_group: int,
          scaling: float):
    """``scores`` [T, E] (sigmoid, float32) -> ``(chosen [T, k] expert
    ids, weights [T, k])``. Experts are chosen by ``scores + bias``: a
    group's score is the sum of its two best, the ``topk_group`` best
    groups stay, the ``top_k`` best experts in them win. Weights come from
    the unbiased scores, normalised over the chosen and scaled."""
    t, e = scores.shape
    biased = scores + bias
    per_group = biased.reshape(t, n_group, e // n_group)
    group_score = jnp.sum(jax.lax.top_k(per_group, 2)[0], axis=-1)
    kept = jax.lax.top_k(group_score, topk_group)[1]          # [T, keep]
    in_kept = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    masked = jnp.where(jnp.repeat(in_kept, e // n_group, axis=1), biased,
                       -jnp.inf)
    chosen = jax.lax.top_k(masked, top_k)[1]
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    weights = picked / jnp.sum(picked, axis=1, keepdims=True)
    return chosen, scaling * weights


@dataclasses.dataclass(frozen=True, repr=False)
class RoutedExperts(Layer):
    """The routed part a chip holds plus the shared expert; see the
    module docstring. ``shared_ff_dim=0`` leaves the shared expert out
    (how a test counts it once over several shares)."""

    scope_kind = "moe"
    num_experts: int                 #: the router's width, all chips
    experts_held: tuple              #: (first, count) of those held here
    top_k: int
    n_group: int
    topk_group: int
    ff_dim: int
    shared_ff_dim: int = 0
    routed_scaling: float = 1.0

    def __post_init__(self):
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(
                f"experts_held {self.experts_held} is not a range of the "
                f"{self.num_experts} routed experts")
        if self.num_experts % self.n_group:
            raise ValueError("n_group must divide num_experts")
        object.__setattr__(self, "experts_held", (int(first), int(count)))

    def init(self, key, in_shape):
        d, f = in_shape[-1], self.ff_dim
        held = self.experts_held[1]
        glorot = initializers.get("glorot_uniform")
        ks = jax.random.split(key, 8)
        params = {
            "router": glorot(ks[0], (d, self.num_experts)),
            "bias": jnp.zeros((self.num_experts,), jnp.float32),
            "wg": glorot(ks[1], (held, d, f)),
            "wu": glorot(ks[2], (held, d, f)),
            "wd": glorot(ks[3], (held, f, d)),
        }
        if self.shared_ff_dim:
            params.update(
                shared_wg=glorot(ks[4], (d, self.shared_ff_dim)),
                shared_wu=glorot(ks[5], (d, self.shared_ff_dim)),
                shared_wd=glorot(ks[6], (self.shared_ff_dim, d)))
        return params, {}, in_shape

    def choose(self, params, flat):
        """Tokens ``flat`` [T, d] -> ``(chosen [T, k], weights [T, k])``."""
        logits = jnp.matmul(flat.astype(jnp.float32),
                            params["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        return route(jax.nn.sigmoid(logits), params["bias"],
                     top_k=self.top_k, n_group=self.n_group,
                     topk_group=self.topk_group, scaling=self.routed_scaling)

    def forward(self, params, x, valid=None):
        """``x`` [.., d] -> ``(y [.., d], stats)``. ``valid`` [..] marks
        the tokens that are somebody's (all when None): the rest (an empty
        slot, a chunk's padding) choose nothing, so they reach no expert
        but the shared one and are not counted. ``stats``, int32:
        ``[assignments made, those that fell on held experts, held experts
        touched, fullest held expert's tokens, sorted rows in the row
        tiles of ``ROW_TILE`` that hold a row with an expert, sorted rows
        in all]``: what the grouped product below computes."""
        first, count = self.experts_held
        d = x.shape[-1]
        flat = x.reshape(-1, d)
        t, k = flat.shape[0], self.top_k
        chosen, weights = self.choose(params, flat)
        local = chosen - first
        held = (local >= 0) & (local < count)
        if valid is not None:
            held &= valid.reshape(-1)[:, None]
        # Rows sorted by expert held; every other row behind them, in a
        # group of no expert, which the grouped product never visits.
        group = jnp.where(held, local, count).reshape(-1)          # [T * k]
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((count + 1,), jnp.int32).at[group].add(1)[:count]
        rows = flat[order // k]                                    # [T*k, d]
        h = (jax.nn.silu(grouped_dot(rows, params["wg"], sizes))
             * grouped_dot(rows, params["wu"], sizes))
        out = grouped_dot(h, params["wd"], sizes)
        scale = jnp.where(held, weights, 0.0).reshape(-1)[order]
        out = jnp.where(scale[:, None] != 0.0,
                        out.astype(jnp.float32) * scale[:, None], 0.0)
        back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
        y = out[back].reshape(t, k, d).sum(axis=1).astype(x.dtype)
        if self.shared_ff_dim:
            from tpu_dist.models.hybrid import swiglu

            y = y + swiglu(flat, params["shared_wg"], params["shared_wu"],
                           params["shared_wd"])
        made = (t if valid is None else jnp.sum(valid)) * k
        n_held = jnp.sum(sizes)
        stats = jnp.stack([jnp.asarray(made, jnp.int32), n_held,
                           jnp.sum(sizes > 0).astype(jnp.int32),
                           jnp.max(sizes),
                           jnp.minimum(-(-n_held // ROW_TILE) * ROW_TILE,
                                       t * k),
                           jnp.asarray(t * k, jnp.int32)])
        return y.reshape(x.shape), stats

    def apply(self, params, state, x, *, training=False, rng=None):
        return self.forward(params, x)[0], state
