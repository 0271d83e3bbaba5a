"""Pallas TPU kernels — the hand-written escape hatch (SURVEY.md §2.4).

The reference's native compute path is TF's C++/CUDA kernels; on TPU the
idiomatic equivalent is XLA-compiled programs, and SURVEY.md §2.4 reserves
Pallas for ops worth fusing beyond what XLA does: "a Pallas kernel for a fused
scale-and-cross-entropy or custom reduction is the escape hatch". Implemented
here:

* :func:`fused_sparse_cross_entropy` — softmax-cross-entropy from logits with
  integer labels, forward and backward each as ONE VMEM-resident kernel:
  max / logsumexp / label-gather fused (forward), softmax-minus-onehot fused
  (backward), with a `jax.custom_vjp` tying them together. Replaces 4-5
  separate HLO reductions/gathers with one pass over the logits block.

* :func:`fused_sgd_apply` — the whole SGD/momentum parameter update as ONE
  kernel over the flattened parameter buffer: every leaf ravels into a
  single padded fp32 vector, so N params x L leaves becomes one grid sweep
  (p, g[, v] in; p'[, v'] out) instead of 2-3 elementwise HLO ops PER LEAF.
  The win is launch/fusion overhead on many-leaf models, the same
  launch-count economics the bucketed all-reduce targets on the comm side.

* :func:`fused_adam_apply` — the same packed-buffer treatment for Adam:
  both moment updates and the bias-corrected parameter step in ONE kernel
  (p, g, m, v + a (1, 1) scalar step-size in; p', m', v' out). The
  bias-correction scale is a scalar *operand* rather than a baked
  constant, so the step counter advancing never retraces the kernel and
  scheduled learning rates work unchanged.

Kernels run on TPU; every entry point takes ``interpret=`` (Pallas interpreter,
used by the CPU test suite) and the public wrapper falls back to the plain
jnp implementation on non-TPU backends, so the framework is correct
everywhere and fast where it matters.

Grid strategy: 1-D over batch tiles; each program owns a ``(TILE_B, C)``
logits block in VMEM (classes padded to the 128-lane by Mosaic). Labels ride
along as a ``(TILE_B, 1)`` int32 block; the one-hot is built with
``broadcasted_iota`` (TPU needs >= 2-D iota).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TILE_B = 128  # batch rows per program; fp32 sublane min is 8, MXU-friendly

#: VMEM bytes per (TILE_B, C) fp32 buffer before the tile shrinks. The bwd
#: kernel holds ~5 such buffers (logits in, dlogits out, double-buffered
#: pipelining); 2 MB each stays well inside the 16 MB scoped-vmem limit —
#: at vocab-scale C (8192+) the old fixed 128-row tile blew it (r3: 20.25M
#: scoped allocation compiling the transformer-LM fused loss).
_TILE_BYTES = 2 * 1024 * 1024


def _pick_tile(batch: int, classes: int = 0) -> int:
    cap = TILE_B
    if classes:
        while cap > 8 and cap * classes * 4 > _TILE_BYTES:
            cap //= 2
        if cap * classes * 4 > _TILE_BYTES:
            return 0  # even 8 rows blow VMEM (vocab > 64k): use jnp path
    for t in (128, 64, 32, 16, 8):
        if t <= cap and batch % t == 0:
            return t
    return batch  # tiny/ragged batch: single tile


# -- forward ------------------------------------------------------------------


def _ce_fwd_kernel(logits_ref, labels_ref, loss_ref, lse_ref):
    """loss_i = logsumexp(logits_i) - logits_i[label_i]; stashes the lse."""
    logits = logits_ref[:].astype(jnp.float32)          # (TB, C)
    labels = labels_ref[:]                               # (TB, 1) int32
    m = jnp.max(logits, axis=-1, keepdims=True)          # (TB, 1)
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)) + m
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, dimension=1)
    picked = jnp.sum(jnp.where(cols == labels, logits, 0.0), axis=-1,
                     keepdims=True)                      # (TB, 1)
    loss_ref[:] = (lse - picked)
    lse_ref[:] = lse


def _ce_fwd(logits, labels, *, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, c = logits.shape
    # The interpreter has no VMEM limit: ignore the class-width budget there
    # (tile 0 = "won't fit on hardware" must not reach the grid divide).
    tb = _pick_tile(b, 0 if interpret else c)
    labels2 = labels.astype(jnp.int32).reshape(b, 1)
    loss, lse = pl.pallas_call(
        _ce_fwd_kernel,
        grid=(b // tb,),
        in_specs=[
            pl.BlockSpec((tb, c), lambda i: (i, 0),
                         memory_space=pl.ANY if interpret else pltpu.VMEM),
            pl.BlockSpec((tb, 1), lambda i: (i, 0),
                         memory_space=pl.ANY if interpret else pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tb, 1), lambda i: (i, 0),
                         memory_space=pl.ANY if interpret else pltpu.VMEM),
            pl.BlockSpec((tb, 1), lambda i: (i, 0),
                         memory_space=pl.ANY if interpret else pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
        ],
        interpret=interpret,
    )(logits, labels2)
    return loss[:, 0], lse


# -- backward -----------------------------------------------------------------


def _ce_bwd_kernel(logits_ref, labels_ref, lse_ref, g_ref, dlogits_ref):
    """dlogits = (softmax(logits) - onehot(labels)) * g."""
    logits = logits_ref[:].astype(jnp.float32)
    labels = labels_ref[:]
    lse = lse_ref[:]
    g = g_ref[:]
    probs = jnp.exp(logits - lse)                        # softmax via saved lse
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, dimension=1)
    onehot = (cols == labels).astype(jnp.float32)
    dlogits_ref[:] = ((probs - onehot) * g).astype(dlogits_ref.dtype)


def _ce_bwd(logits, labels, lse, g, *, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, c = logits.shape
    tb = _pick_tile(b, 0 if interpret else c)
    labels2 = labels.astype(jnp.int32).reshape(b, 1)
    g2 = g.astype(jnp.float32).reshape(b, 1)
    space = pl.ANY if interpret else pltpu.VMEM
    return pl.pallas_call(
        _ce_bwd_kernel,
        grid=(b // tb,),
        in_specs=[
            pl.BlockSpec((tb, c), lambda i: (i, 0), memory_space=space),
            pl.BlockSpec((tb, 1), lambda i: (i, 0), memory_space=space),
            pl.BlockSpec((tb, 1), lambda i: (i, 0), memory_space=space),
            pl.BlockSpec((tb, 1), lambda i: (i, 0), memory_space=space),
        ],
        out_specs=pl.BlockSpec((tb, c), lambda i: (i, 0), memory_space=space),
        out_shape=jax.ShapeDtypeStruct((b, c), logits.dtype),
        interpret=interpret,
    )(logits, labels2, lse, g2)


# -- public op with custom VJP ------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused_ce(logits, labels, interpret):
    loss, _ = _ce_fwd(logits, labels, interpret=interpret)
    return loss


def _fused_ce_fwd(logits, labels, interpret):
    loss, lse = _ce_fwd(logits, labels, interpret=interpret)
    return loss, (logits, labels, lse)


def _fused_ce_bwd(interpret, residuals, g):
    logits, labels, lse = residuals
    dlogits = _ce_bwd(logits, labels, lse, g, interpret=interpret)
    return dlogits, None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fused_sparse_cross_entropy(logits, labels, *,
                               interpret: bool | None = None):
    """Per-example softmax CE from logits, Pallas-fused on TPU.

    [B, C] logits x [B] int labels -> [B] losses, differentiable w.r.t.
    ``logits``. On non-TPU backends (and for ragged shapes Pallas can't tile)
    this is the plain jnp computation — bit-comparable results either way.
    ``interpret=True`` forces the Pallas interpreter (CPU-testable path).

    Builder-measured r2 on a v5e (benchmarks/pallas_ce_bench.py; record
    removed in PR 21, to be re-measured): the fused FORWARD beat XLA's
    fusion by 1.11-1.41x across (128..8192) x (10..1024); the fwd+bwd pair
    only broke even at the largest shape (1.10x at 8192x1024) and LOST at
    small ones (0.65x at 128x10) — XLA's own rematerialized backward is
    already good. Hence this stays OPT-IN
    (``SparseCategoricalCrossentropy(fused=True)``).
    """
    # Rank-general: [.., C] logits with [..] labels flatten to one [B, C]
    # kernel call (the LM loss arrives as [B, L, V]); losses reshape back.
    lead = logits.shape[:-1]
    if logits.ndim != 2:
        logits = logits.reshape(-1, logits.shape[-1])
        labels = labels.reshape(-1)
    if interpret is None:
        interpret = False
        # Fall back to jnp math off-TPU, for batches whose only tile is
        # sublane-unaligned (Mosaic wants multiples of 8 rows), and for
        # vocabularies so wide even an 8-row tile blows the VMEM budget
        # (_pick_tile returns 0).
        tile = _pick_tile(*logits.shape)
        if not _on_tpu() or tile == 0 or tile % 8 != 0:
            from tpu_dist.ops.losses import sparse_categorical_crossentropy

            return sparse_categorical_crossentropy(
                logits, labels, from_logits=True).reshape(lead)
    return _fused_ce(logits, labels, interpret).reshape(lead)


# -- fused SGD/momentum update ------------------------------------------------

#: Lane width of the flattened update buffer; fp32 Mosaic tiles are (8, 128),
#: so the padded vector reshapes to (rows, 128) with rows a multiple of 8.
_SGD_LANES = 128
_SGD_SUBLANES = 8


def _sgd_kernel(lr, p_ref, g_ref, out_ref):
    out_ref[:] = p_ref[:] - lr * g_ref[:]


def _sgd_momentum_kernel(lr, m, nesterov, p_ref, g_ref, v_ref,
                         newp_ref, newv_ref):
    nv = m * v_ref[:] - lr * g_ref[:]
    newv_ref[:] = nv
    if nesterov:
        newp_ref[:] = p_ref[:] + m * nv - lr * g_ref[:]
    else:
        newp_ref[:] = p_ref[:] + nv


def _flatten_padded(leaves):
    """Ravel + concat leaves into one fp32 (rows, 128) buffer, rows padded
    to the sublane multiple. Returns (buffer, sizes, total)."""
    sizes = [int(l.size) for l in leaves]
    total = sum(sizes)
    flat = jnp.concatenate(
        [jnp.ravel(l).astype(jnp.float32) for l in leaves])
    chunk = _SGD_LANES * _SGD_SUBLANES
    padded = -(-max(total, 1) // chunk) * chunk
    flat = jnp.pad(flat, (0, padded - total))
    return flat.reshape(padded // _SGD_LANES, _SGD_LANES), sizes, total


def _unflatten(buf, leaves, sizes, total, treedef):
    flat = buf.reshape(-1)[:total]
    out, offset = [], 0
    for leaf, size in zip(leaves, sizes):
        out.append(flat[offset:offset + size]
                   .reshape(jnp.shape(leaf)).astype(leaf.dtype))
        offset += size
    return jax.tree_util.tree_unflatten(treedef, out)


def _sgd_pallas_call(kernel, n_in, n_out, buf_shape, *, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = buf_shape[0]
    tb = next(t for t in (128, 64, 32, 16, 8) if rows % t == 0)
    space = pl.ANY if interpret else pltpu.VMEM
    spec = pl.BlockSpec((tb, _SGD_LANES), lambda i: (i, 0),
                        memory_space=space)
    outs = [jax.ShapeDtypeStruct(buf_shape, jnp.float32)] * n_out
    return pl.pallas_call(
        kernel,
        grid=(rows // tb,),
        in_specs=[spec] * n_in,
        out_specs=[spec] * n_out if n_out > 1 else spec,
        out_shape=outs if n_out > 1 else outs[0],
        interpret=interpret,
    )


def fused_sgd_apply(params, grads, velocity=None, *, learning_rate: float,
                    momentum: float = 0.0, nesterov: bool = False,
                    interpret: bool | None = None):
    """One-kernel SGD/momentum update over a whole parameter pytree.

    Returns ``(new_params, new_velocity)`` (``new_velocity is None`` when
    ``momentum == 0``). Math matches :class:`tpu_dist.ops.optimizers.SGD`
    leaf-for-leaf — the update runs in fp32 over the packed buffer and
    casts back per leaf, so non-fp32 leaves agree to allclose rather than
    bitwise. ``learning_rate``/``momentum`` must be Python floats (a
    scheduled lr is a traced scalar; callers keep the jnp path for those).
    Off-TPU the plain tree_map math runs unless ``interpret=True`` forces
    the Pallas interpreter (the CPU-testable path).
    """
    leaves, treedef = jax.tree_util.tree_flatten(params)
    if interpret is None:
        interpret = False
        if not _on_tpu() or not leaves:
            return _sgd_jnp(params, grads, velocity,
                            lr=learning_rate, m=momentum, nesterov=nesterov)
    if not leaves:
        return _sgd_jnp(params, grads, velocity,
                        lr=learning_rate, m=momentum, nesterov=nesterov)
    lr = float(learning_rate)
    m = float(momentum)
    g_leaves = [jnp.asarray(g) for g in jax.tree_util.tree_leaves(grads)]
    p_buf, sizes, total = _flatten_padded(
        [jnp.asarray(l) for l in leaves])
    g_buf, _, _ = _flatten_padded(g_leaves)
    if m == 0.0:
        call = _sgd_pallas_call(
            functools.partial(_sgd_kernel, lr), 2, 1, p_buf.shape,
            interpret=interpret)
        new_p = call(p_buf, g_buf)
        return _unflatten(new_p, leaves, sizes, total, treedef), None
    v_leaves = [jnp.asarray(v)
                for v in jax.tree_util.tree_leaves(velocity)]
    v_buf, _, _ = _flatten_padded(v_leaves)
    call = _sgd_pallas_call(
        functools.partial(_sgd_momentum_kernel, lr, m, bool(nesterov)),
        3, 2, p_buf.shape, interpret=interpret)
    new_p, new_v = call(p_buf, g_buf, v_buf)
    return (_unflatten(new_p, leaves, sizes, total, treedef),
            _unflatten(new_v, v_leaves, sizes, total, treedef))


def _sgd_jnp(params, grads, velocity, *, lr, m, nesterov):
    """The reference tree_map math (optimizers.SGD), for off-TPU calls."""
    if m == 0.0:
        return (jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                       params, grads), None)
    new_vel = jax.tree_util.tree_map(
        lambda v, g: m * v - lr * g, velocity, grads)
    if nesterov:
        new_params = jax.tree_util.tree_map(
            lambda p, v, g: p + m * v - lr * g, params, new_vel, grads)
    else:
        new_params = jax.tree_util.tree_map(
            lambda p, v: p + v, params, new_vel)
    return new_params, new_vel


# -- fused Adam update --------------------------------------------------------


def _adam_kernel(b1, b2, eps, p_ref, g_ref, m_ref, v_ref, scale_ref,
                 newp_ref, newm_ref, newv_ref):
    """m/v moment update + bias-corrected parameter step, one pass.

    The betas and epsilon bake into the program (fixed per optimizer
    instance); the bias-correction scale ``lr * sqrt(1-b2^t)/(1-b1^t)``
    depends on the traced step counter, so it rides in as a (1, 1)
    scalar operand — one compiled kernel serves every step instead of
    retracing as ``t`` advances."""
    g = g_ref[:]
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * g * g
    newm_ref[:] = m
    newv_ref[:] = v
    newp_ref[:] = p_ref[:] - scale_ref[0, 0] * m / (jnp.sqrt(v) + eps)


def fused_adam_apply(params, grads, mu, nu, *, scale, beta_1: float = 0.9,
                     beta_2: float = 0.999, epsilon: float = 1e-7,
                     interpret: bool | None = None):
    """One-kernel Adam update over a whole parameter pytree.

    Returns ``(new_params, new_mu, new_nu)``. Math matches
    :class:`tpu_dist.ops.optimizers.Adam` leaf-for-leaf — the update runs
    in fp32 over the packed buffer and casts back per leaf, so non-fp32
    leaves agree to allclose rather than bitwise. ``scale`` is the
    bias-corrected step size ``lr * sqrt(1 - b2^t) / (1 - b1^t)`` — a
    traced scalar is fine (scheduled learning rates included): it enters
    the kernel as a scalar operand, not a baked constant, so step
    advancement never retraces. ``beta_1``/``beta_2``/``epsilon`` must be
    Python floats. Off-TPU the plain tree_map math runs unless
    ``interpret=True`` forces the Pallas interpreter (the CPU-testable
    path).
    """
    from jax.experimental import pallas as pl

    leaves, treedef = jax.tree_util.tree_flatten(params)
    if interpret is None:
        interpret = False
        if not _on_tpu() or not leaves:
            return _adam_jnp(params, grads, mu, nu, scale=scale,
                             b1=beta_1, b2=beta_2, eps=epsilon)
    if not leaves:
        return _adam_jnp(params, grads, mu, nu, scale=scale,
                         b1=beta_1, b2=beta_2, eps=epsilon)
    from jax.experimental.pallas import tpu as pltpu

    b1, b2, eps = float(beta_1), float(beta_2), float(epsilon)
    p_buf, sizes, total = _flatten_padded(
        [jnp.asarray(l) for l in leaves])
    g_buf, _, _ = _flatten_padded(
        [jnp.asarray(g) for g in jax.tree_util.tree_leaves(grads)])
    m_leaves = [jnp.asarray(m) for m in jax.tree_util.tree_leaves(mu)]
    n_leaves = [jnp.asarray(n) for n in jax.tree_util.tree_leaves(nu)]
    m_buf, _, _ = _flatten_padded(m_leaves)
    n_buf, _, _ = _flatten_padded(n_leaves)
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    rows = p_buf.shape[0]
    tb = next(t for t in (128, 64, 32, 16, 8) if rows % t == 0)
    space = pl.ANY if interpret else pltpu.VMEM
    spec = pl.BlockSpec((tb, _SGD_LANES), lambda i: (i, 0),
                        memory_space=space)
    # Every grid step reads the same (1, 1) scale block — scalar memory
    # on hardware, ANY under the interpreter.
    sspec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pl.ANY if interpret else pltpu.SMEM)
    new_p, new_m, new_n = pl.pallas_call(
        functools.partial(_adam_kernel, b1, b2, eps),
        grid=(rows // tb,),
        in_specs=[spec, spec, spec, spec, sspec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct(p_buf.shape, jnp.float32)] * 3,
        interpret=interpret,
    )(p_buf, g_buf, m_buf, n_buf, scale_arr)
    return (_unflatten(new_p, leaves, sizes, total, treedef),
            _unflatten(new_m, m_leaves, sizes, total, treedef),
            _unflatten(new_n, n_leaves, sizes, total, treedef))


def _adam_jnp(params, grads, mu, nu, *, scale, b1, b2, eps):
    """The reference tree_map math (optimizers.Adam), for off-TPU calls."""
    new_mu = jax.tree_util.tree_map(
        lambda m, g: b1 * m + (1.0 - b1) * g, mu, grads)
    new_nu = jax.tree_util.tree_map(
        lambda n, g: b2 * n + (1.0 - b2) * jnp.square(g), nu, grads)
    new_params = jax.tree_util.tree_map(
        lambda p, m, n: p - scale * m / (jnp.sqrt(n) + eps),
        params, new_mu, new_nu)
    return new_params, new_mu, new_nu
