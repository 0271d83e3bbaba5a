"""Model containers: functional core + Keras-style compile/fit surface.

Reproduces the high-level training API the reference script uses (SURVEY.md
R5/R6, L5): ``Sequential([...])`` -> ``compile(loss, optimizer, metrics)`` ->
``fit(dataset, epochs, steps_per_epoch)`` (tf_dist_example.py:39-59), so the
reference example ports line-for-line. Underneath, a Model is two pure
functions over pytrees —

    variables = model.init(seed, input_shape)        # {'params':…, 'state':…}
    logits, new_state = model.apply(variables['params'], variables['state'],
                                    x, training=True, rng=key)

— which is exactly what the jitted SPMD train step consumes. ``compile``
captures the active strategy from the surrounding ``strategy.scope()``
(tf_dist_example.py:56-57 semantics): under TF the scope intercepts variable
creation; here it pins which mesh the variables will be replicated onto when
``fit`` first touches them.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax

from tpu_dist.models.layers import Layer
from tpu_dist.ops import losses as losses_lib
from tpu_dist.ops import metrics as metrics_lib
from tpu_dist.ops import optimizers as optimizers_lib

Variables = dict  # {'params': pytree, 'state': pytree}


class Model:
    """A named pair of (init_fn, apply_fn) plus compile/fit surface.

    init_fn(key, input_shape) -> (params, state)
    apply_fn(params, state, x, training, rng) -> (outputs, new_state)
    """

    def __init__(self, init_fn: Callable, apply_fn: Callable,
                 input_shape: Optional[tuple] = None, name: str = "model"):
        self._init_fn = init_fn
        self._apply_fn = apply_fn
        self.input_shape = input_shape
        self.name = name
        # Set by compile():
        self.optimizer = None
        self.loss = None
        self.metrics: list = []
        self.steps_per_execution = 1
        self.gradient_bucket_bytes = 0
        self.prefetch_to_device = 0
        self.strategy = None
        self._trainer = None
        self._carryover: Optional[dict] = None  # weights across recompiles

    # -- functional core -----------------------------------------------------

    def init(self, seed: int | jax.Array = 0,
             input_shape: Optional[tuple] = None) -> Variables:
        shape = input_shape or self.input_shape
        if shape is None:
            raise ValueError(
                f"{self.name}: input_shape unknown; pass it to init() or set "
                "it on the model")
        key = jax.random.PRNGKey(seed) if isinstance(seed, int) else seed
        params, state = self._init_fn(key, tuple(shape))
        return {"params": params, "state": state}

    def apply(self, params, state, x, *, training: bool = False, rng=None):
        return self._apply_fn(params, state, x, training, rng)

    def __call__(self, variables: Variables, x, *, training: bool = False,
                 rng=None):
        out, _ = self.apply(variables["params"], variables["state"], x,
                            training=training, rng=rng)
        return out

    # -- Keras-style training surface (SURVEY.md D15/D16) ---------------------

    def compile(self, optimizer="sgd", loss=None, metrics=(),
                steps_per_execution: int = 1,
                gradient_bucket_bytes: int = 0,
                prefetch_to_device: int = 0) -> None:
        """Record loss/optimizer/metrics and capture the scoped strategy
        (tf_dist_example.py:50-53 surface).

        ``steps_per_execution``: run K train steps inside one compiled
        dispatch (``lax.scan``) — the Keras knob of the same name; a large
        win when per-step device time is smaller than host dispatch overhead
        (tiny-model training; SURVEY.md hard-part #5). Batch-level callbacks
        and the progress bar then advance once per execution.

        ``gradient_bucket_bytes``: 0 (default) keeps the fused schedule —
        one implicit end-of-step gradient all-reduce, scheduled by the XLA
        partitioner. > 0 switches the train step to the explicit bucketed
        schedule: gradients are reduced in reverse-topological buckets of
        roughly this many bytes so early buckets overlap the remaining
        backward compute (README.md "Step-time performance"; the schedules
        agree to float tolerance, not bitwise — the bucketed step averages
        per-shard means).

        ``prefetch_to_device``: 0 (default) fetches each batch on the hot
        loop; > 0 double-buffers input — a background thread device_puts up
        to this many batches ahead while the current step runs, driving the
        trainer's measured ``data_wait_s`` toward zero.
        """
        from tpu_dist.parallel.strategy import get_strategy

        if steps_per_execution < 1:
            raise ValueError(
                f"steps_per_execution must be >= 1, got {steps_per_execution}")
        if gradient_bucket_bytes < 0:
            raise ValueError(
                f"gradient_bucket_bytes must be >= 0, got "
                f"{gradient_bucket_bytes}")
        if prefetch_to_device < 0:
            raise ValueError(
                f"prefetch_to_device must be >= 0, got {prefetch_to_device}")
        self.optimizer = optimizers_lib.get(optimizer)
        self.loss = losses_lib.get(loss) if loss is not None else None
        self.metrics = [metrics_lib.get(m) for m in metrics]
        self.steps_per_execution = int(steps_per_execution)
        self.gradient_bucket_bytes = int(gradient_bucket_bytes)
        self.prefetch_to_device = int(prefetch_to_device)
        self.strategy = get_strategy()
        # Invalidate the jitted step but carry trained weights forward —
        # recompiling must not reset a trained model (Keras fine-tuning
        # workflow). Optimizer slots are re-created (shapes/algorithm may
        # have changed).
        if self._trainer is not None and self._trainer.variables is not None:
            self._carryover = {
                k: self._trainer.variables[k] for k in ("params", "state")}
        self._trainer = None

    def fit(self, x, epochs: int = 1, steps_per_epoch: Optional[int] = None,
            verbose: int = 1, callbacks: Sequence = (), initial_epoch: int = 0,
            seed: int = 0, profile_dir: Optional[str] = None,
            validation_data=None, validation_steps: Optional[int] = None,
            checkpoint_dir: Optional[str] = None,
            class_weight: Optional[dict] = None):
        """Run the epoch/step loop (tf_dist_example.py:59 surface).

        ``profile_dir`` captures a chief-only jax.profiler trace (SURVEY.md
        §5.1). ``validation_data`` runs a validation pass each epoch,
        reported as ``val_``-prefixed logs. ``checkpoint_dir`` enables
        chief-only per-epoch checkpointing AND resume-from-latest (SURVEY.md
        §5.4): if the directory already holds checkpoints, training continues
        from the epoch after the newest one. ``class_weight`` scales each
        sample's loss contribution by its class's weight (Keras semantics
        for imbalanced data; the weight table compiles into the step)."""
        from tpu_dist.training.trainer import Trainer

        if self.loss is None or self.optimizer is None:
            raise RuntimeError(
                f"{self.name} must be compile()d with a loss and optimizer "
                "before fit()")
        if self._trainer is None:
            self._trainer = Trainer(self)
        return self._trainer.fit(
            x, epochs=epochs, steps_per_epoch=steps_per_epoch,
            verbose=verbose, callbacks=callbacks, initial_epoch=initial_epoch,
            seed=seed, profile_dir=profile_dir,
            validation_data=validation_data,
            validation_steps=validation_steps,
            checkpoint_dir=checkpoint_dir,
            class_weight=class_weight)

    def evaluate(self, x, steps: Optional[int] = None, verbose: int = 1):
        from tpu_dist.training.trainer import Trainer

        if self.loss is None:
            raise RuntimeError(
                f"{self.name} must be compile()d with a loss before "
                "evaluate()")
        if self._trainer is None:
            self._trainer = Trainer(self)
        return self._trainer.evaluate(x, steps=steps, verbose=verbose)

    def predict(self, x):
        from tpu_dist.training.trainer import Trainer

        if self._trainer is None:
            self._trainer = Trainer(self)
        return self._trainer.predict(x)

    def make_train_function(self, steps_per_execution: Optional[int] = None):
        """The jitted SPMD train step (Keras-2 name; SURVEY.md D15) — see
        ``Trainer.make_train_function`` for the callable's contract."""
        from tpu_dist.training.trainer import Trainer

        if self.loss is None or self.optimizer is None:
            raise RuntimeError(
                f"{self.name} must be compile()d with a loss and optimizer "
                "before make_train_function()")
        if self._trainer is None:
            self._trainer = Trainer(self)
        return self._trainer.make_train_function(steps_per_execution)

    def train_state(self) -> tuple:
        """Fresh ``(params, state, opt, metrics, loss_acc)`` for the
        ``make_train_function`` callable."""
        from tpu_dist.training.trainer import Trainer

        if self._trainer is None:
            self._trainer = Trainer(self)
        return self._trainer.train_state()

    @property
    def variables(self) -> Optional[Variables]:
        """Live training variables, once fit/evaluate has materialized them."""
        return self._trainer.variables if self._trainer is not None else None

    def save(self, directory):
        """Full-model save: architecture + weights (+ compile config when
        serializable) in one directory; reload with
        ``tpu_dist.models.load_model``. Chief-only writes (§5.4)."""
        from tpu_dist.models import serialize

        return serialize.save_model(self, directory)

    def save_weights(self, directory, step: int = 0):
        """Chief-only checkpoint write (README.md:51 chief duty; §5.4)."""
        from tpu_dist.training import checkpoint

        return checkpoint.save(directory, self, step=step)

    def load_weights(self, directory, step: Optional[int] = None) -> int:
        """Restore training variables from the latest (or given) checkpoint."""
        from tpu_dist.training import checkpoint

        return checkpoint.restore_model(directory, self, step=step)


class Sequential(Model):
    """Linear layer stack — the reference model container
    (tf_dist_example.py:40)."""

    def __init__(self, layers: Sequence[Layer], *,
                 input_shape: Optional[tuple] = None, name: str = "sequential"):
        self.layers = list(layers)
        if not self.layers:
            raise ValueError("Sequential needs at least one layer")
        self.layer_names = self._unique_names(self.layers)
        super().__init__(self._init_layers, self._apply_layers,
                         input_shape=input_shape, name=name)

    @staticmethod
    def _unique_names(layers: Sequence[Layer]) -> list[str]:
        from tpu_dist.models.layers import unique_layer_names

        return unique_layer_names(layers)

    def _init_layers(self, key, input_shape):
        from tpu_dist.models.layers import init_chain

        params, state, shape = init_chain(self.layers, self.layer_names, key,
                                          tuple(input_shape))
        self.output_shape = shape
        return params, state

    def _apply_layers(self, params, state, x, training, rng):
        from tpu_dist.models.layers import apply_chain
        from tpu_dist.models.policy import compute_dtype
        from tpu_dist.utils import profiler

        # Mixed-precision entry/exit casts (policy.py): activations run in the
        # compute dtype, the returned logits in float32 for a stable loss.
        dtype = compute_dtype()
        if x.dtype != dtype and jax.numpy.issubdtype(x.dtype, jax.numpy.floating):
            x = x.astype(dtype)
        y, new_state = apply_chain(self.layers, self.layer_names, params,
                                   state, x, training=training, rng=rng,
                                   head=True)
        if jax.numpy.issubdtype(y.dtype, jax.numpy.floating):
            with profiler.layer_scope("head"):
                y = y.astype(jax.numpy.float32)
        return y, new_state

    def summary(self) -> str:
        """Keras-style layer table: name, type, output shape, param count
        (shapes/counts need a known ``input_shape``; the dry per-layer init
        used to derive them is host-side and tiny)."""
        header = f"{'Layer (name)':<26}{'Type':<22}{'Output shape':<18}{'Params':>10}"
        lines = [f'Model: "{self.name}"', "=" * len(header), header,
                 "-" * len(header)]
        if self.input_shape is None:
            for name, layer in zip(self.layer_names, self.layers):
                lines.append(f"{name:<26}{type(layer).__name__:<22}"
                             f"{'?':<18}{'?':>10}")
            lines.append("-" * len(header))
            lines.append("(input_shape unknown — shapes/params unavailable)")
        else:
            import math

            def count(tree):
                return sum(math.prod(a.shape) for a in
                           jax.tree_util.tree_leaves(tree))

            key = jax.random.PRNGKey(0)
            shape = tuple(self.input_shape)
            total = total_state = 0
            for name, layer in zip(self.layer_names, self.layers):
                # eval_shape: shapes/counts WITHOUT materializing params
                # (a real init would run every initializer and allocate the
                # full model — tens of MB for the ResNets — per summary()).
                # The out-shape is plain Python computed during tracing, so
                # capture it; the abstracted pytrees carry the shapes.
                captured = {}

                def abstract_init(k, layer=layer, shape=shape):
                    p, s, out = layer.init(k, shape)
                    captured["out"] = out
                    return p, s

                p_spec, s_spec = jax.eval_shape(abstract_init, key)
                shape = captured["out"]
                n = count(p_spec)
                total += n
                total_state += count(s_spec)
                lines.append(f"{name:<26}{type(layer).__name__:<22}"
                             f"{str(tuple(shape)):<18}{n:>10,}")
            lines.append("-" * len(header))
            lines.append(f"Trainable params: {total:,}")
            if total_state:
                lines.append(f"Non-trainable state: {total_state:,}")
        out = "\n".join(lines)
        print(out)
        return out
