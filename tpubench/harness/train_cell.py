"""A training cell: ``Model.fit()`` under ``MirroredStrategy`` on the
cell's chips, timed over whole epochs.

Set-up builds ONE model with its compiled step and state, drives it from
the seed through its first three steps (one ``fit()`` of one step each,
through the same call and the same distributed dataset as the window),
runs one warm-up epoch and hands the same object to the window. The
harness stamps at epoch ends only, after reading the epoch's loss as any
``verbose`` user does: no per-step callback, so the steps inside an epoch
run free. The window is whole epochs until the clock passes
``--seconds``, with one ``block_until_ready`` at each end.
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from tpubench.harness import checks, reference, traffic

CHECK_STEPS = 3


class _Stamper:
    """A ``Callback``: waits for each epoch's loss, stamps the clock, ends
    the fit once ``deadline`` has passed or ``max_epochs`` have run."""

    wants_batches = False
    model = None

    def __init__(self, deadline=None, max_epochs=None, spans=False):
        self.deadline, self.max_epochs = deadline, max_epochs
        self.stamps: list[float] = []
        self._spans = spans
        self._open = None

    def on_train_begin(self): ...
    def on_train_end(self): self._close()
    def on_batch_end(self, step, logs): ...

    def _close(self):
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def on_epoch_begin(self, epoch):
        if self._spans:
            import jax

            self._open = jax.profiler.TraceAnnotation("tpubench.fit_epoch")
            self._open.__enter__()

    def on_epoch_end(self, epoch, logs):
        from tpu_dist.training.callbacks import StopTraining

        self._close()
        # Reading the loss waits for the epoch's last step, as a verbose
        # fit() does at every epoch end.
        if self._spans:
            import jax

            with jax.profiler.TraceAnnotation("tpubench.epoch_loss_wait"):
                float(logs["loss"])
        else:
            float(logs["loss"])
        now = time.perf_counter()
        self.stamps.append(now)
        if ((self.deadline is not None and now >= self.deadline)
                or (self.max_epochs is not None
                    and len(self.stamps) >= self.max_epochs)):
            raise StopTraining("tpubench: the window has closed")


class TrainRun:
    """Everything of one run of a training cell; ``sabotage`` lets the
    benchmark's own tests break the timed path underneath."""

    def __init__(self, cell, seed: int, *, chips: int):
        self.cell, self.seed, self.chips = cell, int(seed), chips
        self.cfg, self.mix, self.family = cell.config, cell.mix, cell.family
        self.global_batch = self.mix["rows_per_chip"] * chips
        self.seq = cell.sizes["n_ctx"]
        self.vocab = cell.sizes["n_vocab"]
        self.steps_per_epoch = int(self.mix["steps_per_epoch"])
        self.fit_seed = self.seed % (2 ** 31 - 1)
        self.prog: dict = {}
        self.epochs_done = 0

    # -- set-up ------------------------------------------------------------

    def build(self):
        import jax

        import tpu_dist as td
        from tpu_dist.models.policy import set_policy

        set_policy(self.mix["policy"])
        self.strategy = td.MirroredStrategy(
            devices=jax.devices()[:self.chips])
        with self.strategy.scope():
            self.model = self.family.build_program(self.cfg, self.seed)
            self.model.compile(
                loss=td.ops.SparseCategoricalCrossentropy(from_logits=True),
                optimizer=td.ops.Adam(
                    learning_rate=self.mix["learning_rate"]))
        rows = self.global_batch * int(self.mix["pool_batches"])
        self.x, self.y = traffic.token_rows(
            self.seed, self.vocab, rows, self.seq)
        ds = td.data.Dataset.from_tensor_slices((self.x, self.y)).batch(
            self.global_batch).repeat()
        # One distributed dataset for every fit(): its iterator persists,
        # so the first steps and the window read on through the same rows.
        self.dist = self.strategy.experimental_distribute_dataset(ds)
        self._grad_norms = self.family.program_grad_norms(self.cfg)
        self._delta_norms = self.family.program_delta_norms(self.cfg)

    def _fit(self, first_epoch, epochs, steps, callbacks=()):
        history = self.model.fit(
            self.dist, epochs=first_epoch + epochs, initial_epoch=first_epoch,
            steps_per_epoch=steps, verbose=0, seed=self.fit_seed,
            callbacks=list(callbacks))
        # History counts the epochs that ended, StopTraining or not.
        self.epochs_done = first_epoch + len(history.epoch)
        return history

    def first_steps(self):
        """The first three steps through ``fit()``, one step an epoch, and
        what the comparison reads of them."""
        import jax

        losses = []
        for i in range(CHECK_STEPS):
            history = self._fit(i, 1, 1)
            losses.append(float(history.history["loss"][-1]))
            if i == 0:
                grad = jax.device_get(
                    self._grad_norms(self.model.variables["opt"].mu))
        delta = jax.device_get(self._delta_norms(
            self.model.variables["params"], reference.seed_key(self.seed)))
        self.prog = {"losses": losses,
                     "grad_norms": {k: float(v) for k, v in grad.items()},
                     "delta_norms": {k: float(v) for k, v in delta.items()}}

    def warm_up(self):
        import jax

        self._fit(self.epochs_done, 1, self.steps_per_epoch)
        jax.block_until_ready(self.model.variables["params"])

    # -- the window --------------------------------------------------------

    def window(self, seconds: float, *, trace_dir=None, traced_epochs=3):
        """Whole epochs until the clock passes ``seconds``. With
        ``trace_dir`` the first ``traced_epochs`` run under the profiler,
        and the rest under the program's own ``Telemetry`` (which blocks
        every step, so it is kept out of the profiled part)."""
        import jax

        jax.block_until_ready(self.model.variables["params"])
        t0 = time.perf_counter()
        host = {}
        if trace_dir is not None:
            from tpu_dist.observe import metrics
            from tpu_dist.observe.telemetry import Telemetry

            jax.profiler.start_trace(str(trace_dir))
            traced = _Stamper(max_epochs=traced_epochs, spans=True)
            with jax.profiler.TraceAnnotation("tpubench.window"):
                self._fit(self.epochs_done, traced_epochs,
                          self.steps_per_epoch, [traced])
                jax.block_until_ready(self.model.variables["params"])
            jax.profiler.stop_trace()
            host["traced_steps"] = traced_epochs * self.steps_per_epoch
            t_b = time.perf_counter()
            rest = _Stamper(deadline=max(t0 + seconds, t_b + 1.0))
            self._fit(self.epochs_done, 100000, self.steps_per_epoch,
                      [Telemetry(), rest])
            snap = metrics.get_registry().snapshot()
            host["counters"] = snap["counters"]
            host["distributions"] = snap["distributions"]
            host["telemetry_wall_s"] = rest.stamps[-1] - t_b
            gaps = np.diff([t_b] + rest.stamps)
            epochs = traced_epochs + len(rest.stamps)
        else:
            stamper = _Stamper(deadline=t0 + seconds)
            self._fit(self.epochs_done, 100000, self.steps_per_epoch,
                      [stamper])
            gaps = np.diff([t0] + stamper.stamps)
            epochs = len(stamper.stamps)
        jax.block_until_ready(self.model.variables["params"])
        t1 = time.perf_counter()
        steps = epochs * self.steps_per_epoch
        host.update(
            window_s=t1 - t0, steps=steps,
            tokens=steps * self.global_batch * self.seq,
            step_ms_p50=1e3 * statistics.median(gaps) / self.steps_per_epoch)
        return host

    # -- after the window --------------------------------------------------

    def free_program(self):
        for name in ("model", "dist", "strategy", "_grad_norms",
                     "_delta_norms"):
            self.__dict__.pop(name, None)
        gc.collect()

    def reference_numbers(self, *, quant=None, keep_rows=1.0, freeze=False):
        """The plain reference over the same first steps: float32 at
        ``highest`` matmul precision, rows in blocks."""
        import jax

        batches = [(self.x[i * self.global_batch:(i + 1) * self.global_batch],
                    self.y[i * self.global_batch:(i + 1) * self.global_batch])
                   for i in range(CHECK_STEPS)]
        with jax.default_matmul_precision("highest"):
            params = jax.jit(
                lambda k: self.family.make_params(k, self.cfg))(
                reference.seed_key(self.seed))
            ref = reference.TrainReference(
                self.family.loss_sum, self.cfg,
                lr=self.mix["learning_rate"], quant=quant,
                rows_per_block=int(self.mix.get("reference_rows_per_block",
                                                2)),
                keep_rows=keep_rows, freeze=freeze,
                devices=jax.devices()[:self.chips])
            return ref.run(params, batches)


def layer_work(run: TrainRun, traced_steps: int) -> dict:
    """Operations and bytes of the traced steps, per device: the
    family counts a step's, the harness sums the steps."""
    fam, cfg = run.family, run.cfg
    rows, seq = run.mix["rows_per_chip"], run.seq
    out = {"train_step_flops": (traced_steps * rows * seq
                                * fam.train_flops_per_token(cfg, seq))}
    for name, (flops, bytes_) in fam.train_kernels(cfg, rows, seq).items():
        out[name] = (flops * traced_steps, bytes_ * traced_steps)
    return out


def run_cell(cell, args, ctx) -> dict:
    """One run of a training cell; returns the harness's result dict."""
    run = TrainRun(cell, args.seed, chips=cell.chips)
    run.build()
    if ctx.get("sabotage"):
        ctx["sabotage"](run)
    run.first_steps()
    run.warm_up()
    before = ctx["meter"].read()
    setup_s = time.perf_counter() - ctx["t_start"]
    host = run.window(args.seconds, trace_dir=ctx.get("trace_dir"))
    after = ctx["meter"].read()
    host["compiles_in_window"] = after["requests"] - before["requests"]
    host["setup_compile_s"] = before["compile_s"]
    memory_peak = ctx["memory_peak"]()
    run.free_program()
    t_ref = time.perf_counter()
    ref = run.reference_numbers()
    host["reference_s"] = time.perf_counter() - t_ref
    numbers = checks.train_numbers(run.prog, ref)
    attempted = host["steps"] + CHECK_STEPS
    failed = sum(not np.isfinite(v) for v in run.prog["losses"])
    return {
        "end_to_end": {
            "train_tokens_per_s": host["tokens"] / host["window_s"],
            "setup_s": setup_s},
        "host": host, "numbers": numbers, "attempted": attempted,
        "failed": failed, "memory_peak_bytes": memory_peak,
        "work": (layer_work(run, host["traced_steps"])
                 if "traced_steps" in host else {}),
        "detail": {"prog_losses": run.prog["losses"],
                   "ref_losses": ref["losses"]},
    }
