"""chip_smoke.py — the quickest proof that tpu_dist still starts on the chip.

Drives the system's main path once, in ONE process, through the entry
points a user calls, at the full width of the one LM the repo has
(``build_transformer_lm`` at GPT-2-small's published widths: vocab 50257,
seq 1024, d_model 768, 12 heads of 64, depth 12, ff 3072; weights random
from ``--seed``):

    python chip_smoke.py            # one chip: trainer, server, MNIST example
    python chip_smoke.py --chips 4  # four chips: DP and data x model vs one

One chip, three phases:

* ``train`` — ``MirroredStrategy().scope()`` -> ``compile`` under the
  ``mixed_bfloat16`` policy -> ``fit()`` on one repeated batch of seeded
  tokens. The loss must be finite and fall, and the step (lowered the way
  ``bench.py`` lowers it) must hold the flash kernel: dense attention at
  this shape is a failure, not a fallback.
* ``serve`` — the same model in ``ServeEngine(paged=True, ragged=True,
  kv_dtype="int8")`` answers ragged prompts (some longer than one prefill
  chunk, two sharing a prefix) to completion. Served tokens are held to
  the plain full-sequence forward, tie-aware (below).
* ``example`` — ``examples/tpu_dist_example.py``, unchanged: the paper's
  own program, and the DeviceDataset promotion.

``--chips 4`` runs instead, and only: the same LM ``fit()`` on one device,
under ``MirroredStrategy()`` over four, and under ``data=2 x model=2``;
losses must agree, the step must hold an all-reduce (and still the flash
kernel), parameters and bytes must be on all four devices.

The parity rule for served tokens: the reference is ONE full-sequence
``model.apply`` over prompt + generated tokens (teacher-forced, so one
early flip cannot cascade). A served token passes when the reference logit
of the token the engine chose is within a stated tolerance of the
reference maximum ("regret", in standard deviations of that position's
logits; 0 where the argmaxes agree). An engine with fp32 pages and the
reference both run under the float32 policy at HIGHEST matmul precision —
the TPU's default f32 matmul rounds operands to bf16, which is not the
CPU's arithmetic — and are held to ``FP32_REGRET``; the int8 engines run
as deployed (bf16 compute, int8 pages) and are held to ``INT8_REGRET``
against the same reference. The parity subject is the same architecture
with weights fresh from the seed: its argmax is decided by context.

Each phase prints one JSON line (wall and compile seconds, persistent-
cache hits and misses, peak device bytes, and what it checked). The LAST
line of stdout is ``{"ok": ..., "device": {"platform", "kind", "count"}}``.
Without a TPU the script fails at once: it never carries on on the CPU.
``--size tiny`` is the rehearsal of guide section 2 (tiny widths, any
backend, never ``"ok": true``); it sizes the script, not the program.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import pathlib
import runpy
import sys
import time

import numpy as np

SIZES = {
    # GPT-2 small's published widths; nothing cut.
    "real": dict(vocab=50257, seq=1024, d_model=768, heads=12, depth=12,
                 ff=3072, batch=8, prompt_lens=(72, 150, 97, 260, 40, 80),
                 shared_prefix=64, new_tokens=12, prefill_chunk=64,
                 ref_pad=128),
    "tiny": dict(vocab=512, seq=128, d_model=64, heads=4, depth=2, ff=256,
                 batch=8, prompt_lens=(24, 40, 33, 70, 12, 28),
                 shared_prefix=16, new_tokens=6, prefill_chunk=16,
                 ref_pad=128),
}
EPOCHS, STEPS_PER_EPOCH = 3, 4
LEARNING_RATE = 1e-3
#: Largest regret a served token may show, in standard deviations of the
#: reference logits at its position (see ``regret``).
FP32_REGRET = 1e-2
INT8_REGRET = 0.1
#: Epoch losses of the same steps on different meshes. bf16 compute and
#: another reduction order perturb the last bits of the gradients; Adam's
#: first, sign-like updates amplify that step by step (measured on v5e:
#: 1e-5, 3e-4, 1.2e-2 over the three epochs), so the bound is set by the
#: last epoch.
MESH_LOSS_RTOL = 2e-2

REPO = pathlib.Path(__file__).resolve().parent


@contextlib.contextmanager
def phase(name: str, meter, report: dict):
    """Time one phase and print its line when it ends WELL; a phase that
    raises prints nothing here and takes the run down with it."""
    import jax

    t0 = time.perf_counter()
    req0, hit0, comp0 = meter.read()
    yield
    req1, hit1, comp1 = meter.read()
    stats = jax.devices()[0].memory_stats() or {}
    line = {"phase": name,
            "wall_s": round(time.perf_counter() - t0, 2),
            "compile_s": round(comp1 - comp0, 2),
            "cache_hits": hit1 - hit0,
            "cache_misses": (req1 - req0) - (hit1 - hit0),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use",
                                           "not reported"),
            **report}
    print(json.dumps(line), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {what}")


@contextlib.contextmanager
def precision_policy(name: str):
    """The global mixed-precision policy, for the length of a block."""
    from tpu_dist.models.policy import policy, set_policy

    before = policy()
    set_policy(name)
    try:
        yield
    finally:
        set_policy(before)


# -- the LM through compile/fit -----------------------------------------------


def token_batch(cfg: dict, seed: int):
    rng = np.random.default_rng(seed)
    t = rng.integers(0, cfg["vocab"], size=(cfg["batch"], cfg["seq"] + 1))
    return t[:, :-1].astype(np.int32), t[:, 1:].astype(np.int32)


def build_lm(cfg: dict):
    from tpu_dist.models.transformer import build_transformer_lm

    return build_transformer_lm(
        cfg["vocab"], cfg["seq"], d_model=cfg["d_model"], depth=cfg["depth"],
        num_heads=cfg["heads"], ff_dim=cfg["ff"])


def fit_lm(cfg: dict, strategy, seed: int):
    """The trainer's main path: scope -> compile (mixed_bfloat16 policy,
    set by the caller) -> fit on one repeated batch. Returns the model,
    the per-epoch losses and the step's lowering."""
    import jax

    import tpu_dist as td

    x, y = token_batch(cfg, seed)
    with strategy.scope():
        model = build_lm(cfg)
        model.compile(
            loss=td.ops.SparseCategoricalCrossentropy(from_logits=True),
            optimizer=td.ops.Adam(learning_rate=LEARNING_RATE))
    ds = td.data.Dataset.from_tensor_slices((x, y)).batch(
        cfg["batch"]).repeat()
    # Outside the scope, as the reference's own script calls it.
    history = model.fit(ds, epochs=EPOCHS, steps_per_epoch=STEPS_PER_EPOCH,
                        verbose=0, seed=seed)
    losses = [float(v) for v in history.history["loss"]]
    check(len(losses) == EPOCHS and all(np.isfinite(losses)),
          f"fit() losses not finite: {losses}")
    check(losses[-1] < losses[0], f"fit() loss did not fall: {losses}")
    # The step, lowered the way bench.py lowers it (nothing executes, so
    # the donated live state is only described).
    fn = model.make_train_function(steps_per_execution=1)
    lowered = fn.lower(*model.train_state(), strategy.distribute_batch(x),
                       strategy.distribute_batch(y), jax.random.PRNGKey(0))
    return model, losses, lowered


def require_flash(lowered, cfg: dict, on_tpu: bool) -> int | str:
    if not on_tpu:
        return "not checked: rehearsal off the TPU runs no Pallas kernel"
    calls = lowered.as_text().count("tpu_custom_call")
    # One forward and at least one backward kernel for every layer.
    check(calls >= 2 * cfg["depth"],
          f"the train step holds {calls} tpu_custom_call(s), expected >= "
          f"{2 * cfg['depth']}: attention went dense")
    return calls


def train_phase(cfg, meter, seed, on_tpu):
    import tpu_dist as td

    report: dict = {}
    with phase("train", meter, report):
        strategy = td.MirroredStrategy()
        model, losses, lowered = fit_lm(cfg, strategy, seed)
        report.update(
            loss_first=losses[0], loss_last=losses[-1], losses=losses,
            steps=EPOCHS * STEPS_PER_EPOCH,
            tokens_per_step=cfg["batch"] * cfg["seq"],
            flash_calls_in_step=require_flash(lowered, cfg, on_tpu))
    return model


# -- the server ---------------------------------------------------------------


def serve_prompts(cfg: dict, seed: int) -> list[list[int]]:
    rng = np.random.default_rng(seed + 1)
    prefix = rng.integers(0, cfg["vocab"], size=cfg["shared_prefix"])
    prompts = [rng.integers(0, cfg["vocab"], size=n).tolist()
               for n in cfg["prompt_lens"]]
    # First and last share a page-aligned prefix; the last is admitted
    # after the first has registered it (six requests, four slots).
    for i in (0, -1):
        prompts[i][:len(prefix)] = prefix.tolist()
    return prompts


def run_engine(model, cfg, prompts, seed, kv_dtype):
    """Serve ``prompts`` to completion; returns (streams, the engine's
    placed params)."""
    from tpu_dist.serve.engine import ServeEngine

    with ServeEngine(model, max_batch=4, paged=True, ragged=True,
                     kv_dtype=kv_dtype, page_size=16,
                     prefill_chunk=cfg["prefill_chunk"],
                     seed=seed) as engine:
        reqs = [engine.submit(p, max_new_tokens=cfg["new_tokens"])
                for p in prompts]
        engine.run_until_idle()
        programs = engine.compiled_programs()
        params = engine.params
    for r in reqs:
        check(r.status == "done" and r.finish_reason == "length"
              and len(r.generated) == cfg["new_tokens"],
              f"request {r.rid} ended {r.status}/{r.finish_reason} with "
              f"{len(r.generated)} tokens")
        check(all(0 <= t < cfg["vocab"] for t in r.generated),
              f"request {r.rid} produced a token outside the vocabulary")
    check(programs["paged_decode"] == [4],
          f"ragged decode compiled {programs['paged_decode']}, not one "
          "full-capacity program")
    return [list(r.generated) for r in reqs], params


def reference_forward(model):
    """The plain full-sequence forward: (params, [1, L] tokens) -> logits."""
    import jax

    return jax.jit(lambda p, x: model.apply(p, {}, x)[0])


def regret(forward, params, cfg, prompts, streams) -> dict:
    """Served streams against ONE full-sequence forward per request.
    Regret of a token: (reference maximum - reference logit of the token
    the engine chose) / standard deviation of that position's reference
    logits; 0 where the argmaxes agree. ``median_top2_gap`` (same unit)
    says how decisive the reference was — the power of the check."""
    longest = max(len(p) + len(s) for p, s in zip(prompts, streams))
    pad = -(-longest // cfg["ref_pad"]) * cfg["ref_pad"]
    worst, flips, gaps = 0.0, 0, []
    for prompt, stream in zip(prompts, streams):
        x = np.zeros((1, pad), np.int32)
        x[0, :len(prompt) + len(stream)] = prompt + stream
        logits = np.asarray(forward(params, x))[0]
        check(bool(np.all(np.isfinite(logits))),
              "reference logits not finite")
        # Generated token j was picked from position len(prompt) - 1 + j.
        rows = logits[len(prompt) - 1:len(prompt) - 1 + len(stream)]
        sigma = rows.std(axis=-1)
        top2 = np.sort(rows, axis=-1)[:, -2:]
        chosen = rows[np.arange(len(stream)), stream]
        worst = max(worst, float(np.max((top2[:, 1] - chosen) / sigma)))
        flips += int(np.sum(rows.argmax(axis=-1) != np.asarray(stream)))
        gaps.extend(((top2[:, 1] - top2[:, 0]) / sigma).tolist())
    return {"max_regret": worst, "argmax_flips": flips,
            "median_top2_gap": float(np.median(gaps))}


def serve_phase(model, cfg, meter, seed):
    import jax

    from tpu_dist.observe import metrics

    report: dict = {}
    with phase("serve", meter, report):
        prompts = serve_prompts(cfg, seed)
        # As deployed: the model fit() trained, bf16 compute, int8 pages.
        metrics.enable()
        try:
            served, served_params = run_engine(model, cfg, prompts, seed,
                                               "int8")
            counters = metrics.get_registry().snapshot()["counters"]
        finally:
            metrics.disable()
        check(counters.get("serve.prefix.hits", 0) >= 1,
              "the shared prefix was never served from the prefix cache")
        check(counters.get("serve.prefill.chunks", 0) > len(prompts),
              "no prompt was prefilled in more than one chunk")
        # A dozen steps on one batch leave the trained model's argmax
        # decided by position, not by context: a wrong page would not
        # flip it. The same architecture with its weights fresh from the
        # seed has near-flat, context-decided logits — the parity subject.
        fresh = build_lm(cfg)
        fresh_int8, _ = run_engine(fresh, cfg, prompts, seed, "int8")
        with precision_policy("float32"), \
                jax.default_matmul_precision("highest"):
            fresh_fp32, fresh_params = run_engine(fresh, cfg, prompts, seed,
                                                  "fp32")
            fresh_forward = reference_forward(fresh)
            parity = {
                "fp32_fresh": regret(fresh_forward, fresh_params, cfg,
                                     prompts, fresh_fp32),
                "int8_fresh": regret(fresh_forward, fresh_params, cfg,
                                     prompts, fresh_int8),
                "int8_trained": regret(reference_forward(model),
                                       served_params, cfg, prompts, served),
            }
        report.update(
            requests=len(prompts), prompt_lens=[len(p) for p in prompts],
            tokens_generated=sum(len(s) for s in served),
            decode_steps=counters.get("serve.decode.steps"),
            prefill_chunks=counters.get("serve.prefill.chunks"),
            prefix_hits=counters.get("serve.prefix.hits"),
            parity=parity, fp32_regret_bound=FP32_REGRET,
            int8_regret_bound=INT8_REGRET)
        # The line above the verdict: the largest differences found.
        print(json.dumps({"serve_parity": parity}), flush=True)
        for name, bound in (("fp32_fresh", FP32_REGRET),
                            ("int8_fresh", INT8_REGRET),
                            ("int8_trained", INT8_REGRET)):
            check(parity[name]["max_regret"] <= bound,
                  f"{name}: served tokens leave the full forward by "
                  f"{parity[name]} (bound {bound})")


# -- the paper's own program --------------------------------------------------


def example_phase(meter, on_tpu):
    import tpu_dist as td
    from tpu_dist.data import native, vectorize

    report: dict = {}
    with phase("example", meter, report):
        scope = runpy.run_path(str(REPO / "examples" / "tpu_dist_example.py"),
                               run_name="__main__")
        model = scope["multi_worker_model"]
        dataset = scope["train_datasets_no_auto_shard"]
        # fit() seeds its weights from seed 0, and so does a fresh model's
        # evaluate(): the second is the first before training.
        with scope["strategy"].scope():
            fresh = scope["build_and_compile_cnn_model"]()
        before = float(fresh.evaluate(dataset, steps=40, verbose=0)["loss"])
        after = float(model.evaluate(dataset, steps=40, verbose=0)["loss"])
        check(np.isfinite(before) and np.isfinite(after),
              f"MNIST example losses not finite: {before} -> {after}")
        check(after < before,
              f"MNIST example did not learn: loss {before} -> {after}")
        promoted = vectorize.try_promote_to_device(dataset) is not None
        check(promoted or not on_tpu,
              "the example's pipeline was not promoted to a DeviceDataset")
        splits, info = td.data.load(name="mnist", with_info=True)
        splits["train"]  # info.synthetic speaks of the splits served
        report.update(
            loss_before=before, loss_after=after,
            data=("seeded synthetic fallback" if info.synthetic
                  else "real files"),
            native_loader_built=native.native_available(),
            device_dataset_promotion=promoted)


# -- four chips ---------------------------------------------------------------


def mesh_phase(name, cfg, meter, seed, on_tpu, devices, axis_shapes, baseline):
    """One LM fit on ``devices`` (one JSON line); losses held to
    ``baseline`` when given."""
    import jax

    import tpu_dist as td

    report: dict = {}
    with phase(name, meter, report):
        strategy = td.MirroredStrategy(devices=devices,
                                       axis_shapes=axis_shapes)
        model, losses, lowered = fit_lm(cfg, strategy, seed)
        report.update(losses=losses, mesh=dict(strategy.mesh.shape),
                      flash_calls_in_step=require_flash(lowered, cfg, on_tpu))
        if baseline is not None:
            rel = np.abs(np.asarray(losses) - baseline) / np.abs(baseline)
            report["rel_loss_diff_vs_one_device"] = rel.tolist()
            check(bool(np.all(rel <= MESH_LOSS_RTOL)),
                  f"{name} losses {losses} leave the one-device run "
                  f"{list(baseline)} by {rel.tolist()} "
                  f"(> {MESH_LOSS_RTOL})")
        if len(devices) > 1:
            check("all-reduce" in lowered.compile().as_text(),
                  f"{name}: the compiled step holds no all-reduce")
            tensor_parallel = strategy.mesh.shape.get("model", 1) > 1
            over_model = 0
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    model.variables["params"])[0]:
                where = jax.tree_util.keystr(path)
                check(len(leaf.sharding.device_set) == len(devices),
                      f"{name}: {where} lives on "
                      f"{len(leaf.sharding.device_set)} device(s)")
                split = "model" in tuple(leaf.sharding.spec)
                over_model += split
                # Megatron layout: inside every block QKV and MLP-up split
                # their columns, the output projections their rows.
                if (tensor_parallel and path[0].key.startswith("block")
                        and path[-1].key in ("wq", "wk", "wv", "wo",
                                             "kernel")):
                    check(split, f"{name}: {where} is not sharded over "
                                 f"'model' ({leaf.sharding.spec})")
            if tensor_parallel:
                check(over_model > 0, f"{name}: nothing sharded over 'model'")
                report["leaves_sharded_over_model"] = over_model
            in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                      for d in devices]
            report["bytes_in_use_per_device"] = in_use
            check(not on_tpu or all(in_use),
                  f"{name}: a device holds no bytes: {in_use}")
    del model
    gc.collect()
    return np.asarray(losses)


def four_chip_phases(cfg, meter, seed, on_tpu):
    import jax

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs four devices, jax reports "
                             f"{len(devices)}")
    devices = devices[:4]
    one = mesh_phase("one_device", cfg, meter, seed, on_tpu, devices[:1],
                     None, None)
    mesh_phase("data_parallel_4", cfg, meter, seed, on_tpu, devices,
               None, one)
    mesh_phase("data2_x_model2", cfg, meter, seed, on_tpu, devices,
               {"data": 2, "model": 2}, one)


# -- entry --------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run the four-chip comparison, and only it")
    parser.add_argument("--size", choices=sorted(SIZES), default="real",
                        help="tiny: rehearsal widths, never a result")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    ok, device = False, None
    try:
        # The package first: importing it places the compile cache, and a
        # directory without it fails here, before the chip is touched.
        from tpu_dist.utils import compile_cache

        import jax

        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        on_tpu = device["platform"] == "tpu"
        if not on_tpu and args.size == "real":
            print(f"chip_smoke: jax found no TPU (platform "
                  f"{device['platform']!r}); not running on it",
                  file=sys.stderr)
            return 1
        cfg = SIZES[args.size]
        meter = compile_cache.meter()
        print(json.dumps({"chip_smoke": vars(args), "device": device,
                          "compile_cache_dir": compile_cache.configure()}),
              flush=True)
        if args.chips == 4:
            with precision_policy("mixed_bfloat16"):
                four_chip_phases(cfg, meter, args.seed, on_tpu)
        else:
            with precision_policy("mixed_bfloat16"):
                model = train_phase(cfg, meter, args.seed, on_tpu)
                serve_phase(model, cfg, meter, args.seed)
            example_phase(meter, on_tpu)
        # A rehearsal is never a result.
        ok = on_tpu and args.size == "real"
    finally:
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
