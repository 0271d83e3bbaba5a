"""A serving cell: one ``ServeEngine`` on one chip under an open loop.

The engine is synchronous, so one thread does it all: between two
``engine.step()`` calls it submits every request that has come due. Load
is offered at the rate the mix fixes, whatever the engine does with it; a
request is timed from when it was DUE, and how late the generator ran is
reported beside it. When the window closes nothing more is submitted and
the engine is stepped until every request sent has answered (a minute at
most): a late answer is late, not wrong.
"""

from __future__ import annotations

import contextlib
import gc
import math
import time

import numpy as np

from tpubench.harness import reference, stats, traffic

DRAIN_LIMIT_S = 60.0


def _span(name, on):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class ServeRun:
    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, int(seed)
        self.cfg, self.mix, self.family = cell.config, cell.mix, cell.family
        self.vocab, self.pad = cell.sizes["n_vocab"], cell.sizes["n_ctx"]
        self.engine_args = dict(self.mix["engine"])

    def build(self, seconds: float):
        from tpu_dist.models.policy import set_policy
        from tpu_dist.serve.engine import ServeEngine

        set_policy(self.mix["policy"])
        self.model = self.family.build_program(self.cfg, self.seed)
        self.engine = ServeEngine(
            self.model, seed=self.seed % (2 ** 31 - 1),
            clock=time.perf_counter, **self.engine_args)
        self.plan = traffic.plan_requests(
            self.mix, seconds, self.seed, self.vocab, self.engine.max_len)

    def warm_up(self):
        """Every program the mix can reach, through submit()/step()."""
        prompts = traffic.warmup_prompts(
            self.mix, self.seed, self.vocab,
            self.engine_args["prefill_chunk"], self.plan)
        for prompt, new in prompts[:-1]:
            self.engine.submit(prompt, max_new_tokens=new)
        self.engine.run_until_idle()
        # The exact repeat comes once its original has finished and left
        # its tail page in the prefix cache: copy-on-write.
        self.engine.submit(prompts[-1][0], max_new_tokens=prompts[-1][1])
        self.engine.run_until_idle()
        self.engine.finished.clear()

    # -- the window --------------------------------------------------------

    def window(self, seconds: float, *, trace_dir=None, traced_s=4.0,
               at_close=None):
        """``at_close`` is called once when the window closes, before the
        profiler stops (a stall of seconds that belongs to the drain): a
        traced run snapshots the program's counters there, so every
        per-layer metric reads the window and nothing of the drain."""
        import jax

        engine, plan = self.engine, self.plan
        spans = trace_dir is not None
        reqs: list = [None] * len(plan)
        submit_s: list = [None] * len(plan)
        last_len, last_stamp = {}, {}
        itl_s: list = []
        steps: list = []          # (t_before, t_after, contexts of ready)
        waiting: list = []        # indices sent and not yet admitted
        live: dict = {}           # index -> request admitted, unfinished
        admitted_at_close: list = []
        nxt = generated = 0
        longest_step = 0.0
        tracing, window_span = False, None
        traced_from = traced_until = None
        t0 = time.perf_counter()
        # The profiler runs over the LAST ``traced_s`` of the window, so
        # that writing the trace out falls after the close.
        t_trace = t0 + max(0.0, seconds - traced_s)
        closed_at = drain_from = None
        while True:
            now = time.perf_counter()
            if spans and traced_from is None and now >= t_trace:
                jax.profiler.start_trace(str(trace_dir))
                window_span = jax.profiler.TraceAnnotation("tpubench.window")
                window_span.__enter__()
                tracing, traced_from = True, time.perf_counter()
            if closed_at is None and now - t0 >= seconds:
                closed_at = now
                admitted_at_close = [i for i in range(nxt)
                                     if reqs[i].status != "queued"]
                if at_close is not None:
                    at_close()
                if tracing:
                    window_span.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    tracing, traced_until = False, now
                # Writing a busy trace out takes tens of seconds: the
                # drain and its limit start when that is done.
                drain_from = time.perf_counter()
            # Every request of the plan is due before the close; one that
            # came due during the last step is still sent, late.
            with _span("tpubench.submit", tracing):
                while nxt < len(plan) and plan[nxt].due_s <= now - t0:
                    r = plan[nxt]
                    submit_s[nxt] = time.perf_counter()
                    reqs[nxt] = engine.submit(
                        r.prompt, max_new_tokens=r.max_new_tokens)
                    waiting.append(nxt)
                    nxt += 1
            if closed_at is not None and (
                    not (live or waiting)
                    or now - drain_from > DRAIN_LIMIT_S):
                break
            if engine.scheduler.idle():
                if closed_at is not None:
                    break
                due = t0 + (plan[nxt].due_s if nxt < len(plan) else seconds)
                with _span("tpubench.wait_for_request", tracing):
                    time.sleep(max(0.0, min(due, t0 + seconds)
                                   - time.perf_counter()))
                continue
            t_a = time.perf_counter()
            with _span("tpubench.engine_step", tracing):
                engine.step()
            t_b = time.perf_counter()
            # A backlog keeps a thousand requests queued: only those the
            # engine has admitted are looked at token by token.
            admitted = [i for i in waiting if reqs[i].status != "queued"]
            if admitted:
                live.update((i, reqs[i]) for i in admitted)
                waiting = [i for i in waiting if i not in live]
            contexts = []
            for i in list(live):
                req = reqs[i]
                n = len(req.generated)
                if n > last_len.get(i, 0):
                    if closed_at is None:
                        generated += n - last_len.get(i, 0)
                    if i in last_stamp:
                        itl_s.append(t_b - last_stamp[i])
                        contexts.append(len(req.prompt) + n)
                    last_len[i], last_stamp[i] = n, t_b
                if req.status != "active":
                    del live[i]
            steps.append((t_a, t_b, contexts))
            longest_step = max(longest_step, t_b - t_a)
        t_end = time.perf_counter()
        t_close = closed_at if closed_at is not None else t_end
        window_s = t_close - t0
        sent = [i for i in range(len(plan)) if reqs[i] is not None]
        due_abs = [t0 + plan[i].due_s for i in sent]
        ttft = stats.ttfts_ms(
            due_abs, [reqs[i].first_token_s
                      if reqs[i].status == "done" else None for i in sent])
        done_in_window = [reqs[i] for i in sent
                          if reqs[i].status == "done"
                          and reqs[i].finish_s <= t_close]
        self.reqs, self.sent, self.closed_s = reqs, sent, t_close
        traced = [(a, b, c) for a, b, c in steps
                  if traced_until is not None
                  and a >= traced_from and b <= traced_until]
        return {
            "window_s": window_s,
            "drain_s": t_end - (drain_from or t_end),
            "sent": len(sent),
            "not_sent": len(plan) - len(sent),
            "failed": sum(reqs[i].status != "done" for i in sent),
            "tokens_generated": generated,
            "tokens_completed": sum(len(r.generated)
                                    for r in done_in_window),
            "completed_in_window": len(done_in_window),
            "ttft_ms": ttft, "itl_ms": [1e3 * v for v in itl_s],
            "lateness_ms": stats.lateness_ms(
                due_abs, [submit_s[i] for i in sent]),
            "prompt_tokens_sent": sum(len(plan[i].prompt) for i in sent),
            "prompt_tokens_admitted": sum(
                len(plan[i].prompt) for i in admitted_at_close),
            "queued_at_close": len(sent) - len(admitted_at_close),
            "traced_decode_contexts": [c for _, _, c in traced if c],
            "engine_steps": len(steps),
            "engine_step_max_ms": 1e3 * longest_step,
        }

    # -- after the window --------------------------------------------------

    def free_program(self):
        self.served = [(list(self.reqs[i].prompt),
                        list(self.reqs[i].generated), self.plan[i])
                       for i in self.sent if self.reqs[i].status == "done"]
        for name in ("engine", "model", "reqs"):
            self.__dict__.pop(name, None)
        gc.collect()

    def sample(self) -> list:
        """Finished requests drawn from the seed, the longest among them,
        and one of each kind the mix has (prefix hit, exact repeat)."""
        served = self.served
        if not served:
            return []
        rng = np.random.default_rng(self.seed + 104729)
        n = int(self.mix["check"]["requests"])
        longest = max(range(len(served)),
                      key=lambda i: len(served[i][0]) + len(served[i][1]))
        chosen = [longest]
        for want in (lambda p: p.repeat_of >= 0, lambda p: p.prefix_id >= 0):
            hits = [i for i, s in enumerate(served)
                    if want(s[2]) and i not in chosen]
            if hits:
                chosen.append(int(rng.choice(hits)))
        rest = [i for i in rng.permutation(len(served)) if i not in chosen]
        chosen += [int(i) for i in rest[:max(0, n - len(chosen))]]
        return [served[i] for i in chosen]

    def reference_numbers(self, *, quant=None) -> dict:
        """Teacher-forced: one plain full-sequence forward (float32,
        ``highest``) over each sampled request's prompt and served tokens;
        the widest gap by which a served token's logit lies below the
        reference's best, in standard deviations of that position's
        logits. With ``quant`` the control stands in the program's place:
        at each position the token that the lower precision puts first."""
        import functools

        import jax

        fam, cfg, pad = self.family, self.cfg, self.pad
        widest, tokens = 0.0, 0
        with jax.default_matmul_precision("highest"):
            params = jax.jit(lambda k: fam.make_params(k, cfg))(
                reference.seed_key(self.seed))
            ref_fn = jax.jit(functools.partial(fam.forward, cfg=cfg))
            low_fn = (jax.jit(functools.partial(fam.forward, cfg=cfg,
                                                quant=quant))
                      if quant else None)
            for prompt, served, _ in self.sample():
                rows = reference.served_rows(ref_fn, params, prompt, served,
                                             pad)
                if not np.all(np.isfinite(rows)):
                    return {"served_logit_gap": math.inf, "_tokens": tokens}
                judged = served
                if low_fn is not None:
                    judged = reference.served_rows(
                        low_fn, params, prompt, served, pad).argmax(axis=-1)
                gaps = reference.gap_in_sigmas(rows, judged)
                widest = max(widest, float(gaps.max()))
                tokens += len(served)
        return {"served_logit_gap": widest, "_tokens": tokens}


def layer_work(run: ServeRun, host: dict) -> dict:
    contexts = host["traced_decode_contexts"]
    if not contexts:
        return {}
    fam, cfg, kv = run.family, run.cfg, run.engine_args["kv_dtype"]
    return {
        "decode_flops": sum(fam.decode_step_flops(cfg, c)
                            for c in contexts),
        "decode_bytes": sum(fam.decode_step_bytes(cfg, sum(c), kv)
                            for c in contexts),
    }


def run_cell(cell, args, ctx) -> dict:
    run = ServeRun(cell, args.seed)
    run.build(args.seconds)
    if ctx.get("sabotage"):
        ctx["sabotage"](run)
    traced = ctx.get("trace_dir") is not None
    if traced:
        from tpu_dist.observe import metrics

        metrics.enable()
    run.warm_up()
    if traced:
        metrics.get_registry().reset()
    before = ctx["meter"].read()
    setup_s = time.perf_counter() - ctx["t_start"]
    snap: dict = {}
    host = run.window(
        args.seconds, trace_dir=ctx.get("trace_dir"),
        at_close=((lambda: snap.update(metrics.get_registry().snapshot()))
                  if traced else None))
    after = ctx["meter"].read()
    host["compiles_in_window"] = after["requests"] - before["requests"]
    host["setup_compile_s"] = before["compile_s"]
    if traced:
        metrics.disable()
        host["counters"] = snap["counters"]
        host["distributions"] = snap["distributions"]
    host["generator_lateness_p95_ms"] = stats.percentile(
        host["lateness_ms"], 95)
    memory_peak = ctx["memory_peak"]()
    work_done = layer_work(run, host) if traced else {}
    run.free_program()
    t_ref = time.perf_counter()
    numbers = run.reference_numbers()
    host["reference_s"] = time.perf_counter() - t_ref
    host["checked_tokens"] = numbers.pop("_tokens")
    end_to_end = {
        "serve_tokens_per_s": host["tokens_generated"] / host["window_s"],
        "ttft_p95_ms": stats.percentile(host["ttft_ms"], 95),
        # No gap at all means nothing was generated: that misses too.
        "itl_p95_ms": (stats.percentile(host["itl_ms"], 95)
                       if host["itl_ms"] else math.inf),
        "setup_s": setup_s,
    }
    for key in ("ttft_ms", "itl_ms", "lateness_ms",
                "traced_decode_contexts"):
        host[key + "_n"] = len(host.pop(key))
    return {"end_to_end": end_to_end, "host": host, "numbers": numbers,
            "attempted": host["sent"] + host["not_sent"],
            "failed": host["failed"] + host["not_sent"],
            "memory_peak_bytes": memory_peak, "work": work_done,
            "detail": {}}
