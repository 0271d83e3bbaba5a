"""The one general traffic generator. A mix is a JSON file of parameters
under ``tpubench/traffic/``; nothing here knows a mix by name.

Two kinds of mix:

* ``"kind": "train"`` — a training job: rows per chip, sequence length,
  steps per epoch, optimizer settings. :func:`token_rows` makes its data.
* ``"kind": "serve"`` — an open-loop request schedule (``rate_rps`` with
  its bursts, or ``requests_at_once``: a batch job's queue). Arrival times and
  each arrival's prompt length, output length, system prompt and repeat
  are fixed by the file (``schedule_seed``), so every run seed offers the
  same work at the same moments and only the tokens differ: a tail over
  some tens of requests is set by which lengths meet in a burst, and a
  seed that reshuffled them would change the work. Lengths are the
  stratified quantiles of a clipped log-normal, not draws.
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass
class PlannedRequest:
    due_s: float
    prompt: list
    max_new_tokens: int
    prefix_id: int      # -1: no shared system prompt
    repeat_of: int      # -1: own prompt; else index of the request copied


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> np.ndarray:
    """n stratified quantiles of a log-normal, clipped to [lo, hi]."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def arrival_times(mix: dict, seconds: float) -> np.ndarray:
    """Arrival offsets in [0, seconds). A mix with ``requests_at_once``
    hands the whole job over at t = 0: that many requests, all due at
    once, whatever ``seconds`` is. Otherwise a steady stream carrying
    ``steady_share`` of ``rate_rps`` with exponential gaps, and bursts of
    ``burst_size`` requests spread evenly over ``burst_span_s`` carrying
    the rest, so one burst every burst_size / ((1 - share) * rate)
    seconds. All of it from ``schedule_seed``, never from the run seed."""
    if mix.get("requests_at_once"):
        return np.zeros(int(mix["requests_at_once"]))
    rng = np.random.default_rng(mix["schedule_seed"])
    rate, share = float(mix["rate_rps"]), float(mix["steady_share"])
    times = []
    t = 0.0
    steady = rate * share
    if steady > 0:
        while True:
            t += rng.exponential(1.0 / steady)
            if t >= seconds:
                break
            times.append(t)
    burst_rate = rate * (1.0 - share)
    if burst_rate > 0:
        size, span = int(mix["burst_size"]), float(mix["burst_span_s"])
        period = size / burst_rate
        start = period / 2
        while start + span < seconds:
            times.extend(start + span * k / size for k in range(size))
            start += period
    return np.sort(np.asarray(times))


def plan_requests(mix: dict, seconds: float, seed: int, vocab: int,
                  max_len: int) -> list[PlannedRequest]:
    """The window's requests, in arrival order."""
    due = arrival_times(mix, seconds)
    n = len(due)
    if n == 0:
        return []
    fixed = np.random.default_rng(mix["schedule_seed"] + 1)
    p, o = mix["prompt_len"], mix["output_len"]
    prompt_lens = _lognormal_quantiles(n, p["median"], p["sigma"],
                                       p["min"], p["max"])
    out_lens = fixed.permutation(_lognormal_quantiles(
        n, o["median"], o["sigma"], o["min"], o["max"]))
    n_prefix = int(round(n * mix["prefix_share"]))
    prefix_ids = np.full(n, -1)
    prefix_ids[fixed.permutation(n)[:n_prefix]] = (
        np.arange(n_prefix) % mix["prefix_count"])
    n_repeat = int(round(n * mix.get("repeat_share", 0.0)))
    order = fixed.permutation(n)
    repeat_at = set(
        fixed.permutation(np.arange(n // 4, n))[:n_repeat].tolist())
    # The run seed: the tokens, and nothing else.
    rng = np.random.default_rng(seed)
    prefix_len = int(mix["prefix_len"])
    prefixes = rng.integers(0, vocab, size=(mix["prefix_count"], prefix_len))
    lag = max(1, int(mix.get("repeat_lag", 8)))
    plan: list[PlannedRequest] = []
    for slot in range(n):
        i = order[slot]
        out = int(out_lens[i])
        if slot in repeat_at and plan[slot - lag].repeat_of < 0:
            src = plan[slot - lag]
            prompt, pid, rep = list(src.prompt), src.prefix_id, slot - lag
        else:
            pid, rep = int(prefix_ids[i]), -1
            plen = int(prompt_lens[i])
            if pid >= 0:
                tail = max(plen - prefix_len, int(mix["prefix_min_tail"]))
                prompt = (prefixes[pid].tolist()
                          + rng.integers(0, vocab, size=tail).tolist())
            else:
                prompt = rng.integers(0, vocab, size=plen).tolist()
        out = max(1, min(out, max_len - len(prompt)))
        plan.append(PlannedRequest(float(due[slot]), prompt, out, pid, rep))
    return plan


def warmup_prompts(mix: dict, seed: int, vocab: int, chunk: int,
                   plan: list[PlannedRequest]) -> list[tuple[list, int]]:
    """(prompt, max_new_tokens) pairs that make the engine compile every
    program the mix can reach: one prompt per power-of-two prefill pad up
    to ``chunk``, the mix's system prompts (so that the window starts with
    them cached, as a server that has been up does), and one exact repeat
    (copy-on-write of a shared tail page)."""
    rng = np.random.default_rng(seed + 7919)
    out = []
    pad = 8
    while pad <= chunk:
        out.append((rng.integers(0, vocab, size=pad).tolist(), 2))
        pad *= 2
    seen = {}
    for r in plan:
        if r.prefix_id >= 0 and r.prefix_id not in seen:
            seen[r.prefix_id] = r.prompt[:int(mix["prefix_len"])]
    for pid in sorted(seen):
        out.append((seen[pid] + rng.integers(0, vocab, size=9).tolist(), 2))
    if out:
        out.append((list(out[-1][0]), 2))
    return out


def token_rows(seed: int, vocab: int, rows: int, seq: int):
    """``rows`` next-token rows that all differ: (x, y) int32 [rows, seq]."""
    rng = np.random.default_rng(seed)
    t = rng.integers(0, vocab, size=(rows, seq + 1), dtype=np.int64)
    return t[:, :-1].astype(np.int32), t[:, 1:].astype(np.int32)
