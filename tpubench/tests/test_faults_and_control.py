"""``correct`` comes out false when the timed path is broken underneath,
and when the control (the reference one precision down) stands in the
program's place. These drive the rest of a run as the chip would, at the
configurations' rehearsal sizes on the CPU, skipping only the harness's
look for a chip."""

import pytest

from tpubench import run as bench_run
from tpubench.harness import checks, serve_cell, train_cell

SEED = 2 ** 31 + 77


def _run(workload, sabotage=None, seconds="2"):
    args = bench_run.parse(["--workload", workload, "--seed", str(SEED),
                            "--seconds", seconds, "--trace", "0",
                            "--rehearse", "1"])
    cell = bench_run.load_cell(args)
    return bench_run.run_cell(cell, args, sabotage=sabotage)


def _failed(result):
    return [r["name"] for r in result["rows"] if not r["ok"]]


class _PartialLoss:
    """The loss over the first ``share`` of the batch only: half of the
    batch left out, or one chip's rows when the exchange is left out."""

    def __init__(self, loss, share):
        self.loss, self.share = loss, share
        self.name = getattr(loss, "name", "loss")

    def __call__(self, logits, labels):
        n = max(1, int(logits.shape[0] * self.share))
        return self.loss(logits[:n], labels[:n])


def _freeze(run):
    run.model.optimizer.update = lambda grads, state, params: (params, state)


def _half_batch(run):
    run.model.loss = _PartialLoss(run.model.loss, 0.5)


def _no_exchange(run):
    run.model.loss = _PartialLoss(run.model.loss, 1.0 / run.chips)


def test_the_sound_training_path_is_correct():
    result = _run("train.gpt2-medium.dp1")
    assert result["checks_ok"], result["rows"]


@pytest.mark.parametrize("workload,sabotage", [
    ("train.gpt2-medium.dp1", _freeze),
    ("train.gpt2-medium.dp1", _half_batch),
    ("train.gpt2-medium.dp4", _no_exchange),
], ids=["state_unchanged", "half_batch", "exchange_left_out"])
def test_a_broken_training_step_is_not_correct(workload, sabotage):
    result = _run(workload, sabotage)
    assert not result["checks_ok"]
    assert set(_failed(result)) & {"loss_gap", "grad_gap", "delta_gap"}


def test_the_sound_four_chip_path_is_correct():
    result = _run("train.gpt2-medium.dp4")
    assert result["checks_ok"], result["rows"]
    assert result["device"]["count"] == 4


SERVE_CELLS = ["serve.gpt2-large.chat", "serve.gpt2-large.backlog"]


@pytest.mark.parametrize("workload", SERVE_CELLS)
def test_the_sound_serving_path_is_correct(workload):
    result = _run(workload, seconds="3")
    assert result["checks_ok"], result["rows"]
    assert result["host"]["checked_tokens"] > 20
    assert result["failed"] == 0


def test_a_backlog_outlasts_its_window_and_is_drained():
    """Every request is due at t = 0 and some are still queued when the
    window closes; the drain answers them all, and only the window's
    tokens count."""
    result = _run("serve.gpt2-large.backlog", seconds="0.05")
    host = result["host"]
    assert host["sent"] == 40 and host["not_sent"] == 0
    assert host["queued_at_close"] > 0 and result["failed"] == 0
    assert host["prompt_tokens_admitted"] < host["prompt_tokens_sent"]
    assert result["checks_ok"], result["rows"]


@pytest.mark.parametrize("workload", SERVE_CELLS)
def test_an_altered_token_is_not_correct(workload):
    def alter(run):
        pick, calls = run.engine._pick, [0]

        def wrong(logits):
            calls[0] += 1
            token = pick(logits)
            return (token + 1) % len(logits) if calls[0] % 5 == 0 else token

        run.engine._pick = wrong

    result = _run(workload, alter, seconds="3")
    assert _failed(result) == ["served_logit_gap"]


def test_the_training_control_is_not_correct():
    args = bench_run.parse(["--workload", "train.gpt2-medium.dp1",
                            "--rehearse", "1"])
    cell = bench_run.load_cell(args)
    run = train_cell.TrainRun(cell, SEED, chips=1)
    from tpubench.harness import traffic

    run.x, run.y = traffic.token_rows(SEED, run.vocab,
                                      run.global_batch * 3, run.seq)
    ref = run.reference_numbers()
    control = checks.train_numbers(run.reference_numbers(quant="fp8"), ref)
    rows = checks.judge(control, cell.mix["limits"])
    assert not all(r["ok"] for r in rows), rows


@pytest.mark.parametrize("workload", SERVE_CELLS)
def test_the_serving_control_is_not_correct(workload):
    args = bench_run.parse(["--workload", workload, "--rehearse", "1"])
    cell = bench_run.load_cell(args)
    run = serve_cell.ServeRun(cell, SEED)
    run.build(3.0)
    run.warm_up()
    run.window(3.0)
    run.free_program()
    program = run.reference_numbers()
    control = run.reference_numbers(quant="fp8")
    limit = cell.mix["limits"]["served_logit_gap"]
    assert program["served_logit_gap"] <= limit
    assert control["served_logit_gap"] > limit
