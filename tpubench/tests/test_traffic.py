"""Seeded traffic repeats exactly; every seed offers the same work."""

import numpy as np

from tpubench.harness import cells, traffic

CHAT = cells.load_json(cells.BENCH_DIR / "traffic" / "chat.json")
BACKLOG = cells.load_json(cells.BENCH_DIR / "traffic" / "backlog.json")


def _plan(seed, seconds=30.0):
    return traffic.plan_requests(CHAT, seconds, seed, 50257, 1024)


def test_the_same_seed_gives_the_same_requests():
    a, b = _plan(2 ** 31 + 12345), _plan(2 ** 31 + 12345)
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.max_new_tokens) for r in b]


def test_every_seed_offers_the_same_work_at_the_same_moments():
    a, b = _plan(1), _plan(2)
    shape = lambda plan: [(r.due_s, len(r.prompt), r.max_new_tokens,
                           r.prefix_id, r.repeat_of) for r in plan]
    assert shape(a) == shape(b)
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_the_mix_is_what_its_file_says():
    plan = _plan(3, seconds=40.0)
    n = len(plan)
    assert abs(n - CHAT["rate_rps"] * 40.0) < 0.15 * n
    lens = np.array([len(r.prompt) for r in plan if r.prefix_id < 0
                     and r.repeat_of < 0])
    assert CHAT["prompt_len"]["min"] <= lens.min()
    assert lens.max() <= CHAT["prompt_len"]["max"]
    assert all(len(r.prompt) + r.max_new_tokens <= 1024 for r in plan)
    shared = [r for r in plan if r.prefix_id >= 0]
    assert abs(len(shared) - n / 2) <= 0.08 * n
    assert len({tuple(r.prompt[:128]) for r in shared}) == CHAT["prefix_count"]
    repeats = [r for r in plan if r.repeat_of >= 0]
    assert repeats and all(
        r.prompt == plan[r.repeat_of].prompt for r in repeats)
    # Bursts: burst_size arrivals inside burst_span_s, several times.
    due = np.array([r.due_s for r in plan])
    in_span = [(np.abs(due - t) <= CHAT["burst_span_s"]).sum() for t in due]
    assert max(in_span) >= CHAT["burst_size"]


def test_warmup_reaches_every_prefill_pad_and_a_copy_on_write():
    plan = _plan(4)
    warm = traffic.warmup_prompts(CHAT, 4, 50257, 256, plan)
    lens = [len(p) for p, _ in warm]
    assert lens[:6] == [8, 16, 32, 64, 128, 256]
    assert warm[-1][0] == warm[-2][0]


def test_token_rows_all_differ_and_shift_by_one():
    x, y = traffic.token_rows(2 ** 31 + 5, 50257, 64, 128)
    assert len({row.tobytes() for row in x}) == 64
    assert (x[:, 1:] == y[:, :-1]).all()


def test_a_backlog_is_due_all_at_once_whatever_the_window():
    """``requests_at_once`` is a parameter of a mix: that many requests,
    all due at t = 0, the same lengths for every seed and every window."""
    n = BACKLOG["requests_at_once"]
    assert n % 128 == 0 and "rate_rps" not in BACKLOG
    a = traffic.plan_requests(BACKLOG, 40.0, 5, 50257, 1024)
    b = traffic.plan_requests(BACKLOG, 7.0, 6, 50257, 1024)
    assert len(a) == len(b) == n
    assert {r.due_s for r in a} == {0.0}
    shape = lambda plan: [(len(r.prompt), r.max_new_tokens, r.prefix_id,
                           r.repeat_of) for r in plan]
    assert shape(a) == shape(b)
    # Chat's lengths, prefixes and repeats, letter for letter.
    for key in ("prompt_len", "output_len", "prefix_share", "prefix_count",
                "prefix_len", "prefix_min_tail", "repeat_share",
                "schedule_seed", "engine", "policy", "check"):
        assert BACKLOG[key] == CHAT[key], key
    shared = [r for r in a if r.prefix_id >= 0]
    assert abs(len(shared) - n / 2) <= 0.08 * n
    repeats = [r for r in a if r.repeat_of >= 0]
    assert abs(len(repeats) - 0.05 * n) <= 0.02 * n
    assert all(r.prompt == a[r.repeat_of].prompt for r in repeats)
    lens = np.array([len(r.prompt) for r in a])
    assert 150 <= np.median(lens) <= 260 and lens.max() <= 768 + 128
