"""Device time by program name and by layer scope (``harness/scopes.py``),
on a hand-made trace whose answers can be worked out on paper, on an
XPlane written here the way a TPU trace keeps an operation's name stack,
and on the recorded traces, which hold no scopes."""

import json
import pathlib

import pytest

from tpubench.harness import readers, scopes, xplane
from tpubench.harness import trace as T

DATA = pathlib.Path(__file__).parent / "data"
US = 1000  # ns


def hand_made():
    """One device, a window of 100 us. The decode program runs 0-40 and
    50-90 (two executions), a swap program 92-96. Its operations: an
    attention fusion 0-10; a ``while`` 10-40 that holds two expert
    fusions 12-20 and 25-35 (the loop itself carries no name stack); a
    copy 50-55 with no name stack; the head 55-60; the experts again
    60-90; the swap's copy 92-96, unscoped."""
    ops = [["%fusion.1 = attn", 0, 10 * US],
           ["%while.2 = loop", 10 * US, 30 * US],
           ["%fusion.3 = moe", 12 * US, 8 * US],
           ["%fusion.4 = moe", 25 * US, 10 * US],
           ["%copy.5 = copy", 50 * US, 5 * US],
           ["%fusion.6 = head", 55 * US, 5 * US],
           ["%fusion.7 = moe", 60 * US, 30 * US],
           ["%copy.8 = copy", 92 * US, 4 * US]]
    return {
        "/device:TPU:0": {
            "XLA Ops": ops,
            "XLA Modules": [
                ["jit_serve_paged_decode_ragged(11)", 0, 40 * US],
                ["jit_serve_paged_decode_ragged(11)", 50 * US, 40 * US],
                ["jit_serve_swap_state(12)", 92 * US, 4 * US]],
            scopes.SCOPES_LINE: [
                ["tpu_dist.attn", 0, 10 * US],
                ["tpu_dist.moe", 12 * US, 8 * US],
                ["tpu_dist.moe", 25 * US, 10 * US],
                ["tpu_dist.head", 55 * US, 5 * US],
                ["tpu_dist.moe", 60 * US, 30 * US]]},
        "/host:CPU": {"python": [["tpubench.window", 0, 100 * US]]},
    }


def share(trace, **spec):
    return scopes.trace_scope_share({"trace": trace}, spec)


def test_scope_shares_by_hand():
    t = hand_made()
    # Busy: 0-40, 50-90, 92-96 = 84 us.
    assert T.busy_seconds(t) == pytest.approx(84e-6)
    assert share(t, patterns=[r"^tpu_dist\.moe$"]) == pytest.approx(
        100 * 48 / 84)
    assert share(t, patterns=[r"^tpu_dist\.(attn|mla|gqa)"]) == \
        pytest.approx(100 * 10 / 84)
    assert share(t, patterns=[r"^tpu_dist\.head$"]) == pytest.approx(
        100 * 5 / 84)
    # Under no scope: the loop's own 10-12, 20-25, 35-40, the copy 50-55
    # and the swap 92-96 = 21 us; the shares sum to the whole.
    unscoped = share(t, complement=True)
    assert unscoped == pytest.approx(100 * 21 / 84)
    assert unscoped + share(t, patterns=["."]) == pytest.approx(100)


def test_a_scope_over_a_loop_and_its_body_counts_once():
    t = hand_made()
    t["/device:TPU:0"][scopes.SCOPES_LINE].append(
        ["tpu_dist.moe", 10 * US, 30 * US])
    # The loop 10-40 is the experts' now: 30 + 30 us, not 30 + 18 + 30.
    assert share(t, patterns=[r"^tpu_dist\.moe$"]) == pytest.approx(
        100 * 60 / 84)
    assert share(t, complement=True) == pytest.approx(100 * 9 / 84)


def test_without_a_scopes_line_the_share_is_not_read():
    t = hand_made()
    del t["/device:TPU:0"][scopes.SCOPES_LINE]
    assert share(t, complement=True) is None
    assert share({}, complement=True) is None


def test_program_milliseconds_by_name():
    ctx = {"trace": hand_made()}
    read = scopes.trace_program_ms
    assert read(ctx, {"patterns": ["^jit_serve_paged_decode"]}) == \
        pytest.approx(40e-3)
    assert read(ctx, {"patterns": ["^jit_serve_swap_state"]}) == \
        pytest.approx(4e-3)
    assert read(ctx, {"patterns": ["^jit_serve_prefill"]}) is None
    assert read({"trace": {}}, {"patterns": ["."]}) is None


def test_scope_seconds_and_what_no_scope_holds():
    t = hand_made()
    assert scopes.scope_seconds(t) == pytest.approx(
        {"tpu_dist.attn": 10e-6, "tpu_dist.head": 5e-6,
         "tpu_dist.moe": 48e-6, "busy": 84e-6})
    assert dict(scopes.unscoped_ops(t)) == pytest.approx(
        {"while": 12e-6, "copy": 9e-6})


@pytest.mark.parametrize("stack, scope", [
    ("jit(serve_paged_prefill)/tpu_dist.moe/dot_general:", "tpu_dist.moe"),
    ("jit(scoped)/transpose(jvp(tpu_dist.gqa.window))/mul",
     "tpu_dist.gqa.window"),
    ("jit(f)/tpu_dist.dense/reshape;jit(f)/tpu_dist.head/dot_general",
     "tpu_dist.dense"),
    ("jit(f)/squeeze;jit(f)/tpu_dist.attn/add", "tpu_dist.attn"),
    ("jit(serve_next_inputs)/gather", None),
])
def test_scope_of_a_name_stack(stack, scope):
    assert scopes.scope_of(stack) == scope


def _xspace(path):
    """A device plane as a TPU trace writes one: an ``XLA Ops`` line whose
    events name metadata; the name stack is the metadata's ``tf_op`` stat,
    held inline or interned as a reference to a stat metadata."""
    space = xplane.space_class()()
    plane = space.planes.add(name="/device:TPU:0")
    stat_names = {1: "tf_op", 2: "hlo_category",
                  3: "jit(serve_prefill_chunk)/tpu_dist.kda/while/body/mul:"}
    for key, name in stat_names.items():
        entry = plane.stat_metadata.add(key=key)
        entry.value.id, entry.value.name = key, name
    for key, stats in (
            (10, [(1, "jit(serve_x)/tpu_dist.moe/dot:", 0)]),
            (11, [(2, "convolution", 0), (1, "", 3)]),
            (12, [(2, "data formatting", 0)])):
        entry = plane.event_metadata.add(key=key)
        entry.value.id = key
        for sid, text, ref in stats:
            stat = entry.value.stats.add(metadata_id=sid)
            if ref:
                stat.ref_value = ref
            else:
                stat.str_value = text
    line = plane.lines.add(name="XLA Ops", timestamp_ns=5000)
    for mid, off_ps, dur_ps in ((10, 1500, 2_000_000), (11, 3_000_000, 999),
                                (12, 4_000_000, 7000), (10, 9_000_000, 1000)):
        line.events.add(metadata_id=mid, offset_ps=off_ps, duration_ps=dur_ps)
    other = space.planes.add(name="/host:CPU")
    other.lines.add(name="python")
    path.parent.mkdir(parents=True)
    path.write_bytes(space.SerializeToString())


def test_load_op_scopes_reads_the_metadata_of_each_event(tmp_path):
    _xspace(tmp_path / "plugins" / "profile" / "t.xplane.pb")
    got = scopes.load_op_scopes(tmp_path)
    assert got == {"/device:TPU:0": {scopes.SCOPES_LINE: [
        ["tpu_dist.moe", 5001, 2000],
        ["tpu_dist.kda", 8000, 0],
        ["tpu_dist.moe", 14000, 1]]}}
    trace = {"/device:TPU:0": {"XLA Ops": []}}
    assert scopes.with_op_scopes(trace, tmp_path)["/device:TPU:0"] == {
        "XLA Ops": [], **got["/device:TPU:0"]}


@pytest.mark.parametrize("name", ["recorded_trace.json",
                                  "recorded_serve_trace.json"])
def test_the_recorded_traces_hold_no_scopes(name):
    t = json.loads((DATA / name).read_text())["trace"]
    assert share(t, complement=True) is None
    assert scopes.scope_seconds(t)["busy"] == pytest.approx(
        T.busy_seconds(t))


def test_program_milliseconds_on_the_recorded_serve_trace():
    """The recorded serve programs carry no kind (``jit__unknown``): the
    one told by what it runs, as ``decode_step_mfu`` tells the decode, and
    its milliseconds are the seconds such a metric divides by."""
    t = json.loads((DATA / "recorded_serve_trace.json").read_text())["trace"]
    spec = {"patterns": ["^jit__unknown"], "containing": [r"f32\[50257\]"]}
    seconds, count = readers._program_seconds(
        {"trace": t}, {"line": T.MODULES_LINE, **spec})
    assert count == 1
    assert scopes.trace_program_ms({"trace": t}, spec) == pytest.approx(
        1e3 * seconds)
