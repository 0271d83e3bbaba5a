"""ServeFleet (serve/fleet.py): prefix-affinity routing, journal-backed
failover, autoscaling, and the fleet fault grammar.

Pins: the router key IS the prefix-cache key (``prompt_digest`` vs the
live ``_full``/``_partial`` cache tables); fleet token streams are
bit-identical to an uninterrupted solo engine (routing, failover, and
rid-space merges included); a 1-replica fleet compiles exactly the solo
program set (the router adds no device programs); torn trailing journal
lines and overlapping rid spaces are survivable; autoscale decisions
are deterministic functions of router-side signals.
"""

import numpy as np
import pytest

from tpu_dist.models.transformer import build_transformer_lm
from tpu_dist.resilience.faults import (FLEET_KINDS, SERVE_KINDS, FaultPlan,
                                        FaultSpec)
from tpu_dist.serve import journal as journal_lib
from tpu_dist.serve.engine import ServeEngine
from tpu_dist.serve.fleet import (AutoscalePolicy, FleetFaultInjector,
                                  ReplicaKilled, ServeFleet)
from tpu_dist.serve.paging import PagedKVState, PrefixCache
from tpu_dist.serve.paging import _ROOT, _digest
from tpu_dist.serve.scheduler import DONE

VOCAB = 32
PAGE = 8


@pytest.fixture(scope="module")
def model():
    model = build_transformer_lm(VOCAB, 32, d_model=16, depth=1,
                                 num_heads=2)
    model.init(0)
    return model


def _factory(model, **engine_kwargs):
    def factory(replica, *, journal, fault_injector):
        del replica
        return ServeEngine(model, max_batch=4, max_len=32, seed=0,
                           journal=journal, fault_injector=fault_injector,
                           **engine_kwargs)
    return factory


def _sessioned_workload(sessions=3, visits=3, *, seed=0):
    """Shared full-page prefixes + ragged suffixes, work-identical
    sessions (same per-visit suffix/budget schedule)."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(1, VOCAB, size=PAGE).tolist()
                for _ in range(sessions)]
    suffix_lens = [int(rng.integers(1, 4)) for _ in range(visits)]
    budgets = [int(rng.integers(3, 7)) for _ in range(visits)]
    out = []
    for v in range(visits):
        for s in range(sessions):
            suffix = rng.integers(1, VOCAB, size=suffix_lens[v]).tolist()
            out.append((prefixes[s] + suffix, budgets[v]))
    return out


def _solo_streams(model, workload, **engine_kwargs):
    solo = ServeEngine(model, max_batch=4, max_len=32, seed=0,
                       **engine_kwargs)
    reqs = [solo.submit(p, max_new_tokens=n) for p, n in workload]
    solo.run_until_idle()
    programs = solo.compiled_programs()
    solo.close()
    return [list(r.generated) for r in reqs], programs


# -- satellite: router-key == cache-key --------------------------------------


class TestPromptDigest:
    def _state(self):
        return PagedKVState(num_pages=16, page_size=4, slots=4,
                            max_pages=6, bytes_per_token=8)

    def test_full_page_digest_is_full_cache_key(self):
        """A page-aligned prompt's digest is the exact key its last page
        sits under in the live ``_full`` table."""
        st = self._state()
        prompt = list(range(1, 9))  # 2 full pages at page_size=4
        st.allocator.reserve_pending(2)
        st.begin(0, prompt, 8)
        st.register_prefill(0, prompt)
        key = PrefixCache.prompt_digest(prompt, 4)
        assert key in st.prefix._full
        # And it is the chain walked page by page from the root.
        assert key == _digest(_digest(_ROOT, tuple(prompt[:4])),
                              tuple(prompt[4:]))

    def test_partial_tail_digest_is_hashed_partial_key(self):
        """A ragged prompt's digest folds the tail into the parent chain
        — the hashed form of the ``(parent, tail)`` ``_partial`` key."""
        st = self._state()
        prompt = list(range(1, 8))  # 1 full page + tail of 3
        st.allocator.reserve_pending(3)
        st.begin(0, prompt, 9)
        st.register_prefill(0, prompt)
        st.finish(0, prompt)  # partial tail cached at finish
        ((parent, tail),) = st.prefix._partial.keys()
        assert PrefixCache.prompt_digest(prompt, 4) == _digest(parent, tail)
        assert parent == PrefixCache.prompt_digest(prompt[:4], 4)

    def test_sub_page_and_empty_prompts(self):
        assert PrefixCache.prompt_digest([5, 6, 7], 4) == _digest(
            _ROOT, (5, 6, 7))
        assert PrefixCache.prompt_digest([], 4) == _ROOT

    def test_page_size_validated(self):
        with pytest.raises(ValueError, match="page_size"):
            PrefixCache.prompt_digest([1, 2], 0)


# -- fleet fault grammar ------------------------------------------------------


class TestFleetFaultGrammar:
    def test_replica_kill_parses_with_replica_address(self):
        (f,) = FaultPlan.parse("replica_kill@req2:replica1").faults
        assert (f.kind, f.req, f.replica) == ("replica_kill", 2, 1)
        (g,) = FaultPlan.parse("replica-kill@req0").faults
        assert (g.kind, g.req, g.replica) == ("replica_kill", 0, None)

    def test_router_storm_parses_with_count(self):
        (f,) = FaultPlan.parse("router_storm@req3:x8").faults
        assert (f.kind, f.req, f.count) == ("router_storm", 3, 8)
        (g,) = FaultPlan.parse("router-storm@req0").faults
        assert g.kind == "router_storm"

    def test_replica_address_rejected_on_other_kinds(self):
        with pytest.raises(ValueError, match="replica"):
            FaultPlan.parse("engine_crash@req1:replica1")

    def test_fleet_kinds_are_serve_kinds(self):
        assert FLEET_KINDS < SERVE_KINDS

    def test_injector_arms_only_its_replica(self):
        spec = FaultSpec(kind="replica_kill", req=1, replica=1)
        assert not FleetFaultInjector(0, [spec]).faults
        inj = FleetFaultInjector(1, [spec])
        inj.on_step_end(0)  # not due yet
        with pytest.raises(ReplicaKilled):
            inj.on_step_end(1)
        assert inj.fired and inj.fired[0]["replica"] == 1

    def test_chaos_cli_rejects_fleet_kinds(self, capsys):
        from tpu_dist.serve.cli import main
        assert main(["--chaos", "--plan", "replica_kill@req0"]) == 2
        assert "--fleet" in capsys.readouterr().err

    def test_fleet_cli_rejects_solo_kinds(self, capsys):
        from tpu_dist.serve.cli import main
        assert main(["--fleet", "--plan", "engine_crash@req0"]) == 2
        assert "--chaos" in capsys.readouterr().err

    def test_fleet_cli_leaves_the_registry_off(self):
        """``run_fleet`` records while it runs and, however it returns,
        hands the registry back off: a later test (or caller) in the same
        process finds spans recording nothing."""
        from tpu_dist.observe import metrics
        from tpu_dist.serve.cli import main
        assert not metrics.enabled()
        assert main(["--fleet", "--plan", "engine_crash@req0"]) == 2
        assert not metrics.enabled()

    def test_fleet_ctor_rejects_solo_kinds(self, model):
        plan = FaultPlan.parse("engine_crash@req0")
        with pytest.raises(ValueError, match="--chaos"):
            ServeFleet(_factory(model), plan=plan)


# -- routing + parity ---------------------------------------------------------


class TestFleetRouting:
    def test_parity_affinity_and_program_pin(self, model, tmp_path):
        """One workload, three runs: solo, 1-replica fleet, 2-replica
        fleet.  All stream bit-identically; the 1-replica fleet compiles
        exactly the solo program set; the 2-replica run routes by both
        affinity and fallback."""
        workload = _sessioned_workload(sessions=4, visits=3)
        baseline, solo_programs = _solo_streams(model, workload)

        for replicas in (1, 2):
            fleet = ServeFleet(_factory(model), replicas=replicas,
                               page_size=PAGE,
                               journal_root=str(tmp_path / f"j{replicas}"))
            fleet.start()
            frs = [fleet.submit(p, max_new_tokens=n) for p, n in workload]
            fleet.drain(timeout_s=120.0)
            fleet.close()
            assert all(fr.status == DONE for fr in frs)
            assert [fr.tokens for fr in frs] == baseline
            if replicas == 1:
                # Steady-state router adds no device programs.
                assert fleet.compiled_programs() == {0: solo_programs}
                assert fleet.route_counts["affinity"] > 0
            else:
                assert fleet.route_counts["affinity"] >= 1
                assert fleet.route_counts["fallback"] >= 1
                # Sessions stick: every request of a session lands on
                # the replica its first visit chose.
                by_session = {}
                for (prompt, _), fr in zip(workload, frs):
                    by_session.setdefault(tuple(prompt[:PAGE]),
                                          set()).add(fr.replica)
                assert all(len(v) == 1 for v in by_session.values())

    def test_hot_prefix_load_shed_overrides_affinity(self, model):
        """A hotspot session pins one replica; once that replica's queue
        runs ``affinity_load_slack`` outstanding requests past the
        coldest one, further hot requests shed to the cold replica
        (route == 'overridden', counted) WITHOUT re-pinning — after the
        queue drains the session snaps back to its warm replica.

        Outstanding counters only decay on the main-thread drain, so a
        burst submitted without draining sees a deterministic decision
        sequence regardless of worker timing."""
        fleet = ServeFleet(_factory(model), replicas=2, page_size=PAGE,
                           affinity_load_slack=3)
        fleet.start()
        hot = list(range(1, PAGE + 1))       # one full page: real digest
        first = fleet.submit(hot + [1], max_new_tokens=2)
        assert first.route == "fallback"     # first visit pins
        pin, cold = first.replica, 1 - first.replica
        burst = [fleet.submit(hot + [2], max_new_tokens=2)
                 for _ in range(6)]
        # Leads vs the cold replica: 1,2,3 -> affinity; 4 -> shed;
        # 3 -> affinity; 4 -> shed.
        assert [fr.route for fr in burst] == [
            "affinity", "affinity", "affinity", "overridden",
            "affinity", "overridden"]
        assert [fr.replica for fr in burst] == [
            pin, pin, pin, cold, pin, cold]
        assert fleet.route_counts == {
            "affinity": 4, "fallback": 1, "affinity_overridden": 2}
        fleet.drain(timeout_s=60.0)
        # Shedding never migrated the pin: the drained session still
        # routes to its warm replica.
        after = fleet.submit(hot + [3], max_new_tokens=2)
        assert after.route == "affinity" and after.replica == pin
        fleet.drain(timeout_s=60.0)
        fleet.close()
        assert all(fr.status == DONE
                   for fr in [first, after] + burst)

    def test_short_prompts_route_stateless(self, model):
        """Prompts under one page have no reusable pages: least-loaded
        spread, never pinned to one replica by a shared root digest."""
        fleet = ServeFleet(_factory(model), replicas=2, page_size=PAGE)
        fleet.start()
        frs = [fleet.submit([7, 8, 9], max_new_tokens=3) for _ in range(2)]
        assert {fr.replica for fr in frs} == {0, 1}
        assert all(fr.route == "fallback" for fr in frs)
        fleet.drain(timeout_s=60.0)
        fleet.close()
        assert all(fr.status == DONE for fr in frs)


# -- failover -----------------------------------------------------------------


class TestFleetFailover:
    def test_double_kill_merges_rid_spaces_onto_survivor(self, model,
                                                         tmp_path):
        """Kill replicas 0 and 1 at their first step: both rid spaces
        (overlapping, both starting at rid 0) merge onto replica 2 via
        ``reserve_rid``-backed adoption.  Every request completes with
        the uninterrupted solo stream; the survivor records no restart
        and no rid collides."""
        workload = _sessioned_workload(sessions=3, visits=3)
        baseline, _ = _solo_streams(model, workload)
        plan = FaultPlan.parse(
            "replica_kill@req0:replica0,replica_kill@req0:replica1")
        fleet = ServeFleet(_factory(model), replicas=3, page_size=PAGE,
                           plan=plan, journal_root=str(tmp_path))
        fleet.start()
        frs = [fleet.submit(p, max_new_tokens=n) for p, n in workload]
        fleet.drain(timeout_s=120.0)
        fleet.close()

        assert sorted(d["replica"] for d in fleet.deaths) == [0, 1]
        assert all(d["killed"] for d in fleet.deaths)
        assert fleet.failover_replayed >= 2
        assert all(fr.status == DONE for fr in frs)
        assert [fr.tokens for fr in frs] == baseline
        # Both dead replicas allocated from the same rid space...
        rids0 = set(fleet._workers[0].rid_map())
        rids1 = set(fleet._workers[1].rid_map())
        assert rids0 & rids1
        # ...yet every request that finished on the survivor holds a
        # distinct rid there (adopt_request reserved fresh ones).
        survivor_rids = [fr.rid for fr in frs if fr.replica == 2]
        assert len(survivor_rids) == len(set(survivor_rids))
        assert fleet._workers[2].restarts == 0 and fleet._workers[2].killed \
            is False

    def test_mid_stream_kill_resumes_from_journal(self, model, tmp_path):
        """A kill after some completions leaves journaled mid-stream
        tokens; adoption resumes them and the streams stay
        bit-identical."""
        workload = _sessioned_workload(sessions=2, visits=4)
        baseline, _ = _solo_streams(model, workload)
        plan = FaultPlan.parse("replica_kill@req1:replica0")
        fleet = ServeFleet(_factory(model), replicas=2, page_size=PAGE,
                           plan=plan, journal_root=str(tmp_path))
        fleet.start()
        frs = [fleet.submit(p, max_new_tokens=n) for p, n in workload]
        fleet.drain(timeout_s=120.0)
        fleet.close()
        assert [d["replica"] for d in fleet.deaths] == [0]
        assert fleet.failover_replayed >= 1
        assert all(fr.status == DONE for fr in frs)
        assert [fr.tokens for fr in frs] == baseline
        assert fleet._workers[1].restarts == 0

    def test_replay_tolerates_torn_trailing_journal_line(self, model,
                                                         tmp_path):
        """The fleet replay path (``journal.load`` on the dead replica's
        file, then ``adopt_request`` on a survivor) with the journal's
        last line torn mid-append — exactly what a kill between
        ``write`` and ``fsync`` leaves behind."""
        prompt = list(range(1, 11))
        dead = ServeEngine(model, max_batch=4, max_len=32, seed=0,
                           journal=str(tmp_path / "dead"))
        req = dead.submit(prompt, max_new_tokens=6)
        for _ in range(3):
            dead.step()
        # Abandon the engine un-closed (kill semantics) and tear the
        # trailing line the way a mid-append death would.
        path = tmp_path / "dead" / journal_lib.JOURNAL_NAME
        with open(path, "a", encoding="utf-8") as fh:
            fh.write('{"kind": "token", "rid"')
        state = journal_lib.load(path)
        partial = list(state.requests[req.rid].tokens)
        assert 0 < len(partial) < 6  # genuinely mid-stream
        survivor = ServeEngine(model, max_batch=4, max_len=32, seed=0,
                               journal=str(tmp_path / "survivor"))
        adopted = survivor.adopt_request(prompt, generated=partial,
                                         max_new_tokens=6)
        survivor.run_until_idle()
        survivor.close()
        solo = ServeEngine(model, max_batch=4, max_len=32, seed=0)
        base = solo.submit(prompt, max_new_tokens=6)
        solo.run_until_idle()
        solo.close()
        assert adopted.status == DONE
        assert list(adopted.generated) == list(base.generated)

    def test_int8_ragged_replicas_smoke(self, model, tmp_path):
        """The factory seam carries ``kv_dtype``/``ragged`` untouched: a
        fleet of int8 ragged paged replicas must stream bit-identically
        to a solo engine in the same configuration, surviving a kill +
        journal failover along the way."""
        quant_kw = dict(paged=True, page_size=PAGE, kv_dtype="int8",
                        ragged=True)
        workload = _sessioned_workload(sessions=2, visits=3)
        baseline, _ = _solo_streams(model, workload, **quant_kw)
        plan = FaultPlan.parse("replica_kill@req1:replica0")
        fleet = ServeFleet(_factory(model, **quant_kw), replicas=2,
                           page_size=PAGE, plan=plan,
                           journal_root=str(tmp_path))
        fleet.start()
        frs = [fleet.submit(p, max_new_tokens=n) for p, n in workload]
        fleet.drain(timeout_s=120.0)
        fleet.close()
        assert [d["replica"] for d in fleet.deaths] == [0]
        assert fleet.failover_replayed >= 1
        assert all(fr.status == DONE for fr in frs)
        assert [fr.tokens for fr in frs] == baseline

    def test_adopt_request_reprefills_int8_midstream(self, model):
        """Failover migration onto an int8 survivor: ``adopt_request``
        carries tokens, never pool bytes, so the survivor re-prefills —
        and re-quantizes — prompt + partial stream from scratch. Per-
        position scaling makes those bytes independent of the donor's
        write history, so the resumed stream must match a solo int8 run
        bit-for-bit."""
        quant_kw = dict(paged=True, page_size=PAGE, kv_dtype="int8")
        prompt = list(range(1, 11))
        solo = ServeEngine(model, max_batch=4, max_len=32, seed=0,
                           **quant_kw)
        base = solo.submit(prompt, max_new_tokens=6)
        solo.run_until_idle()
        solo.close()
        partial = list(base.generated)[:3]
        survivor = ServeEngine(model, max_batch=4, max_len=32, seed=0,
                               **quant_kw)
        adopted = survivor.adopt_request(prompt, generated=partial,
                                         max_new_tokens=6)
        survivor.run_until_idle()
        survivor.close()
        assert adopted.status == DONE
        assert list(adopted.generated) == list(base.generated)

    def test_router_storm_settles(self, model):
        plan = FaultPlan.parse("router_storm@req1:x5")
        fleet = ServeFleet(_factory(model), replicas=2, page_size=PAGE,
                           plan=plan, storm_vocab=VOCAB)
        fleet.start()
        workload = _sessioned_workload(sessions=2, visits=2)
        frs = [fleet.submit(p, max_new_tokens=n) for p, n in workload]
        fleet.drain(timeout_s=120.0)
        fleet.close()
        assert fleet._storm_fired and fleet._storm_fired[0]["count"] == 5
        chaff = [f for f in fleet.requests.values() if f.chaff]
        assert len(chaff) == 5
        assert all(f.status is not None for f in chaff)
        assert all(fr.status == DONE for fr in frs)


# -- autoscaling --------------------------------------------------------------


class TestAutoscale:
    def test_decide_is_deterministic(self):
        pol = AutoscalePolicy(min_replicas=1, max_replicas=3,
                              scale_up_outstanding=4, ttft_target_s=0.2,
                              idle_ticks_down=5)
        up = pol.decide(outstanding={0: 4, 1: 5}, idle_ticks={},
                        step_ema_s=None, max_batch=4)
        assert up[0] == "up"
        ttft = pol.decide(outstanding={0: 3, 1: 0}, idle_ticks={0: 0, 1: 0},
                          step_ema_s=1.0, max_batch=4)
        assert ttft[0] == "up"  # projected 3/(2*4)*1.0 = 0.375s > 0.2s
        hold = pol.decide(outstanding={0: 1, 1: 0}, idle_ticks={0: 0, 1: 2},
                          step_ema_s=0.01, max_batch=4)
        assert hold[0] == "hold"
        down = pol.decide(outstanding={0: 0, 1: 0},
                          idle_ticks={0: 5, 1: 5},
                          step_ema_s=0.01, max_batch=4)
        assert down[:2] == ("down", 1)  # highest idle index retires
        # Bounds: never below min_replicas, never above max_replicas.
        floor = AutoscalePolicy(min_replicas=2, max_replicas=2)
        assert floor.decide(outstanding={0: 99, 1: 99},
                            idle_ticks={0: 99, 1: 99},
                            step_ema_s=1.0, max_batch=1)[0] == "hold"

    def test_fleet_scales_up_then_retires_idle(self, model):
        fleet = ServeFleet(_factory(model), replicas=2, page_size=PAGE)
        fleet.start()
        workload = _sessioned_workload(sessions=2, visits=3)
        frs = [fleet.submit(p, max_new_tokens=n) for p, n in workload]
        # Router-side outstanding is synchronous: 3 per replica now.
        pol = AutoscalePolicy(min_replicas=2, max_replicas=3,
                              scale_up_outstanding=2, idle_ticks_down=3)
        fleet._autoscale = pol
        assert fleet.autoscale_tick() == "up"
        assert set(fleet._workers) == {0, 1, 2}
        # New replica idle, so the backlog signal is gone.
        assert fleet.autoscale_tick() is None
        fleet.drain(timeout_s=120.0)
        for _ in range(2 * pol.idle_ticks_down):
            fleet.autoscale_tick()
        actions = [e["action"] for e in fleet.autoscale_events]
        assert actions == ["up", "down"]
        retired = fleet.autoscale_events[-1]["replica"]
        assert fleet._workers[retired].join(20.0)
        assert sorted(fleet.alive_indices()) == sorted(
            set(fleet._workers) - {retired})
        fleet.close()
        assert all(fr.status == DONE for fr in frs)
