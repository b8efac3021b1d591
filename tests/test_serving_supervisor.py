"""Self-healing fleet: supervisor watchdog + auto-restart + chaos
harness (serving.supervisor/chaos + the router's restart machinery).

The acceptance-critical properties pinned here:

* IDEMPOTENT FENCING — killing/fencing an already-FAILED replica is a
  no-op: no second fence, no double-resubmission of its requests.
* RESTART ROUND-TRIP — a FAILED replica is rebuilt from its retained
  factory, re-warmed, and rejoins HEALTHY serving token-identical
  output; fleet-merged stats stay monotone across the swap (the retired
  engine's counters fold into a ledger instead of vanishing).
* HANG WATCHDOG — a replica whose heartbeat stalls past ``hang_timeout``
  while ``engine.error`` is still None (the failure lazy health checks
  can never see) is fenced and killed; its in-flight work completes on
  survivors token-exact.
* CIRCUIT BREAKER — ``max_restarts`` failed rebuild attempts within the
  window park the replica in CRASH_LOOP; no further attempts until an
  operator ``reset_circuit``; lazy health refresh must NOT flip
  CRASH_LOOP back to FAILED (which would re-arm the breaker).
* PROJECTED-PRESSURE SHED — the gateway 429s on projected KV-page
  demand (admitted + queued vs pool headroom at the observed drain
  rate) with a drain-rate-derived Retry-After, while a cold fleet
  (no drain observed) never sheds.
* CHAOS SOAK — a scripted kill + hang + restart sequence over a mixed
  32-request workload loses and duplicates zero tokens and keeps the
  fleet-merged counters balanced across the restarts.

Chaos faults are keyed on decode ticks (token progress), so they fire
at the same stream position on every run; timing-sensitive scenarios
run on bench's deterministic-sleep model like the gateway tests.
"""

import json
import os
import sys
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402
from accelerate_tpu import generation  # noqa: E402
from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from accelerate_tpu.serving import (  # noqa: E402
    ChaosKilled,
    ChaosSchedule,
    FleetSupervisor,
    GatewayConfig,
    HungReplicaError,
    ReplicaSet,
    ReplicaState,
    RequestStatus,
    ServingEngine,
    ServingGateway,
)
from accelerate_tpu.utils.profiling import CompileWatcher  # noqa: E402

EOS = 7

PROMPTS = [
    np.array([[3, 5, 7, 11, 2]], np.int32),
    np.array([[1, 4, 9]], np.int32),
    np.array([[8, 6, 4, 2, 10, 12, 14]], np.int32),
    np.array([[42]], np.int32),
]


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(use_flash_attention=False)
    m = LlamaForCausalLM(cfg)
    params = m.init_params(jax.random.PRNGKey(0), batch_size=2, seq_len=8)
    return cfg, m, params


@pytest.fixture(scope="module")
def sleepy(tiny):
    cfg, _, params = tiny
    m = bench._sleepy_llama_cls(step_ms=15.0)(cfg)
    return m, params


def _offline(m, params, prompt, n):
    out = generation.generate(m, params, prompt, max_new_tokens=n,
                              eos_token_id=EOS)
    return np.asarray(out)[0, prompt.shape[1]:]


def _assert_matches_offline(got, ref, n):
    got = np.asarray(got)
    assert np.array_equal(got, ref[: len(got)]), (got, ref)
    if len(got) < n:
        assert got[-1] == EOS and np.all(ref[len(got):] == EOS), (got, ref)


def _factory(m, params, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("eos_token_id", EOS)
    return lambda: ServingEngine(m, params, **kw)


def _wait_state(rs, index, state, timeout=120):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if rs.replicas[index].state is state:
            return True
        time.sleep(0.02)
    return rs.replicas[index].state is state


def _wait_dead(engine, timeout=30):
    deadline = time.monotonic() + timeout
    while engine.running and time.monotonic() < deadline:
        time.sleep(0.01)
    return not engine.running


def _get(url, path, timeout=30):
    try:
        with urllib.request.urlopen(url + path, timeout=timeout) as resp:
            return resp.status, resp.read().decode(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


def _post(url, payload, timeout=120):
    req = urllib.request.Request(
        url + "/v1/completions", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


# ---------------------------------------------------------------------
# Heartbeat + chaos primitives (no fleet, fast)
# ---------------------------------------------------------------------
class TestHeartbeatAndChaos:
    def test_heartbeat_advances_and_freeze_stalls_it(self, tiny):
        _, m, params = tiny
        eng = _factory(m, params, max_slots=2)()
        try:
            i0, w0 = eng.heartbeat
            deadline = time.monotonic() + 30
            while eng.heartbeat[0] <= i0 and time.monotonic() < deadline:
                time.sleep(0.01)
            i1, w1 = eng.heartbeat
            assert i1 > i0 and w1 >= w0, "idle run loop must keep beating"
            eng._heartbeat_frozen = True
            time.sleep(0.05)
            frozen = eng.heartbeat
            time.sleep(0.1)
            assert eng.heartbeat == frozen, "frozen heartbeat must not move"
            assert eng.running and eng.error is None  # hung != dead
            eng._heartbeat_frozen = False
            deadline = time.monotonic() + 30
            while eng.heartbeat == frozen and time.monotonic() < deadline:
                time.sleep(0.01)
            assert eng.heartbeat != frozen
        finally:
            eng.shutdown(drain=False)

    def test_chaos_schedule_fires_on_stub_ticks(self):
        class StubFlight:
            def __init__(self):
                self.events = []

            def record(self, kind, **kw):
                self.events.append(kind)

        class StubEngine:
            def __init__(self):
                self.decode_ticks = 0
                self._heartbeat_frozen = False
                self._flight = StubFlight()
                self.killed = None

            def kill(self, error):
                self.killed = error

        # kill: not before its tick, exactly once at/after it.
        eng = StubEngine()
        chaos = ChaosSchedule().kill(at_tick=3)
        chaos.apply(eng)
        assert eng.killed is None and chaos.fired() == []
        eng.decode_ticks = 3
        chaos.apply(eng)
        assert isinstance(eng.killed, ChaosKilled)
        eng.killed = None
        chaos.apply(eng)  # must not re-fire
        assert eng.killed is None and chaos.fired() == ["kill"]

        # hang with a duration freezes then self-heals.
        eng2 = StubEngine()
        chaos2 = ChaosSchedule().hang(at_tick=1, duration_s=0.05)
        eng2.decode_ticks = 1
        chaos2.apply(eng2)
        assert eng2._heartbeat_frozen
        time.sleep(0.08)
        chaos2.apply(eng2)
        assert not eng2._heartbeat_frozen
        assert eng2._flight.events == ["chaos_hang", "chaos_hang_end"]

        # wedge arms the engine's reconcile-stall knob exactly once.
        eng4 = StubEngine()
        eng4._wedge_s = 0.0
        chaos4 = ChaosSchedule().wedge(at_tick=2, duration_s=0.7)
        chaos4.apply(eng4)
        assert eng4._wedge_s == 0.0 and chaos4.fired() == []
        eng4.decode_ticks = 2
        chaos4.apply(eng4)
        assert eng4._wedge_s == 0.7
        eng4._wedge_s = 0.0  # the engine consumes it at its barrier
        chaos4.apply(eng4)  # must not re-arm
        assert eng4._wedge_s == 0.0 and chaos4.fired() == ["wedge"]
        assert eng4._flight.events == ["chaos_wedge"]

        # slow delays only inside its window.
        eng3 = StubEngine()
        chaos3 = ChaosSchedule().slow(from_tick=2, until_tick=4, delay_s=0.04)
        t0 = time.monotonic()
        chaos3.apply(eng3)
        assert time.monotonic() - t0 < 0.02, "must not delay before window"
        eng3.decode_ticks = 2
        t0 = time.monotonic()
        chaos3.apply(eng3)
        assert time.monotonic() - t0 >= 0.04
        eng3.decode_ticks = 4
        t0 = time.monotonic()
        chaos3.apply(eng3)
        assert time.monotonic() - t0 < 0.02, "must not delay past window"

    def test_wedge_stalls_reconcile_then_stream_completes_exact(self, tiny):
        """A wedge genuinely stops the loop inside a reconcile barrier
        (no heartbeats while it sleeps — unlike ``hang``, which only
        freezes the published value), then the engine resumes and the
        stream is bit-identical: a stalled device wait must never skew
        what gets committed."""
        _, m, params = tiny
        chaos = ChaosSchedule().wedge(at_tick=2, duration_s=0.5)
        eng = ServingEngine(m, params, max_slots=2, max_len=64,
                            eos_token_id=EOS, chaos=chaos)
        n = 20
        try:
            ref = _offline(m, params, PROMPTS[0], n)
            r = eng.submit(PROMPTS[0], max_new_tokens=n, ignore_eos=True)
            max_gap, last = 0.0, eng.heartbeat[1]
            deadline = time.monotonic() + 60
            while not r.done and time.monotonic() < deadline:
                hb = eng.heartbeat[1]
                if hb != last:
                    last = hb
                max_gap = max(max_gap, time.monotonic() - last)
                time.sleep(0.005)
            assert r.wait(timeout=60)
            assert np.array_equal(np.asarray(r.tokens), ref[: n])
            assert "wedge" in chaos.fired()
            assert max_gap >= 0.4, (
                f"heartbeat gap {max_gap:.3f}s — a 0.5s wedge must "
                "visibly stall the beat (it is republished only at the "
                "reconcile barrier, after the stalled wait returns)")
            kinds = [e["kind"] for e in eng.flight_recorder.snapshot()]
            assert "chaos_wedge" in kinds
        finally:
            eng.shutdown(drain=False)

    def test_chaos_schedule_validation(self):
        with pytest.raises(ValueError, match="until_tick"):
            ChaosSchedule().slow(from_tick=5, until_tick=5, delay_s=0.01)
        with pytest.raises(ValueError, match="duration_s"):
            ChaosSchedule().wedge(at_tick=3, duration_s=0.0)
        rep = repr(ChaosSchedule().kill(at_tick=8).hang(at_tick=2))
        assert "kill@8" in rep and "hang@2" in rep

    def test_supervisor_ctor_validation(self, tiny):
        _, m, params = tiny
        rs = ReplicaSet([_factory(m, params, max_slots=1, max_len=16)()])
        try:
            with pytest.raises(ValueError, match="hang_timeout"):
                FleetSupervisor(rs, hang_timeout_s=0)
            with pytest.raises(ValueError, match="max_restarts"):
                FleetSupervisor(rs, max_restarts=0)
        finally:
            rs.shutdown(drain=False)


# ---------------------------------------------------------------------
# Fencing idempotence + manual restart round-trip (fast)
# ---------------------------------------------------------------------
class TestFenceAndRestart:
    def test_idempotent_fence_and_restart_round_trip(self, tiny):
        """Satellite regression: killing/fencing an already-FAILED
        replica is a no-op (no double fence, no re-resubmission), and a
        manual restart_replica brings the replica back serving
        token-identical output with monotone fleet-merged stats."""
        _, m, params = tiny
        rs = ReplicaSet.from_factory(_factory(m, params), 2)
        try:
            n = 8
            ref = _offline(m, params, PROMPTS[0], n)
            r = rs.submit(PROMPTS[0], max_new_tokens=n)
            assert r.wait(timeout=120)
            _assert_matches_offline(r.tokens, ref, n)

            rs.kill_replica(0, RuntimeError("die once"))
            assert _wait_dead(rs.replicas[0].engine)
            rs.refresh_health()
            assert rs.replica_states()[0] is ReplicaState.FAILED
            fences = rs.fleet_metrics()["fleet_fences"]
            before = rs.merged_stats().summary()

            # Second kill and a direct _fence on the corpse: both no-ops.
            rs.kill_replica(0, RuntimeError("die twice"))
            rs._fence(rs.replicas[0])
            fm = rs.fleet_metrics()
            assert fm["fleet_fences"] == fences
            assert fm["fleet_failovers"] == 0
            assert rs.replica_states()[0] is ReplicaState.FAILED
            # No phantom resubmissions either.
            assert rs.merged_stats().summary()["requests_submitted"] == \
                before["requests_submitted"]

            new_eng = rs.restart_replica(0)
            assert rs.replica_states()[0] is ReplicaState.HEALTHY
            assert rs.replicas[0].engine is new_eng and new_eng.healthy
            assert rs.replicas[0].restarts == 1
            assert rs.fleet_metrics()["fleet_restarts"] == 1

            # The rebuilt replica serves bit-identical output...
            rs.drain_replica(1)  # force routing onto the rebuilt replica
            r2 = rs.submit(PROMPTS[0], max_new_tokens=n)
            assert r2.wait(timeout=120)
            assert r2.replica_trail == [0]
            _assert_matches_offline(r2.tokens, ref, n)
            # ...and the old engine's counters folded into the ledger:
            # fleet-merged totals stayed monotone across the swap.
            after = rs.merged_stats().summary()
            for key in ("requests_submitted", "requests_completed",
                        "decode_tokens"):
                assert after[key] >= before[key], (key, before, after)
            assert after["requests_completed"] == \
                before["requests_completed"] + 1
        finally:
            rs.shutdown(drain=False)

    def test_restart_requires_failed_state_and_factory(self, tiny):
        _, m, params = tiny
        make = _factory(m, params, max_slots=1, max_len=16)
        rs = ReplicaSet([make()])  # direct list: no factories retained
        try:
            with pytest.raises(RuntimeError, match="factory"):
                rs.restart_replica(0)
        finally:
            rs.shutdown(drain=False)
        rs2 = ReplicaSet.from_factory(make, 1)
        try:
            with pytest.raises(RuntimeError):
                rs2.restart_replica(0)  # still HEALTHY
        finally:
            rs2.shutdown(drain=False)

    def test_circuit_breaker_parks_flapping_replica(self, tiny):
        """N failed rebuilds within the window -> CRASH_LOOP, zero
        further attempts, lazy health refresh does NOT re-arm the
        breaker, and an operator reset_circuit makes it eligible
        again."""
        _, m, params = tiny
        make = _factory(m, params, max_slots=1, max_len=16)
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] > 1:  # first call builds the fleet; rebuilds fail
                raise RuntimeError(f"factory boom #{calls['n']}")
            return make()

        rs = ReplicaSet.from_factory(flaky, 1)
        sup = FleetSupervisor(rs, restart_backoff_s=0.001,
                              restart_backoff_max_s=0.002,
                              max_restarts=3, restart_window_s=60.0)
        try:
            rs.kill_replica(0, RuntimeError("die"))
            assert _wait_dead(rs.replicas[0].engine)
            deadline = time.monotonic() + 60
            while (rs.replica_states()[0] is not ReplicaState.CRASH_LOOP
                   and time.monotonic() < deadline):
                sup.check_once()
                time.sleep(0.01)
            assert rs.replica_states()[0] is ReplicaState.CRASH_LOOP
            assert sup.restarts_failed == 3 and sup.breaker_trips == 1
            kinds = [e["kind"] for e in sup.events()]
            assert kinds.count("restart_failed") == 3
            assert "circuit_open" in kinds

            # Open breaker: further scans attempt nothing, and the lazy
            # health pass must not demote CRASH_LOOP back to FAILED.
            attempts = calls["n"]
            sup.check_once()
            rs.refresh_health()
            sup.check_once()
            assert calls["n"] == attempts
            assert rs.replica_states()[0] is ReplicaState.CRASH_LOOP
            fm = rs.fleet_metrics()
            assert fm["replicas_crash_loop"] == 1
            assert fm["fleet_crash_loops"] == 1
            assert not rs.ready  # nothing healthy remains

            rs.reset_circuit(0)
            assert rs.replica_states()[0] is ReplicaState.FAILED
        finally:
            rs.shutdown(drain=False)

    def test_projected_deficit_and_drain_rate_units(self, tiny):
        _, m, params = tiny
        eng = ServingEngine(m, params, max_slots=2, max_len=32,
                            eos_token_id=EOS, prefill_chunk=8, page_size=8)
        try:
            free = eng._pool.free_pages
            page = eng._page
            assert eng.projected_page_deficit(free * page) == 0
            assert eng.projected_page_deficit((free + 3) * page) == 3
            assert eng.projected_page_deficit(0) == 0
            assert eng.page_drain_rate() == 0.0  # nothing observed yet
        finally:
            eng.shutdown(drain=False)


# ---------------------------------------------------------------------
# End-to-end self-healing (slow: sleepy model / soak workloads)
# ---------------------------------------------------------------------
class TestSelfHealing:
    @pytest.mark.slow
    def test_hang_watchdog_fences_and_work_completes_on_survivor(
            self, sleepy):
        """The failure lazy health can never see: a replica that stops
        beating while ``engine.error`` stays None. The watchdog must
        fence it within hang_timeout, its in-flight stream must finish
        on the survivor token-exact, and the replica must heal."""
        m, params = sleepy
        make = _factory(m, params, max_slots=2)
        n = 30
        ref = _offline(m, params, PROMPTS[0], n)
        chaos = ChaosSchedule().hang(at_tick=3)
        rs = ReplicaSet([ServingEngine(m, params, max_slots=2, max_len=64,
                                       eos_token_id=EOS, chaos=chaos),
                         make()],
                        factories=[make, make])
        try:
            with FleetSupervisor(rs, hang_timeout_s=0.6,
                                 poll_interval_s=0.02,
                                 restart_backoff_s=0.05) as sup:
                # Pin the victim stream to the chaos replica by filling
                # the clean one first.
                ballast = [rs.submit(PROMPTS[1], max_new_tokens=60,
                                     ignore_eos=True) for _ in range(2)]
                deadline = time.monotonic() + 60
                while (ballast[0].replica_trail[0] == 0
                       and time.monotonic() < deadline):
                    time.sleep(0.005)
                r = rs.submit(PROMPTS[0], max_new_tokens=n, ignore_eos=True)
                deadline = time.monotonic() + 60
                while sup.hang_fences < 1 and time.monotonic() < deadline:
                    time.sleep(0.02)
                assert sup.hang_fences >= 1, "watchdog never fenced"
                assert "hang" in chaos.fired()
                assert rs.fleet_metrics()["fleet_hang_fences"] >= 1
                assert r.wait(timeout=120)
                assert r.status is RequestStatus.COMPLETED
                assert np.array_equal(np.asarray(r.tokens), ref)
                if r.failovers:  # stream was live on the hung replica
                    assert r.replica_trail[0] == 0
                # The fence carries the liveness error, not a fake fault
                # (reports stringify the error for the postmortem dump).
                reports = rs.failover_reports
                assert any("HungReplicaError" in str(rep["error"])
                           for rep in reports), reports
                # ...and the watchdogged replica heals without help.
                assert _wait_state(rs, 0, ReplicaState.HEALTHY)
                kinds = [e["kind"] for e in sup.events()]
                assert "hang_fence" in kinds and "restart" in kinds
                for b in ballast:
                    b.wait(timeout=120)
        finally:
            rs.shutdown(drain=False)

    @pytest.mark.slow
    def test_wedged_dispatch_is_fenced_within_hang_timeout(self, sleepy):
        """A genuinely wedged compiled call: the replica sleeps inside
        the reconcile barrier of a DISPATCHED tick, so no heartbeats are
        published at all (the async runtime republishes them exactly at
        that barrier). The watchdog must fence on liveness within
        ``hang_timeout_s`` — well before the wedge clears — and the
        victim stream must finish on the survivor token-exact."""
        m, params = sleepy
        make = _factory(m, params, max_slots=2)
        n = 30
        ref = _offline(m, params, PROMPTS[0], n)
        chaos = ChaosSchedule().wedge(at_tick=3, duration_s=2.5)
        rs = ReplicaSet([ServingEngine(m, params, max_slots=2, max_len=64,
                                       eos_token_id=EOS, chaos=chaos),
                         make()],
                        factories=[make, make])
        try:
            with FleetSupervisor(rs, hang_timeout_s=0.6,
                                 poll_interval_s=0.02,
                                 restart_backoff_s=0.05) as sup:
                # Pin the victim stream to the chaos replica by filling
                # the clean one first.
                ballast = [rs.submit(PROMPTS[1], max_new_tokens=60,
                                     ignore_eos=True) for _ in range(2)]
                deadline = time.monotonic() + 60
                while (ballast[0].replica_trail[0] == 0
                       and time.monotonic() < deadline):
                    time.sleep(0.005)
                r = rs.submit(PROMPTS[0], max_new_tokens=n, ignore_eos=True)
                t0 = time.monotonic()
                deadline = t0 + 60
                while sup.hang_fences < 1 and time.monotonic() < deadline:
                    time.sleep(0.02)
                assert sup.hang_fences >= 1, "watchdog never fenced"
                assert "wedge" in chaos.fired()
                assert time.monotonic() - t0 < 2.5, (
                    "the fence must come from the stalled heartbeat, not "
                    "from waiting out the wedge")
                assert r.wait(timeout=120)
                assert r.status is RequestStatus.COMPLETED
                assert np.array_equal(np.asarray(r.tokens), ref)
                reports = rs.failover_reports
                assert any("HungReplicaError" in str(rep["error"])
                           for rep in reports), reports
                # The wedge clears on its own; the restart machinery then
                # brings the killed replica back.
                assert _wait_state(rs, 0, ReplicaState.HEALTHY)
                kinds = [e["kind"] for e in sup.events()]
                assert "hang_fence" in kinds and "restart" in kinds
                for b in ballast:
                    b.wait(timeout=120)
        finally:
            rs.shutdown(drain=False)

    @pytest.mark.slow
    def test_kill_mid_prefilling_resumes_token_exact(self, sleepy):
        """Satellite: the victim dies while a chunked prefill is still
        streaming into KV (PREFILLING, zero tokens emitted). The
        survivor must re-prefill from scratch and produce the exact
        uninterrupted stream."""
        m, params = sleepy
        make = _factory(m, params, max_slots=2, prefill_chunk=8,
                        max_len=128)
        rs = ReplicaSet.from_factory(make, 2)
        try:
            n = 10
            prompt = np.arange(1, 49, dtype=np.int32)[None, :]  # 6 chunks
            ref = _offline(m, params, prompt, n)
            r = rs.submit(prompt, max_new_tokens=n)
            deadline = time.monotonic() + 60
            caught_prefilling = False
            while time.monotonic() < deadline:
                # The fleet handle only tracks terminal states; the
                # chunked-prefill phase lives on the inner flight.
                inner = r._inner
                if (inner is not None
                        and inner.status is RequestStatus.PREFILLING):
                    caught_prefilling = True
                    break
                if r.tokens or r.done:
                    break
                time.sleep(0.0005)
            assert caught_prefilling, "never observed PREFILLING backlog"
            rs.kill_replica(r.replica_trail[0])
            assert r.wait(timeout=120)
            assert r.status is RequestStatus.COMPLETED
            assert r.failovers == 1
            _assert_matches_offline(r.tokens, ref, n)
        finally:
            rs.shutdown(drain=False)

    @pytest.mark.slow
    def test_gateway_e2e_kill_heals_with_metrics_and_zero_compiles(
            self, sleepy):
        """The acceptance test: with the supervisor on, killing a
        replica mid-stream yields (a) token-identical output, (b) the
        replica back HEALTHY with no operator action, (c) fence+restart
        events in the flight recorder and /metrics — and the fence +
        failover window itself triggers ZERO new XLA compiles (the
        survivor serves the resumed stream entirely from its warm
        executables)."""
        m, params = sleepy
        make = _factory(m, params, max_slots=3)
        n = 16
        chaos = ChaosSchedule().kill(at_tick=6)
        rs = ReplicaSet([ServingEngine(m, params, max_slots=3, max_len=64,
                                       eos_token_id=EOS, chaos=chaos),
                         make()],
                        factories=[make, make])
        refs = [_offline(m, params, p, n) for p in PROMPTS]
        sup = FleetSupervisor(rs, hang_timeout_s=5.0, poll_interval_s=0.02,
                              restart_backoff_s=0.05)
        try:
            with ServingGateway(rs, config=GatewayConfig(port=0)) as gw:
                # Phase 1 — fence + failover with the compile listener
                # pinned. The supervisor is NOT running yet so the only
                # XLA activity in this window is the failover itself
                # (compile events are process-global; a concurrent
                # rebuild warmup would pollute the pin).
                watcher = CompileWatcher().start()
                reqs = [rs.submit(p, max_new_tokens=n) for p in PROMPTS]
                for r in reqs:
                    assert r.wait(timeout=120)
                failed_over = [r for r in reqs if r.failovers]
                assert "kill" in chaos.fired()
                assert failed_over, "chaos kill hit no live stream"
                # (a) token-identical across the kill.
                for r, ref in zip(reqs, refs):
                    assert r.status is RequestStatus.COMPLETED
                    _assert_matches_offline(r.tokens, ref, n)
                # The fence + token-exact failover compiled nothing new:
                # the survivor served the resumed streams entirely from
                # its warm executables.
                watcher.stop()
                assert watcher.summary()["compile_events"] == 0
                assert "ChaosKilled" in str(rs.failover_reports[-1]["error"])
                # Phase 2 — (b) healed without operator action once the
                # supervisor runs.
                sup.start()
                assert _wait_state(rs, 0, ReplicaState.HEALTHY)
                code, body, _ = _get(gw.url, "/readyz")
                assert (code, body) == (200, "ready\n")
                # Post-rejoin steady state: the rebuilt replica serves
                # from ITS warm executables — zero compiles again.
                steady = CompileWatcher().start()
                rs.drain_replica(1)
                r2 = rs.submit(PROMPTS[0], max_new_tokens=n)
                assert r2.wait(timeout=120)
                assert r2.replica_trail == [0]
                _assert_matches_offline(r2.tokens, refs[0], n)
                steady.stop()
                assert steady.summary()["compile_events"] == 0
                # (c) events in the recorder and /metrics.
                kinds = [e["kind"] for e in sup.events()]
                assert "restart" in kinds
                code, text, _ = _get(gw.url, "/metrics")
                assert code == 200
                metrics = {line.split()[0]: line.split()[1]
                           for line in text.splitlines()
                           if line and not line.startswith("#")
                           and "{" not in line}
                assert float(
                    metrics["accelerate_tpu_serving_fleet_restarts"]) >= 1
                assert float(
                    metrics["accelerate_tpu_serving_fleet_fences"]) >= 1
                assert "accelerate_tpu_serving_fleet_hang_fences" in metrics
                assert "accelerate_tpu_serving_replicas_crash_loop" in metrics
        finally:
            sup.stop()
            rs.shutdown(drain=False)

    @pytest.mark.slow
    def test_pressure_shed_429_with_drain_rate_retry_after(self, tiny):
        """Satellite: the gateway sheds on PROJECTED page pressure — a
        request whose worst-case page demand (on top of admitted +
        queued work) cannot be covered within shed_wait_s at the
        observed drain rate gets 429 with a drain-derived Retry-After —
        while a cold pool (no drain observed) never sheds."""
        _, m, params = tiny
        # 20 pages x 8 tokens = 160-token pool for 2 slots of 128: the
        # pool is oversubscribed, so projected demand CAN outrun it.
        eng = ServingEngine(m, params, max_slots=2, max_len=128,
                            max_queued=64, eos_token_id=EOS,
                            prefill_chunk=8, page_size=8, max_pages=20)
        rs = ReplicaSet([eng])
        cfg = GatewayConfig(port=0, shed_wait_s=0.05, retry_after_s=1.0)
        big = {"prompt": [1, 2, 3], "max_new_tokens": 120}  # 16 pages
        try:
            with ServingGateway(rs, config=cfg) as gw:
                # COLD: headroom still covers demand -> admit normally.
                code, _, _ = _post(gw.url, dict(big, max_new_tokens=8))
                assert code == 200
                # Observe drain: a few short completions free their pages.
                for _ in range(3):
                    code, _, _ = _post(gw.url, {"prompt": [5, 6],
                                                "max_new_tokens": 4})
                    assert code == 200
                deadline = time.monotonic() + 30
                while (rs.page_drain_rate() <= 0.0
                       and time.monotonic() < deadline):
                    time.sleep(0.01)
                assert rs.page_drain_rate() > 0.0
                # Saturate the pool with ignore_eos blockers...
                blockers = [rs.submit(PROMPTS[i % len(PROMPTS)],
                                      max_new_tokens=100, ignore_eos=True)
                            for i in range(2)]
                deadline = time.monotonic() + 30
                while (eng.projected_page_deficit(123) <= 0
                       and time.monotonic() < deadline):
                    time.sleep(0.01)
                assert eng.projected_page_deficit(123) > 0
                # ...so the big request's projected demand now exceeds
                # headroom by far more than the drain covers: 429.
                code, payload, headers = _post(gw.url, big)
                assert code == 429, payload
                assert "pressure" in payload["error"]
                retry = float(headers["Retry-After"])
                assert cfg.retry_after_s <= retry <= cfg.retry_after_max_s
                code, text, _ = _get(gw.url, "/metrics")
                assert "accelerate_tpu_gateway_pressure_sheds 1" in text
                for b in blockers:
                    b.wait(timeout=180)
        finally:
            rs.shutdown(drain=False)

    @pytest.mark.slow
    def test_chaos_soak_mixed_workload_exact_and_balanced(self, tiny):
        """Satellite soak: scripted kill + hang + auto-restart while a
        32-request mixed workload runs. Every request completes with
        its exact uninterrupted token stream (zero dup/lost tokens) and
        the fleet-merged counters stay balanced and monotone across the
        restarts."""
        _, m, params = tiny
        make = _factory(m, params, max_slots=3, max_len=96)
        chaos_kill = ChaosSchedule().kill(at_tick=8)
        chaos_hang = ChaosSchedule().hang(at_tick=12)
        rs = ReplicaSet(
            [ServingEngine(m, params, max_slots=3, max_len=96,
                           eos_token_id=EOS, chaos=chaos_kill),
             ServingEngine(m, params, max_slots=3, max_len=96,
                           eos_token_id=EOS, chaos=chaos_hang),
             make()],
            factories=[make, make, make])
        N = 32
        prompts = [PROMPTS[i % len(PROMPTS)] for i in range(N)]
        lengths = [8 + (i % 3) * 8 for i in range(N)]  # 8/16/24 mixed
        refs = [_offline(m, params, p, n) for p, n in zip(prompts, lengths)]
        try:
            with FleetSupervisor(rs, hang_timeout_s=0.8,
                                 poll_interval_s=0.02,
                                 restart_backoff_s=0.05) as sup:
                before = rs.merged_stats().summary()
                reqs = [rs.submit(p, max_new_tokens=n)
                        for p, n in zip(prompts, lengths)]
                for r in reqs:
                    assert r.wait(timeout=300)
                # Zero duplicated, zero lost tokens anywhere.
                for i, (r, ref, n) in enumerate(zip(reqs, refs, lengths)):
                    assert r.status is RequestStatus.COMPLETED, (i, r)
                    _assert_matches_offline(r.tokens, ref, n)
                assert "kill" in chaos_kill.fired()
                assert "hang" in chaos_hang.fired()
                # Both chaos replicas heal. The hung replica's heartbeat
                # stays frozen even after the workload drains, so the
                # watchdog fences it whenever the timeout elapses — wait
                # for both recoveries, not just the kill's.
                deadline = time.monotonic() + 120
                while ((sup.hang_fences < 1 or sup.restarts < 2)
                       and time.monotonic() < deadline):
                    time.sleep(0.05)
                assert sup.hang_fences >= 1, sup.events()
                assert sup.restarts >= 2, sup.events()
                assert _wait_state(rs, 0, ReplicaState.HEALTHY)
                assert _wait_state(rs, 1, ReplicaState.HEALTHY)
                # Fleet totals stay consistent across the restarts: the
                # ledger keeps dead engines' counters, so merged stats
                # are monotone and balanced.
                after = rs.merged_stats().summary()
                for key in ("requests_submitted", "requests_completed",
                            "requests_failed", "decode_tokens"):
                    assert after[key] >= before[key], key
                fm = rs.fleet_metrics()
                assert fm["fleet_submitted"] == N
                assert fm["fleet_restarts"] >= 2
                assert fm["fleet_hang_fences"] >= 1
                assert sup.restarts >= 2
                # Engine-level balance: every submission reached exactly
                # one terminal state; each failover is one engine-level
                # FAILED retire plus one resubmission on a survivor.
                assert after["requests_completed"] == \
                    before["requests_completed"] + N
                assert after["requests_submitted"] == (
                    before["requests_submitted"] + N + fm["fleet_failovers"])
                assert (after["requests_failed"] - before["requests_failed"]
                        == fm["fleet_failovers"])
        finally:
            rs.shutdown(drain=False)
