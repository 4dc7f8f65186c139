"""The fault layer: wire hardening, deterministic injection, recovery.

The contract under test (ISSUE 10 / CONTRIBUTING "fault injection"):
zero-fault paths stay bit-identical to the historical byte streams;
every injected fault is survived — retried paths produce bit-identical
outputs, degraded paths are explicitly flagged; and the whole schedule
is a deterministic function of ``(seed, rid, hop/stage, attempt)``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.netsim.channel import Channel
from repro.netsim.protocols import RetryBudgetExceeded, simulate_tcp
from repro.runtime import wire as W
from repro.runtime.engine import SplitRuntime, TailServer
from repro.runtime.faults import (FaultPlan, RecoveryExhausted,
                                  RecoveryPolicy, downgrade_ladder)
from repro.runtime.partition import make_partition

CUT = 3
CH = Channel(latency_s=0.005, capacity_bps=50e6, interface_bps=100e6,
             loss_rate=0.02, seed=3)


@pytest.fixture(scope="module")
def split_setup(vgg_small, toy_data):
    model, params = vgg_small
    xs, _ = toy_data
    return model, params, jnp.asarray(xs[:4])


# ---------------------------------------------------------------- wire ----
class TestWireHardening:
    def _frame(self, checksum):
        rng = np.random.default_rng(0)
        f = rng.normal(size=(2, 4, 8)).astype(np.float32)
        pkt = W.encode_activation(jnp.asarray(f))
        return pkt, W.to_bytes(pkt, checksum=checksum)

    def test_default_framing_unchanged(self):
        """checksum=False is the historical SEI1 layout, byte for byte."""
        pkt, buf = self._frame(False)
        assert buf[:4] == b"SEI1"
        assert pkt.nbytes == len(buf)
        # hand-assemble the v1 frame: magic|kind|ndim|dims|payload|scales
        import struct
        head = (b"SEI1" + struct.pack("<BB", 1, 3)
                + struct.pack("<3I", *pkt.shape))
        assert buf == head + pkt.data.tobytes() + pkt.scales.tobytes()

    def test_checksummed_frame_roundtrips(self):
        pkt, buf = self._frame(True)
        assert buf[:4] == b"SEI2"
        assert len(buf) == pkt.nbytes + 8     # pkt built v1: +8 CRC bytes
        out = W.from_bytes(buf)
        assert out.checksum
        assert np.array_equal(out.data, pkt.data)
        assert np.array_equal(out.scales, pkt.scales)
        # SEI2 payload bytes are the SEI1 payload bytes, just re-headed
        v1 = W.to_bytes(pkt)
        head = 6 + 4 * len(pkt.shape)
        assert buf[head + 8:] == v1[head:]

    @pytest.mark.parametrize("checksum", [False, True])
    def test_truncation_at_every_field_boundary(self, checksum):
        """Any prefix of a valid frame raises WireError, never a raw
        struct/IndexError or a garbage parse."""
        _, buf = self._frame(checksum)
        boundaries = {0, 1, 3, 4, 5, 6, 9, 13, 17}   # magic/kind/ndim/dims
        if checksum:
            boundaries |= {18, 21, 25}               # inside the CRC pair
        boundaries |= {len(buf) // 2, len(buf) - 5, len(buf) - 1}
        for cut in sorted(boundaries):
            with pytest.raises(W.WireError):
                W.from_bytes(buf[:cut])

    def test_crc_detects_payload_and_scale_flips(self):
        _, buf = self._frame(True)
        header_end = 6 + 4 * buf[5] + 8
        for off in (header_end, len(buf) - 2):
            bad = bytearray(buf)
            bad[off] ^= 0xFF
            with pytest.raises(W.WireError, match="CRC mismatch"):
                W.from_bytes(bytes(bad))

    def test_unknown_kind_id(self):
        _, buf = self._frame(False)
        bad = bytearray(buf)
        bad[4] = 7
        with pytest.raises(W.WireError, match="kind id 7"):
            W.from_bytes(bytes(bad))

    def test_wire_error_is_value_error_and_magic_msg(self):
        assert issubclass(W.WireError, ValueError)
        with pytest.raises(ValueError, match="magic"):
            W.from_bytes(b"NOPE" + b"\x00" * 16)


# ----------------------------------------------------------- fault plan ----
class TestFaultPlan:
    def test_schedule_is_deterministic_and_order_free(self):
        plan = FaultPlan(seed=11, drop_rate=0.3, corrupt_rate=0.2,
                         straggle_rate=0.1)
        sched = plan.transfer_schedule(rid=5, hop=0, n=6)
        # same draw, any order, fresh instance: identical
        again = FaultPlan(seed=11, drop_rate=0.3, corrupt_rate=0.2,
                          straggle_rate=0.1)
        assert sched == tuple(again.transfer_fault(5, 0, a)
                              for a in range(6))
        assert sched == tuple(again.transfer_fault(5, 0, a)
                              for a in reversed(range(6)))[::-1]
        # a different seed moves the schedule
        other = FaultPlan(seed=12, drop_rate=0.3, corrupt_rate=0.2,
                          straggle_rate=0.1)
        assert any(other.transfer_schedule(5, 0, 32)
                   != plan.transfer_schedule(5, 0, 32)
                   for _ in [0])

    def test_max_consecutive_bounds_every_burst(self):
        plan = FaultPlan(seed=0, drop_rate=1.0, stage_fault_rate=1.0,
                         max_consecutive=4)
        assert plan.transfer_fault(0, 0, 4) is None
        assert not plan.stage_fault(0, 0, 4)
        assert plan.transfer_fault(0, 0, 3) == "drop"

    def test_blackout_windows(self):
        plan = FaultPlan(blackouts=((0.1, 0.2), (0.5, 0.6)))
        assert plan.blackout_at(0.15) and not plan.blackout_at(0.3)
        assert plan.blackout_end(0.15) == 0.2
        assert plan.blackout_end(0.3) == 0.3
        with pytest.raises(ValueError, match="empty"):
            FaultPlan(blackouts=((0.2, 0.1),))

    def test_corrupt_bytes_deterministic_and_past_lo(self):
        plan = FaultPlan(seed=3)
        buf = bytes(range(64))
        a = plan.corrupt_bytes(buf, 1, 0, 2, lo=16)
        assert a == plan.corrupt_bytes(buf, 1, 0, 2, lo=16)
        assert a != buf and a[:16] == buf[:16]

    def test_recovery_policy_timeout_tracks_channel_rto(self):
        pol = RecoveryPolicy()
        rto = 2 * (2 * CH.latency_s) + CH.serialization_s(1500) + 1e-6
        assert pol.rto_s(CH) == pytest.approx(rto)
        assert pol.timeout_s(CH, 3000) == pytest.approx(
            rto + CH.serialization_s(3000))
        assert pol.timeout_s(None, 3000) == pol.default_timeout_s

    def test_backoff_caps_and_jitters_deterministically(self):
        pol = RecoveryPolicy(base_backoff_s=0.01, backoff_mult=2.0,
                             backoff_cap_s=0.05, jitter=0.1)
        b = [pol.backoff_s(a, seed=0, rid=0, hop=0) for a in range(8)]
        assert b == [pol.backoff_s(a, seed=0, rid=0, hop=0)
                     for a in range(8)]
        assert all(x <= 0.05 * 1.1 + 1e-12 for x in b)
        assert b[1] > b[0]

    def test_downgrade_ladders(self):
        assert downgrade_ladder("ae8") == ("ae8", "int8", "f32")
        assert downgrade_ladder("int8") == ("int8", "f32")
        assert downgrade_ladder("f32") == ("f32",)


# ------------------------------------------------------------- recovery ----
class TestRecovery:
    def test_drops_retry_to_bit_identical_logits(self, split_setup):
        model, params, x = split_setup
        base = SplitRuntime(model, params, CUT, channel=CH).infer(x, iters=1)
        plan = FaultPlan(seed=7, drop_rate=0.5)
        rt = SplitRuntime(model, params, CUT, channel=CH, faults=plan)
        r = rt.infer(x, iters=1, rid=0)
        rec = r.meta["recovery"]
        assert rec["faults"]["drop"] > 0 and rec["retries"] > 0
        assert not r.meta["degraded"] and not r.meta["local_fallback"]
        # the retried path delivered the SAME payload: logits identical
        assert np.array_equal(base.logits, r.logits)
        # retries are priced: timeouts + backoff pushed transfer_s up
        assert r.transfer_s > base.transfer_s
        assert rec["backoff_s"] > 0

    def test_corruption_detected_then_downgraded(self, split_setup):
        model, params, x = split_setup
        plan = FaultPlan(seed=1, corrupt_rate=0.95, max_consecutive=10)
        rt = SplitRuntime(model, params, CUT, channel=CH, faults=plan,
                          recovery=RecoveryPolicy(downgrade_after=2,
                                                  max_attempts=12))
        r = rt.infer(x, iters=1, rid=0)
        rec = r.meta["recovery"]
        assert rec["faults"]["corrupt"] >= 2
        assert rec["downgrades"] and r.meta["degraded"]
        assert rec["downgrades"][0]["to"] in ("int8", "f32")
        # every corrupted frame was *detected* (logged WireError), and
        # the run still completed with sane logits
        assert all(e["event"] == "corrupt" for e in rec["log"])
        assert np.isfinite(r.logits).all()

    def test_blackout_falls_back_locally(self, split_setup):
        model, params, x = split_setup
        plan = FaultPlan(seed=2, blackouts=((0.0, 1e9),))
        rt = SplitRuntime(model, params, CUT, channel=CH, faults=plan,
                          recovery=RecoveryPolicy(max_attempts=3))
        r = rt.infer(x, iters=1, rid=0)
        assert r.meta["local_fallback"] and r.meta["degraded"]
        assert r.meta["recovery"]["faults"]["blackout"] == 3
        assert not r.hops[0]["delivered"]
        # local fallback skips the codec: logits match the unsplit model
        np.testing.assert_allclose(r.logits, rt.reference(x),
                                   rtol=1e-5, atol=1e-5)

    def test_deadline_budget_escalates(self, split_setup):
        model, params, x = split_setup
        plan = FaultPlan(seed=4, drop_rate=1.0, max_consecutive=100)
        rt = SplitRuntime(model, params, CUT, channel=CH, faults=plan,
                          recovery=RecoveryPolicy(deadline_s=0.05,
                                                  max_attempts=100))
        r = rt.infer(x, iters=1, rid=0)
        assert r.meta["local_fallback"]

    def test_exhaustion_without_fallback_raises_typed(self, split_setup):
        model, params, x = split_setup
        plan = FaultPlan(seed=4, drop_rate=1.0, max_consecutive=100)
        rt = SplitRuntime(model, params, CUT, channel=CH, faults=plan,
                          recovery=RecoveryPolicy(max_attempts=3,
                                                  local_fallback=False))
        with pytest.raises(RecoveryExhausted):
            rt.infer(x, iters=1, rid=0)

    def test_stage_faults_retried_and_priced(self, split_setup):
        model, params, x = split_setup
        plan = FaultPlan(seed=3, stage_fault_rate=0.6, max_consecutive=4)
        rt = SplitRuntime(model, params, CUT, faults=plan)
        r = rt.infer(x, iters=1, rid=1)
        base = SplitRuntime(model, params, CUT).infer(x, iters=1)
        assert r.meta["recovery"]["faults"]["stage"] > 0
        assert np.array_equal(base.logits, r.logits)

    def test_all_requests_complete_under_chaos(self, split_setup):
        """The acceptance bar: 100% completion under mixed faults."""
        model, params, x = split_setup
        plan = FaultPlan(seed=5, drop_rate=0.25, corrupt_rate=0.2,
                         straggle_rate=0.1, stage_fault_rate=0.1,
                         blackouts=((0.02, 0.06),))
        rt = SplitRuntime(model, params, CUT, channel=CH, faults=plan,
                          recovery=RecoveryPolicy(deadline_s=2.0))
        base = SplitRuntime(model, params, CUT, channel=CH).infer(x, iters=1)
        done = 0
        for rid in range(12):
            r = rt.infer(x, iters=1, rid=rid)
            assert np.isfinite(r.logits).all()
            assert r.meta["recovery"]["t_virtual_s"] <= 2.0 + 1.0  # budget+legs
            if not r.meta["degraded"]:
                assert np.array_equal(base.logits, r.logits)
            done += 1
        assert done == 12

    def test_trace_reconciles_with_total(self, split_setup):
        model, params, x = split_setup
        plan = FaultPlan(seed=7, drop_rate=0.5, corrupt_rate=0.2)
        r = SplitRuntime(model, params, CUT, channel=CH,
                         faults=plan).infer(x, iters=1, rid=0)
        assert (r.trace.t1 - r.trace.t0) == pytest.approx(r.total_s,
                                                          rel=1e-9)
        names = [c.name for h in r.hops for c in [] ] # noqa: placeholder
        events = r.hops[0]["events"]
        assert sum(d for _, b, d in events if b == "encode") == \
            pytest.approx(r.encode_s)
        assert sum(d for _, b, d in events if b == "transfer") == \
            pytest.approx(r.transfer_s)

    def test_fault_counters_reach_obs(self, split_setup):
        from repro.obs import Recorder
        model, params, x = split_setup
        rec = Recorder()
        plan = FaultPlan(seed=7, drop_rate=0.5)
        rt = SplitRuntime(model, params, CUT, channel=CH, faults=plan,
                          obs=rec)
        rt.infer(x, iters=1, rid=0)
        rep = rec.report()
        counters = rep.counters()
        assert counters.get("runtime.fault.drop", 0) > 0
        assert counters.get("runtime.retry.attempts", 0) > 0
        assert counters.get("runtime.retry.timeouts", 0) > 0


# ------------------------------------------------------------ tail server ----
class TestTailServerFaults:
    def test_rejects_corrupted_frames(self, split_setup):
        model, params, x = split_setup
        part = make_partition(model, params, CUT, None)
        plan = FaultPlan(seed=0)
        srv = TailServer(part, n_slots=2, client_batch=int(x.shape[0]),
                         faults=plan)
        f = part.head(x)
        good = W.to_bytes(W.encode_activation(f), checksum=True)
        bad = plan.corrupt_bytes(good, 0, 0, 0, lo=6 + 4 * good[5] + 8)
        assert srv.submit(0, good) is True
        assert srv.submit(1, bad) is False
        assert srv.n_rejected == 1 and srv.rejected == [1]
        out = srv.drain()
        assert set(out) == {0}

    def test_blackout_step_serves_nothing(self, split_setup):
        model, params, x = split_setup
        part = make_partition(model, params, CUT, None)
        plan = FaultPlan(blackouts=((1.0, 2.0),))
        srv = TailServer(part, n_slots=2, client_batch=int(x.shape[0]),
                         faults=plan)
        f = part.head(x)
        srv.submit(0, W.to_bytes(W.encode_activation(f)))
        assert srv.step(now=1.5) == {}
        assert srv.n_blackout_steps == 1
        assert set(srv.step(now=2.5)) == {0}


# ---------------------------------------------------- netsim / planner ----
class TestRetryBudget:
    def test_typed_and_contextual(self):
        ch = Channel(latency_s=1e-4, capacity_bps=1e9, interface_bps=1e9,
                     loss_rate=0.999, seed=0)
        with pytest.raises(RetryBudgetExceeded) as ei:
            simulate_tcp(1500 * 4, ch, max_rounds=3)
        assert isinstance(ei.value, RuntimeError)
        assert ei.value.loss_rate == 0.999
        assert ei.value.rounds > 3

    def test_measure_flow_reports_retries(self, vgg_small, toy_data):
        from repro.core.scenarios import Scenario
        from repro.core.split import SplitPlan
        from repro.netsim.simulator import NetworkConfig, measure_flow
        model, params = vgg_small
        sc = Scenario("SC", SplitPlan(CUT))
        lossy = Channel(latency_s=0.002, capacity_bps=100e6,
                        interface_bps=100e6, loss_rate=0.3, seed=1)
        flow = measure_flow(sc, NetworkConfig("tcp", lossy), model, params,
                            16 * 16 * 3 * 4, n_frames=8)
        assert "retries" in flow and len(flow["retries"]) == 8
        assert all(r >= 0 for r in flow["retries"])
        assert any(r > 0 for r in flow["retries"])   # 30% loss resends

    def test_planner_counts_infeasible_legs(self, vgg_small):
        from repro.fleet.planner import DeploymentPlanner, SearchSpace
        from repro.fleet.traffic import DeviceClass, generate_trace
        from repro.models.vgg import feature_index
        model, params = vgg_small
        fi = feature_index(model)
        # a link so lossy every TCP frame blows the retry budget
        dead = Channel(1e-3, 1e6, 1e6, loss_rate=0.995, seed=0)
        dev = DeviceClass.make("mcu", dead)
        planner = DeploymentPlanner(
            model, params, cs_curve=np.linspace(1.0, 0.2, len(fi)),
            layer_idx=fi, accuracy_fn=lambda s, n: 0.9,
            input_bytes=16 * 16 * 3 * 4, n_frames=2)
        legal = set(model.cut_points())
        space = SearchSpace(split_points=tuple(sp for sp in fi
                                               if sp in legal)[:2],
                            protocols=("tcp",), batch_sizes=(1,),
                            replica_counts=(1,), top_k_splits=2,
                            include_rc=False, include_lc=True)
        trace = generate_trace([dev], 50, 50.0, seed=0)
        points = planner.search(trace, [dev], space)   # must not raise
        # infeasible legs were skipped + counted, not a crash, and no
        # point was priced on the budget-blowing leg
        assert planner.n_infeasible_legs > 0
        assert all(np.isfinite(p.p99_s) for p in points)


# ------------------------------------------------------------ controller ----
class TestControllerFaultTrigger:
    def test_runtime_fault_reports_trigger_replan(self):
        from repro.fleet import (AdaptiveController, CandidatePlan,
                                 ControllerConfig, DeviceClass, Phase,
                                 RegimeChangeTrace)
        from repro.serving.engine import BatchCostModel
        cost = BatchCostModel(flops_per_item=1e7, flops_per_s=1e12,
                              fixed_overhead_s=2e-4)
        cands = [CandidatePlan("b1", "SC@3", 3, "tcp", 1, 1, 5e-3, cost),
                 CandidatePlan("b8", "SC@3", 3, "tcp", 8, 1, 5e-3, cost)]
        mix = (DeviceClass.make(
            "edge-embedded", Channel(1e-4, 100e6, 100e6, seed=1)),)
        scenario = RegimeChangeTrace.from_phases(
            mix, [Phase(4.0, 400.0)], seed=7)
        cfg = ControllerConfig(control_period_s=1.0, drift_threshold=None,
                               drop_trigger=None, queue_trigger=None,
                               fault_trigger=3, min_improvement=-10.0,
                               cooldown_s=0.0)
        ctl = AdaptiveController(cands, config=cfg)
        ctl.report_faults(1.5, 5)          # a burst of runtime faults
        res = ctl.run(scenario, initial="b8", engine="vectorized")
        reasons = [s.reason for s in res.switches]
        assert "runtime-fault" in reasons
        # without reports, the same config never triggers
        ctl2 = AdaptiveController(cands, config=cfg)
        res2 = ctl2.run(scenario, initial="b8", engine="vectorized")
        assert all(s.reason != "runtime-fault" for s in res2.switches)


# ------------------------------------------------------------- facade ----
class TestStudyFacade:
    def test_deploy_threads_faults(self, vgg_small, toy_data):
        from repro.api import Study, StudyScenario
        model, params = vgg_small
        xs, ys = toy_data
        study = Study(model, StudyScenario(channel=CH, protocol="tcp"),
                      params=params, data=(xs[:16], ys[:16]))
        plan = FaultPlan(seed=0, drop_rate=0.3)
        pol = RecoveryPolicy(max_attempts=5)
        rt = study.deploy(candidate=f"SC@{CUT}", faults=plan, recovery=pol)
        assert rt.faults is plan and rt.recovery is pol
        r = rt.infer(jnp.asarray(xs[:4]), iters=1, rid=0)
        assert "recovery" in r.meta
