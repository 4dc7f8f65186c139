"""Hypothesis property-based tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.saliency import local_maxima
from repro.kernels import ref
from repro.models.layers import rmsnorm
from repro.netsim.channel import Channel
from repro.netsim.protocols import simulate_tcp, simulate_udp
from repro.netsim.simulator import chunk_mask_from_packets

SET = dict(deadline=None, max_examples=25)


@settings(**SET)
@given(n_bytes=st.integers(1, 500_000), loss=st.floats(0, 0.3),
       seed=st.integers(0, 100))
def test_tcp_always_delivers(n_bytes, loss, seed):
    ch = Channel(1e-4, 1e9, 1e9, loss_rate=loss, seed=seed)
    r = simulate_tcp(n_bytes, ch)
    assert r.delivered.all()
    assert r.duration_s >= ch.serialization_s(min(n_bytes, 1500))


@settings(**SET)
@given(n_bytes=st.integers(1, 500_000), loss=st.floats(0, 0.9),
       seed=st.integers(0, 100))
def test_udp_duration_independent_of_delivery(n_bytes, loss, seed):
    ch = Channel(1e-4, 1e9, 1e9, loss_rate=loss, seed=seed)
    r = simulate_udp(n_bytes, ch)
    full = ch.serialization_s(1500) * r.n_packets + ch.latency_s
    assert r.duration_s <= full + 1e-12
    assert 0.0 <= r.loss_fraction <= 1.0


@settings(**SET)
@given(n_elems=st.integers(1, 5000), elem_bytes=st.sampled_from([1, 2, 4]),
       seed=st.integers(0, 50), loss=st.floats(0, 0.5))
def test_chunk_mask_covers_all_elements(n_elems, elem_bytes, seed, loss):
    rng = np.random.default_rng(seed)
    import math
    n_pkts = max(1, math.ceil(n_elems * elem_bytes / 1500))
    delivered = rng.random(n_pkts) >= loss
    mask = chunk_mask_from_packets(n_elems, delivered, elem_bytes, 1500)
    assert mask.shape == (n_elems,)
    if delivered.all():
        assert mask.all()
    if not delivered.any():
        assert not mask.any()


@settings(**SET)
@given(data=st.lists(st.floats(-10, 10), min_size=3, max_size=40))
def test_local_maxima_are_maxima(data):
    arr = np.asarray(data)
    for p in local_maxima(arr, tol=1e-9):
        assert 0 < p < len(arr) - 1
        left = arr[:p][::-1]
        right = arr[p + 1:]
        nl = next((x for x in left if abs(x - arr[p]) > 1e-9), None)
        nr = next((x for x in right if abs(x - arr[p]) > 1e-9), None)
        assert nl is None or nl < arr[p]
        assert nr is None or nr < arr[p]


@settings(**SET)
@given(b=st.integers(1, 4), n=st.integers(1, 6), c=st.integers(1, 8))
def test_rmsnorm_output_rms_is_one(b, n, c):
    x = jax.random.normal(jax.random.PRNGKey(b * 100 + n), (b, 8 * c)) * n
    y = rmsnorm(x, jnp.ones((8 * c,)))
    rms = np.sqrt(np.mean(np.asarray(y) ** 2, -1))
    np.testing.assert_allclose(rms, 1.0, atol=1e-2)


@settings(**SET)
@given(scale=st.floats(0.01, 10.0), seed=st.integers(0, 1000))
def test_quantisation_bound_property(scale, seed):
    """Dequantised wire payload is within amax/254 of the true latent."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    f = jax.random.normal(ks[0], (8, 32)) * scale
    w = jax.random.normal(ks[1], (32, 16)) * 0.2
    q, s = ref.bottleneck_compress_ref(f, w, jnp.zeros((16,)))
    z = jax.nn.relu(f @ w)
    deq = ref.bottleneck_decompress_ref(q, s)
    amax = np.asarray(jnp.max(jnp.abs(z), 1))
    err = np.max(np.abs(np.asarray(deq) - np.asarray(z)), 1)
    assert (err <= amax / 254.0 + 1e-6).all()


@settings(**SET)
@given(b=st.integers(0, 5), rows=st.integers(1, 9), c=st.integers(1, 67),
       kind=st.sampled_from(["f32", "int8", "ae8"]), seed=st.integers(0, 50))
def test_wire_byte_format_roundtrip(b, rows, c, kind, seed):
    """The split-wire byte format survives serialise -> parse -> decode
    for every payload kind, across odd tile shapes and the empty batch:
    f32 is exact, int8 respects the symmetric per-row error bound, ae8
    agrees with the reference encode/decode chain."""
    from repro.core import bottleneck as B
    from repro.runtime import wire as W
    rng = np.random.default_rng(seed)
    f = jnp.asarray(rng.standard_normal((b, rows, c)) * 3.0, jnp.float32)
    ae = (B.init_bottleneck(jax.random.PRNGKey(seed), (c,), rate=0.5)
          if kind == "ae8" else None)
    pkt = W.encode_activation(f, ae, quantize=kind != "f32")
    buf = W.to_bytes(pkt)
    back = W.from_bytes(buf)
    assert back.kind == kind and tuple(back.shape) == pkt.data.shape
    assert pkt.nbytes == len(buf)
    np.testing.assert_array_equal(back.data, pkt.data)
    out = np.asarray(W.decode_activation(back, ae))
    if kind == "f32":
        assert out.shape == (b, rows, c)
        np.testing.assert_array_equal(out, np.asarray(f))
    elif kind == "int8":
        assert out.shape == (b, rows, c)
        if b:                       # per-row bound: amax/(2*127) + rounding
            err = np.abs(out - np.asarray(f)).reshape(-1, c).max(1)
            amax = np.abs(np.asarray(f)).reshape(-1, c).max(1)
            assert (err <= amax / 254.0 + 1e-6).all()
    else:
        want = np.asarray(B.decode_wire(
            ae, jnp.asarray(pkt.data),
            jnp.asarray(pkt.scales).reshape((b, rows, 1))))
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    assert out.shape == (b, rows, c)    # decoded back to channel width


@settings(**SET)
@given(b=st.integers(1, 4), rows=st.integers(1, 9), c=st.integers(1, 67),
       kind=st.sampled_from(["f32", "int8", "ae8"]), seed=st.integers(0, 50))
def test_jitted_decode_of_a_parsed_frame_equals_eager(b, rows, c, kind, seed):
    """Jitted encode -> zero-copy frame -> parse -> jitted decode (the
    decode ``Partition.served_tail`` runs as its prologue) produces
    byte-identical payloads and bit-identical activations vs the eager
    WirePacket path, for random shapes/batches across every payload
    kind."""
    from repro.core import bottleneck as B
    from repro.runtime import wire as W
    rng = np.random.default_rng(seed)
    f = jnp.asarray(rng.standard_normal((b, rows, c)) * 3.0, jnp.float32)
    ae = (B.init_bottleneck(jax.random.PRNGKey(seed), (c,), rate=0.5)
          if kind == "ae8" else None)
    quantize = kind != "f32"
    assert W.wire_kind(ae, quantize) == kind
    pkt = W.encode_activation(f, ae, quantize=quantize)
    buf_eager = W.to_bytes(pkt)
    out_eager = np.asarray(W.decode_activation(W.from_bytes(buf_eager), ae))
    enc = jax.jit(lambda v, a: W.encode_arrays(v, a, quantize=quantize))
    data, scales = enc(f, ae)
    buf = W.frame_arrays(kind, data, scales)
    assert buf == buf_eager
    pkt2 = W.from_bytes(buf)
    dec = jax.jit(lambda d, s, a: W.decode_arrays(kind, d, s, a))
    out = np.asarray(dec(jnp.asarray(pkt2.data),
                         None if pkt2.scales is None
                         else jnp.asarray(pkt2.scales), ae))
    np.testing.assert_array_equal(out, out_eager)


@settings(**SET)
@given(n_hops=st.integers(1, 3), n_micro=st.integers(1, 6),
       seed=st.integers(0, 10_000))
def test_pipeline_closed_form_matches_event_engine(n_hops, n_micro, seed):
    """The planner fast path's contract: on loss-free paths the closed
    forms in ``netsim.analytic`` reproduce ``simulate_pipeline``'s
    sequential *and* pipelined makespans to 1e-9 relative — across random
    stage/hop/path tensors, n_micro=1, zero-byte hops and pass-through
    (zero-time) stages."""
    import math

    from repro.netsim import analytic
    from repro.netsim.simulator import (NetworkConfig, NetworkPath,
                                        simulate_pipeline)
    rng = np.random.default_rng(seed)
    hops = tuple(NetworkConfig(str(rng.choice(["tcp", "udp"])),
                               Channel(float(rng.choice([1e-6, 1e-4, 1e-2])),
                                       float(rng.choice([1e6, 20e6, 1e9])),
                                       float(rng.choice([20e6, 1e9])),
                                       seed=k))
                 for k in range(n_hops))
    path = NetworkPath(hops)
    stage_s = [float(rng.choice([0.0, 1e-4, 2e-3, 5e-2]))
               for _ in range(n_hops + 1)]
    hop_bytes = [int(rng.choice([0, 1, 1500, 20_000, 300_000]))
                 for _ in range(n_hops)]
    pipe = simulate_pipeline(stage_s, hop_bytes, path, n_micro=n_micro)
    cf_pipe, cf_seq = analytic.closed_form_pipeline(stage_s, hop_bytes,
                                                    path, n_micro=n_micro)
    assert math.isclose(cf_pipe, pipe.latency_s, rel_tol=1e-9, abs_tol=1e-15)
    assert math.isclose(cf_seq, pipe.sequential_s, rel_tol=1e-9,
                        abs_tol=1e-15)


@settings(**SET)
@given(sq=st.sampled_from([32, 64]), sk=st.sampled_from([32, 64, 128]),
       g=st.sampled_from([1, 2, 4]), seed=st.integers(0, 100))
def test_attention_softmax_convexity(sq, sk, g, seed):
    """Attention output is a convex combination of V rows."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, sq, 2 * g, 16))
    k = jax.random.normal(ks[1], (1, sk, 2, 16))
    v = jax.random.normal(ks[2], (1, sk, 2, 16))
    out = ref.flash_attention_ref(q, k, v, causal=sq <= sk)
    assert float(jnp.abs(out).max()) <= float(jnp.abs(v).max()) + 1e-4


@settings(**SET)
@given(n=st.integers(1, 400), k=st.integers(1, 4),
       max_batch=st.integers(1, 16),
       window_ms=st.sampled_from([0.0, 0.1, 2.0, 10.0]),
       queue_limit=st.integers(1, 120),
       service_us=st.floats(10.0, 2000.0), per_item=st.floats(0.0, 1e7),
       load=st.floats(0.2, 5.0), seed=st.integers(0, 1000))
def test_cluster_engines_agree_on_random_fleets(n, k, max_batch, window_ms,
                                                queue_limit, service_us,
                                                per_item, load, seed):
    """The vectorized cluster engine replays the event engine exactly:
    identical drop/batch/served counts and percentile agreement for
    random arrival processes, service costs, and cluster configs."""
    from repro.fleet.cluster import ClusterConfig
    from repro.fleet.vectorized import (check_against_event_engine,
                                        simulate_cluster_vectorized)
    from repro.serving.engine import BatchCostModel
    cost = BatchCostModel(flops_per_item=per_item, flops_per_s=1e12,
                          fixed_overhead_s=service_us * 1e-6)
    cfg = ClusterConfig(n_replicas=k, max_batch=max_batch,
                        batch_window_s=window_ms * 1e-3,
                        queue_limit=queue_limit)
    cap = k * max_batch / cost.service_time(max_batch)
    rng = np.random.default_rng(seed)
    t = np.cumsum(rng.exponential(1.0 / (cap * load), n))
    stats = simulate_cluster_vectorized(t, cost, cfg)
    # raises AssertionError on any count mismatch or percentile drift
    check_against_event_engine(t, cost, cfg, stats)


@settings(**SET)
@given(n=st.integers(2, 300), rate=st.floats(100.0, 20_000.0),
       seed=st.integers(0, 100))
def test_cluster_engines_agree_on_bursty_arrivals(n, rate, seed):
    """Non-poisson (MMPP) arrival processes through both engines."""
    from repro.fleet.cluster import ClusterConfig
    from repro.fleet.traffic import bursty_arrivals
    from repro.fleet.vectorized import (check_against_event_engine,
                                        simulate_cluster_vectorized)
    from repro.serving.engine import BatchCostModel
    rng = np.random.default_rng(seed)
    t = bursty_arrivals(rate, n, rng)
    cost = BatchCostModel(flops_per_item=1e6, flops_per_s=1e12,
                          fixed_overhead_s=1e-3)
    cfg = ClusterConfig(n_replicas=2, max_batch=4, batch_window_s=2e-3,
                        queue_limit=32)
    stats = simulate_cluster_vectorized(t, cost, cfg)
    check_against_event_engine(t, cost, cfg, stats)


@settings(deadline=None, max_examples=15)
@given(rates=st.lists(st.floats(200.0, 20_000.0), min_size=2, max_size=4),
       max_switches=st.integers(0, 3), seed=st.integers(0, 50))
def test_controller_never_exceeds_max_switches(rates, max_switches, seed):
    """However hostile the regime changes, voluntary switches stay
    within the configured bound (forced replica reconfigs excepted —
    there are none here)."""
    from repro.fleet import (AdaptiveController, CandidatePlan,
                             ControllerConfig, DeviceClass, Phase,
                             RegimeChangeTrace)
    from repro.serving.engine import BatchCostModel
    cost = BatchCostModel(flops_per_item=1e7, flops_per_s=1e12,
                          fixed_overhead_s=2e-4)
    cands = [CandidatePlan("b1", "SC@3", 3, "tcp", 1, 1, 5e-3, cost),
             CandidatePlan("b64", "SC@3", 3, "tcp", 64, 1, 5e-3, cost)]
    mix = (DeviceClass.make("edge-embedded",
                            Channel(1e-4, 100e6, 100e6, seed=1)),)
    sc = RegimeChangeTrace.from_phases(
        mix, [Phase(0.5, r) for r in rates], seed=seed)
    cfg = ControllerConfig(control_period_s=0.2, drift_threshold=0.2,
                           min_improvement=0.0,
                           max_switches=max_switches)
    res = AdaptiveController(cands, config=cfg).run(sc)
    assert res.n_switches <= max_switches
    assert res.n_forced == 0


@settings(deadline=None, max_examples=10)
@given(rate=st.floats(200.0, 5_000.0), seed=st.integers(0, 50),
       engine=st.sampled_from(["vectorized", "event"]))
def test_controller_with_triggers_disabled_is_exactly_static(rate, seed,
                                                             engine):
    """Drift detection off + no faults ⇒ the adaptive run IS the static
    run, bit-for-bit, on either engine."""
    from repro.fleet import (AdaptiveController, CandidatePlan,
                             ControllerConfig, DeviceClass, Phase,
                             RegimeChangeTrace)
    from repro.serving.engine import BatchCostModel
    cost = BatchCostModel(flops_per_item=1e7, flops_per_s=1e12,
                          fixed_overhead_s=2e-4)
    cands = [CandidatePlan("b1", "SC@3", 3, "tcp", 1, 1, 5e-3, cost),
             CandidatePlan("b8", "SC@3", 3, "tcp", 8, 1, 5e-3, cost)]
    mix = (DeviceClass.make("edge-embedded",
                            Channel(1e-4, 100e6, 100e6, seed=1)),)
    sc = RegimeChangeTrace.from_phases(mix, [Phase(1.0, rate)], seed=seed)
    cfg = ControllerConfig(control_period_s=0.25, drift_threshold=None,
                           drop_trigger=None, queue_trigger=None)
    ctl = AdaptiveController(cands, config=cfg)
    a = ctl.run(sc, initial="b8", engine=engine)
    s = ctl.run_static(sc, "b8", engine=engine)
    assert np.array_equal(a.latencies, s.latencies)
    assert a.plan_keys == s.plan_keys == ("b8",)
    assert a.n_switches == 0 and a.n_replans == 0


@settings(**SET)
@given(b=st.integers(1, 4), rows=st.integers(1, 9), c=st.integers(1, 67),
       kind=st.sampled_from(["f32", "int8", "ae8"]), seed=st.integers(0, 50))
def test_checksummed_frames_preserve_zero_fault_bytes(b, rows, c, kind, seed):
    """The SEI2 (checksummed) frame is the SEI1 frame with a new magic
    and an 8-byte CRC pair spliced after the dims — the payload bytes
    are untouched — and checksum=False stays the historical layout."""
    from repro.core import bottleneck as B
    from repro.runtime import wire as W
    rng = np.random.default_rng(seed)
    f = jnp.asarray(rng.standard_normal((b, rows, c)) * 3.0, jnp.float32)
    ae = (B.init_bottleneck(jax.random.PRNGKey(seed), (c,), rate=0.5)
          if kind == "ae8" else None)
    pkt = W.encode_activation(f, ae, quantize=kind != "f32")
    v1, v2 = W.to_bytes(pkt), W.to_bytes(pkt, checksum=True)
    assert v1[:4] == b"SEI1" and v2[:4] == b"SEI2"
    head = 6 + 4 * len(pkt.shape)
    assert v1[4:head] == v2[4:head]          # kind + dims identical
    assert v2[head + 8:] == v1[head:]        # payload bit-identical
    back = W.from_bytes(v2)
    np.testing.assert_array_equal(back.data, pkt.data)
    np.testing.assert_array_equal(back.scales, pkt.scales)
    out1 = np.asarray(W.decode_activation(W.from_bytes(v1), ae))
    out2 = np.asarray(W.decode_activation(back, ae))
    np.testing.assert_array_equal(out1, out2)


@settings(**SET)
@given(seed=st.integers(0, 1000), drop=st.floats(0, 1), corr=st.floats(0, 1),
       strag=st.floats(0, 1), rid=st.integers(0, 40), hop=st.integers(0, 3))
def test_fault_schedule_is_pure_function_of_seed(seed, drop, corr, strag,
                                                 rid, hop):
    """The injected fault schedule depends only on (seed, rid, hop,
    attempt) — never on query order or instance identity — and every
    burst ends within max_consecutive attempts."""
    from repro.runtime.faults import TRANSFER_FAULTS, FaultPlan
    kw = dict(seed=seed, drop_rate=drop, corrupt_rate=corr,
              straggle_rate=strag, max_consecutive=5)
    sched = FaultPlan(**kw).transfer_schedule(rid, hop, 8)
    again = tuple(FaultPlan(**kw).transfer_fault(rid, hop, a)
                  for a in reversed(range(8)))[::-1]
    assert sched == again
    assert all(f is None or f in TRANSFER_FAULTS for f in sched)
    assert all(f is None for f in sched[5:])     # bounded burst


@settings(deadline=None, max_examples=5)
@given(seed=st.integers(0, 30), drop=st.floats(0.2, 0.7),
       corr=st.floats(0.0, 0.4))
def test_faulted_runtime_is_deterministic(vgg_small, toy_data, seed, drop,
                                          corr):
    """Same FaultPlan seed ⇒ identical fault counts, retry/backoff
    sequence and bit-identical logits across fresh runtimes; retried
    (non-degraded) outputs equal fault-free."""
    from repro.runtime.engine import SplitRuntime
    from repro.runtime.faults import FaultPlan, RecoveryPolicy
    model, params = vgg_small
    x = jnp.asarray(toy_data[0][:2])
    ch = Channel(1e-3, 100e6, 100e6, seed=0)
    plan = FaultPlan(seed=seed, drop_rate=drop, corrupt_rate=corr)
    pol = RecoveryPolicy(max_attempts=8)

    def run():
        rt = SplitRuntime(model, params, 3, channel=ch, faults=plan,
                          recovery=pol)
        return rt.infer(x, iters=1, rid=seed)

    a, b2 = run(), run()
    np.testing.assert_array_equal(a.logits, b2.logits)
    for k in ("retries", "backoff_s", "downgrades", "faults"):
        assert a.meta["recovery"][k] == b2.meta["recovery"][k]
    if not a.meta["degraded"]:
        base = SplitRuntime(model, params, 3, channel=ch).infer(x, iters=1)
        np.testing.assert_array_equal(a.logits, base.logits)
