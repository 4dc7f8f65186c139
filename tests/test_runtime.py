"""Live split-execution runtime tests: partition equivalence, wire
round-trips, kernel routing on CPU, multi-client batching, and the
measured-calibration path into the simulators."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bottleneck as B
from repro.core.split import validate_cut
from repro.kernels.bottleneck_compress import (bottleneck_compress_any,
                                               resolve_backend)
from repro.runtime import wire as W
from repro.runtime.calibrate import CalibrationTable, calibrate
from repro.runtime.engine import SplitRuntime, TailServer, run_clients
from repro.runtime.partition import make_partition


# ------------------------------------------------------------- partition ----
def test_split_vs_unsplit_every_legal_cut(vgg_small, toy_data):
    """tail(head(x)) == apply(x) at every legal cut (f32, no codec)."""
    model, params = vgg_small
    xs, _ = toy_data
    x = jnp.asarray(xs[:4])
    full = np.asarray(model.apply(params, x))
    for cut in model.cut_points():
        part = make_partition(model, params, cut)
        y = np.asarray(part.tail(part.head(x)))
        np.testing.assert_allclose(y, full, atol=1e-5,
                                   err_msg=f"cut={cut}")


def test_illegal_cut_raises(vgg_small):
    model, params = vgg_small
    bad = [i for i in range(len(model.layers))
           if i not in model.cut_points()][0]
    with pytest.raises(ValueError, match="not legal"):
        validate_cut(model, bad)
    with pytest.raises(ValueError, match="not legal"):
        make_partition(model, params, len(model.layers) - 1)


def test_boundary_shape_matches_head_output(vgg_small, toy_data):
    model, params = vgg_small
    xs, _ = toy_data
    part = make_partition(model, params, model.cut_points()[3])
    f = part.head(jnp.asarray(xs[:2]))
    assert tuple(f.shape) == part.boundary_shape(batch=2)


# ------------------------------------------------------------------ wire ----
def test_wire_f32_roundtrip_exact():
    f = jnp.asarray(np.random.default_rng(0).standard_normal((3, 5, 8)),
                    jnp.float32)
    f2 = W.roundtrip(f, quantize=False)
    np.testing.assert_array_equal(np.asarray(f2), np.asarray(f))


def test_wire_int8_roundtrip_error_bound():
    """Symmetric int8: per-row error <= amax/(2*127) (+ rounding eps)."""
    rng = np.random.default_rng(1)
    f = jnp.asarray(rng.standard_normal((4, 6, 32)) * 3.0, jnp.float32)
    f2 = np.asarray(W.roundtrip(f, quantize=True))
    err = np.abs(f2 - np.asarray(f)).reshape(-1, 32).max(axis=1)
    amax = np.abs(np.asarray(f)).reshape(-1, 32).max(axis=1)
    bound = amax / (2 * 127.0) + 1e-6
    assert (err <= bound).all(), (err.max(), bound.min())


def test_wire_bytes_self_describing():
    f = jnp.asarray(np.random.default_rng(2).standard_normal((2, 7, 16)),
                    jnp.float32)
    pkt = W.encode_activation(f, quantize=True)
    buf = W.to_bytes(pkt)
    back = W.from_bytes(buf)
    assert back.kind == "int8" and tuple(back.shape) == (2, 7, 16)
    np.testing.assert_array_equal(back.data, pkt.data)
    np.testing.assert_allclose(back.scales, pkt.scales)
    assert pkt.nbytes == len(buf)
    with pytest.raises(ValueError, match="magic"):
        W.from_bytes(b"XXXX" + buf[4:])


def test_wire_ae8_matches_reference_encode_wire():
    """The kernel-routed ae8 path == core.bottleneck.encode_wire."""
    rng = np.random.default_rng(3)
    f = jnp.asarray(rng.standard_normal((6, 48)), jnp.float32)
    ae = B.init_bottleneck(jax.random.PRNGKey(0), (48,), rate=0.5)
    pkt = W.encode_activation(f, ae)
    q_ref, s_ref = B.encode_wire(ae, f)
    np.testing.assert_array_equal(pkt.data, np.asarray(q_ref))
    np.testing.assert_allclose(pkt.scales, np.asarray(s_ref).reshape(-1, 1),
                               rtol=1e-6)
    # decode side: dequant + AE decoder == decode_wire
    f_hat = W.decode_activation(W.from_bytes(W.to_bytes(pkt)), ae)
    np.testing.assert_allclose(np.asarray(f_hat),
                               np.asarray(B.decode_wire(ae, q_ref, s_ref)),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------- kernel route ----
def test_kernel_auto_routes_off_tpu():
    assert resolve_backend("auto") in ("kernel", "ref")
    if jax.devices()[0].platform != "tpu":
        assert resolve_backend() == "ref"
    assert resolve_backend("interpret") == "interpret"
    with pytest.raises(ValueError):
        resolve_backend("vulkan")


def test_compress_any_ref_matches_interpret():
    """Pure-JAX route == Pallas interpret route, including padding shapes."""
    rng = np.random.default_rng(4)
    for n, c in [(8, 48), (130, 16)]:          # 130 exercises N-padding
        f = jnp.asarray(rng.standard_normal((n, c)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((c, 24)) * 0.1, jnp.float32)
        b = jnp.zeros((24,), jnp.float32)
        q_r, s_r = bottleneck_compress_any(f, w, b, backend="ref")
        q_i, s_i = bottleneck_compress_any(f, w, b, backend="interpret",
                                           bn=128, bc=512)
        np.testing.assert_allclose(np.asarray(s_r), np.asarray(s_i),
                                   rtol=1e-5)
        # rounding at the .5 boundary may differ by 1 code in fp
        assert np.abs(np.asarray(q_r, np.int32)
                      - np.asarray(q_i, np.int32)).max() <= 1


# ----------------------------------------------------------- end-to-end ----
def test_runtime_f32_wire_is_exact(vgg_small, toy_data):
    model, params = vgg_small
    xs, _ = toy_data
    x = xs[:2]
    cut = model.cut_points()[4]
    rt = SplitRuntime(model, params, cut, quantize=False)
    res = rt.infer(x, iters=1)
    np.testing.assert_allclose(res.logits, rt.reference(x), atol=1e-5)
    assert res.wire_bytes > 0 and res.transfer_s == 0.0
    assert res.total_s >= res.compute_s > 0


def test_runtime_int8_wire_close_and_timed(vgg_small, toy_data):
    from repro.netsim.channel import Channel
    model, params = vgg_small
    xs, _ = toy_data
    x = xs[:2]
    cut = model.cut_points()[2]
    ch = Channel(1e-3, 100e6, 100e6, seed=0)
    rt = SplitRuntime(model, params, cut, channel=ch, quantize=True)
    res = rt.infer(x, iters=1)
    ref = rt.reference(x)
    # int8 wire: small perturbation, same decisions
    assert np.argmax(res.logits, -1).tolist() == np.argmax(ref, -1).tolist()
    rel = np.abs(res.logits - ref).max() / (np.abs(ref).max() + 1e-9)
    assert rel < 0.15, rel
    assert res.transfer_s > 0 and res.meta["n_packets"] >= 1
    # int8 payload beats the f32 payload by ~4x
    raw = SplitRuntime(model, params, cut, quantize=False).infer(x, iters=1)
    assert res.wire_bytes < raw.wire_bytes / 2


def _ae_for(model, params, cut, rate=0.5):
    shapes = model.activation_shapes(params, 1)
    return B.init_bottleneck(jax.random.PRNGKey(1), shapes[cut], rate=rate)


@pytest.mark.parametrize("ae_at,quantize", [(0, True), (0, False), (1, True)],
                         ids=["ae_first", "ae_first_f32", "ae_second"])
def test_runtime_hops_match_hand_chain(vgg_small, toy_data, ae_at, quantize):
    """On a 2-cut partition ``SplitRuntime.infer`` is the hand-run chain
    stage -> to_bytes -> from_bytes -> decode_activation, bit for bit:
    the same logits and the same bytes on every hop, across ae8, int8
    and f32 payloads."""
    model, params = vgg_small
    xs, _ = toy_data
    x = jnp.asarray(xs[:4])
    cuts = model.cut_points()
    splits = (cuts[1], cuts[3])
    ae = {splits[ae_at]: _ae_for(model, params, splits[ae_at])}
    rt = SplitRuntime(model, params, splits, ae=ae, quantize=quantize)
    res = rt.infer(x, iters=1)
    part, cur, nbytes = rt.part, x, []
    for k, cut in enumerate(splits):
        cur = part.stage(k)(cur)
        buf = W.to_bytes(W.encode_activation(cur, ae.get(cut),
                                             quantize=quantize))
        nbytes.append(len(buf))
        cur = W.decode_activation(W.from_bytes(buf), ae.get(cut))
    want = np.asarray(part.stage(len(splits))(cur))
    np.testing.assert_array_equal(res.logits, want)
    assert [h["bytes"] for h in res.hops] == nbytes
    assert res.wire_bytes == sum(nbytes)


@pytest.mark.parametrize("n_cuts", [1, 2])
def test_total_s_is_transfer_inclusive_and_reconciles(vgg_small, toy_data,
                                                      n_cuts):
    """Regression pin: ``RuntimeResult.total_s`` includes the netsim-priced
    transfer time (``compute_s + transfer_s``) and reconciles exactly with
    the per-stage/per-hop breakdown and the ``build_infer_spans`` root —
    a dropped ``transfer_s`` would undercount end-to-end latency on every
    slow link."""
    from repro.netsim.channel import Channel
    model, params = vgg_small
    xs, _ = toy_data
    # a slow, high-latency link so transfer dominates unambiguously
    ch = Channel(latency_s=0.05, capacity_bps=1e6, interface_bps=1e6)
    cuts = model.cut_points()
    splits = cuts[2] if n_cuts == 1 else (cuts[1], cuts[3])
    rt = SplitRuntime(model, params, splits, channel=ch)
    res = rt.infer(xs[:2], iters=1)
    assert len(res.stage_s) == n_cuts + 1 and len(res.hops) == n_cuts
    assert res.transfer_s > 0
    assert res.total_s == res.compute_s + res.transfer_s
    assert res.total_s > res.compute_s          # the transfer is in there
    parts = sum(res.stage_s) + sum(h["encode_s"] + h["transfer_s"]
                                   + h["decode_s"] for h in res.hops)
    assert abs(parts - res.total_s) < 1e-12
    assert abs(res.trace.dur - res.total_s) < 1e-9


def test_multi_client_tail_batching(vgg_small, toy_data):
    model, params = vgg_small
    xs, _ = toy_data
    cut = model.cut_points()[3]
    clients = [xs[i:i + 1] for i in range(5)]
    results, server = run_clients(model, params, cut, clients,
                                  n_slots=2, quantize=False)
    assert sorted(results) == list(range(5))
    assert server.n_batches >= 3          # 5 clients through 2 slots
    assert server.n_served == 5
    for cid, x in enumerate(clients):
        ref = np.asarray(model.apply(params, jnp.asarray(x)))
        np.testing.assert_allclose(results[cid], ref, atol=1e-4,
                                   err_msg=f"client {cid}")


def test_tail_server_empty_step(vgg_small):
    model, params = vgg_small
    part = make_partition(model, params, model.cut_points()[0])
    server = TailServer(part, n_slots=2)
    assert server.step() == {} and server.drain() == {}


# ----------------------------------------------------------- calibration ----
@pytest.fixture(scope="module")
def cal_setup():
    from repro.models.vgg import vgg_cifar
    model = vgg_cifar(n_classes=4, input_hw=8, width_mult=0.25)
    params = model.init(jax.random.PRNGKey(1))
    splits = model.cut_points()[:3]
    table = calibrate(model, params, splits, batch=1, iters=1)
    return model, params, splits, table


def test_calibration_table_roundtrip(tmp_path, cal_setup):
    model, params, splits, table = cal_setup
    for sp in splits:
        e = table.lookup("SC", sp)
        assert e.head_s > 0 and e.tail_s > 0 and e.wire_bytes > 0
    assert table.splits() == sorted(splits)
    p = str(tmp_path / "cal.json")
    table.to_json(p)
    back = CalibrationTable.from_json(p)
    assert back.model_name == table.model_name
    assert back.lookup("SC", splits[0]) == table.lookup("SC", splits[0])
    assert back.lookup("RC").server_s > 0
    assert back.lookup("LC").edge_s > 0


def test_calibrate_quotes_head_plus_codec_costs(vgg_small, tmp_path):
    """An SC entry prices the edge as head + encode and the server as
    decode + tail, quotes exactly that through ``flow_times``, and comes
    back from JSON unchanged."""
    model, params = vgg_small
    c0 = model.cut_points()[1]
    t = calibrate(model, params, [c0], ae_map={c0: _ae_for(model, params, c0)},
                  batch=2, iters=1)
    e = t.lookup("SC", c0)
    assert e.head_s > 0 and e.encode_s > 0 and e.decode_s > 0
    assert e.edge_s == e.head_s + e.encode_s
    assert e.server_s == e.decode_s + e.tail_s
    ft = t.flow_times("SC", c0)
    assert (ft["edge_s"], ft["server_s"]) == (e.edge_s, e.server_s)
    p = str(tmp_path / "cal.json")
    t.to_json(p)
    assert CalibrationTable.from_json(p).lookup("SC", c0) == e


def test_measured_flow_uses_calibration(cal_setup):
    from repro.core.scenarios import Scenario
    from repro.core.split import SplitPlan
    from repro.netsim.channel import Channel
    from repro.netsim.simulator import NetworkConfig, measure_flow

    model, params, splits, table = cal_setup
    netcfg = NetworkConfig("tcp", Channel(1e-3, 100e6, 100e6, seed=0))
    sc = Scenario("SC", SplitPlan(splits[1]))
    input_bytes = 8 * 8 * 3 * 4

    flow_a = measure_flow(sc, netcfg, model, params, input_bytes)
    assert flow_a["cost_source"] == "analytic"
    flow_m = measure_flow(sc, netcfg, model, params, input_bytes,
                          calibration=table)
    e = table.lookup("SC", splits[1])
    assert flow_m["cost_source"] == "measured"
    assert flow_m["edge_s"] == pytest.approx(e.edge_s)
    assert flow_m["server_s"] == pytest.approx(e.server_s)
    assert flow_m["wire_bytes"] == e.wire_bytes
    assert len(flow_m["wire_s"]) == 8
    # uncovered cell falls back to analytic
    other = [c for c in model.cut_points() if c not in splits][0]
    flow_f = measure_flow(Scenario("SC", SplitPlan(other)), netcfg, model,
                          params, input_bytes, calibration=table)
    assert flow_f["cost_source"] == "analytic"


def test_measured_flow_rescales_calibration_batch(cal_setup):
    """A table calibrated at batch B serves batch-1 flows at 1/B cost."""
    from repro.core.scenarios import Scenario
    from repro.core.split import SplitPlan
    from repro.netsim.channel import Channel
    from repro.netsim.simulator import NetworkConfig, measure_flow

    model, params, splits, _ = cal_setup
    table2 = calibrate(model, params, splits[:1], batch=2, iters=1)
    e = table2.lookup("SC", splits[0])
    netcfg = NetworkConfig("tcp", Channel(1e-3, 100e6, 100e6, seed=0))
    sc = Scenario("SC", SplitPlan(splits[0]))
    flow1 = measure_flow(sc, netcfg, model, params, 8 * 8 * 3 * 4,
                         calibration=table2, batch=1)
    assert flow1["edge_s"] == pytest.approx(e.edge_s / 2)
    assert flow1["server_s"] == pytest.approx(e.server_s / 2)
    assert flow1["wire_bytes"] == pytest.approx(e.wire_bytes / 2, abs=1)
    flow2 = measure_flow(sc, netcfg, model, params, 8 * 8 * 3 * 4,
                         calibration=table2, batch=2)
    assert flow2["edge_s"] == pytest.approx(e.edge_s)
    assert flow2["wire_bytes"] == e.wire_bytes


def test_planner_measured_cost_source(cal_setup):
    from repro.core.qos import QoSRequirements
    from repro.fleet import (DeviceClass, DeploymentPlanner, SearchSpace,
                             generate_trace)
    from repro.netsim.channel import Channel

    model, params, splits, table = cal_setup

    def accuracy_fn(scenario, netcfg):
        return 0.9

    fi = list(model.cut_points())
    cs = np.linspace(1.0, 0.3, len(fi))
    input_bytes = 8 * 8 * 3 * 4
    planner = DeploymentPlanner(model, params, cs_curve=cs, layer_idx=fi,
                                accuracy_fn=accuracy_fn,
                                input_bytes=input_bytes, cost=table)
    mix = [DeviceClass.make("edge-embedded",
                            Channel(5e-4, 100e6, 100e6, seed=2))]
    trace = generate_trace(mix, 50, 20.0, seed=0)
    space = SearchSpace(split_points=tuple(splits), batch_sizes=(1,),
                        replica_counts=(1,), top_k_splits=2)
    points = planner.search(trace, mix, space)
    assert points
    # the flow the planner cached is the measured one
    flow = planner._flow(mix[0], f"SC@{splits[0]}", splits[0], "tcp")
    assert flow["cost_source"] == "measured"
    plans = planner.suggest(QoSRequirements(10.0, 0.5), (trace, mix),
                            points=points)
    assert plans["edge-embedded"] is not None

    # the deprecated cost_source=/calibration= pair is gone for good
    with pytest.raises(TypeError):
        DeploymentPlanner(model, params, cs_curve=cs, layer_idx=fi,
                          accuracy_fn=accuracy_fn, input_bytes=input_bytes,
                          cost_source="measured", calibration=table)
