"""Live split-execution: head on the edge, tail on the server, the int8
wire in between.

Two drivers on top of :class:`repro.runtime.partition.Partition`:

* :class:`SplitRuntime` — one client end-to-end: head forward, wire
  encode -> bytes -> (netsim-priced transfer) -> decode, tail forward.
  Every stage is wall-clock timed (``jax.block_until_ready`` fences), so a
  run doubles as a measurement — this is what ``runtime.calibrate`` sweeps
  to build the simulator's measured cost tables.
* :class:`TailServer` — the server side under *many* clients: tail
  requests queue and are batched through a fixed
  :class:`repro.serving.continuous.SlotPool`, one jitted batched tail
  forward per step (the SplitNets-style partitioned serving discipline).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.netsim.channel import Channel
from repro.netsim.protocols import simulate_transfer
from repro.obs import NULL, Span, labelled
from repro.runtime import wire as W
from repro.runtime.faults import (FaultError, FaultPlan, RecoveryExhausted,
                                  RecoveryPolicy, downgrade_ladder)
from repro.runtime.partition import Partition, make_partition
from repro.serving.continuous import SlotPool


def timeit_blocked(fn, *args, iters: int = 3, warmup: int = 1) -> tuple:
    """(best seconds, last output) with compile excluded and device fences.

    Min-over-iterations, not mean: the repeatable cost of the stage.  On a
    loaded host the mean smears scheduler noise into the calibration
    tables; min is stable, and since the runtime and the calibrator both
    measure through here, simulated-vs-executed comparisons cancel the
    estimator choice.
    """
    out = None
    for _ in range(max(1, warmup)):
        out = jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best, out


@dataclass
class RuntimeResult:
    """One timed end-to-end split inference.

    The scalar fields keep the historical 1-cut decomposition for any
    number of cuts: ``head_s`` is stage 0, ``tail_s`` sums the later
    stages, and ``encode_s``/``transfer_s``/``decode_s``/``wire_bytes``
    sum over hops.  The per-stage / per-hop breakdown lives in
    ``stage_s`` and ``hops``, and ``trace`` holds the same decomposition
    as a span tree (``infer`` -> ``stage{k}`` -> ``encode``/``transfer``/
    ``decode`` per hop) on a reconstructed timeline, so an executed run
    and a simulated one are comparable span-by-span in Perfetto.

    ``total_s`` is **transfer-inclusive** — ``compute_s + transfer_s``,
    i.e. stages + codec + the netsim-priced wire time — and reconciles
    exactly with the root span of :func:`build_infer_spans` (pinned by a
    regression test; a device-only latency lives in ``compute_s``).
    """
    logits: np.ndarray
    split_layer: int                 # first (edge-side) cut
    head_s: float
    encode_s: float
    transfer_s: float                # netsim-priced wire time (0 w/o channel)
    decode_s: float
    tail_s: float
    wire_bytes: int
    meta: dict = field(default_factory=dict)
    splits: tuple = ()               # full ordered cut list
    stage_s: tuple = ()              # per-stage compute seconds (K+1)
    hops: tuple = ()                 # per-hop dicts: bytes/encode_s/...
    trace: Optional[Span] = None     # root span of the timing tree

    @property
    def compute_s(self) -> float:
        return self.head_s + self.encode_s + self.decode_s + self.tail_s

    @property
    def total_s(self) -> float:
        return self.compute_s + self.transfer_s


def build_infer_spans(stage_s, hops, splits, *, base: float = 0.0,
                      clock: str = "wall", tid: str = "runtime") -> Span:
    """The span tree of one timed split inference.

    The measured per-stage / per-hop durations are laid out back-to-back
    from ``base`` (a *reconstructed* timeline: ``timeit_blocked`` takes
    the min over iterations, so the stages were not literally contiguous
    on the host clock).  By construction the root span's duration equals
    the sum of its leaves — i.e. it reconciles exactly with
    ``RuntimeResult.total_s``.
    """
    total = sum(stage_s) + sum(h["encode_s"] + h["transfer_s"]
                               + h["decode_s"] for h in hops)
    root = Span("infer", base, base + total, clock, tid, "runtime",
                {"splits": list(splits)})
    t = base
    for k, s in enumerate(stage_s):
        root.children.append(Span(f"stage{k}", t, t + s, clock, tid,
                                  "runtime", {"k": k}))
        t += s
        if k >= len(hops):
            continue
        h = hops[k]
        hop = Span(f"hop{k}", t, t + h["encode_s"] + h["transfer_s"]
                   + h["decode_s"], clock, tid, "runtime",
                   {"cut": h["cut"], "bytes": h["bytes"]})
        root.children.append(hop)
        # recovery hops carry an event log (timeouts, backoffs, failed
        # parses, re-encodes...); its bucket sums ARE encode_s/transfer_s/
        # decode_s, so rendering per-event keeps the root reconciled
        events = h.get("events") or [(part, part, h[f"{part}_s"])
                                     for part in ("encode", "transfer",
                                                  "decode")]
        for name, _bucket, d in events:
            hop.children.append(Span(name, t, t + d, clock, tid, "runtime"))
            t += d
    return root


class SplitRuntime:
    """Execute a model split at ``split_layer`` (one cut or an ordered
    cut list) end-to-end on this host.

    The stages run as a chain: stage k computes, its boundary activation
    crosses hop k through the wire codec, stage k+1 continues — with
    per-stage and per-hop wall-clock timing.  ``channel``/``protocol``
    price the wire hops with the discrete-event transport models (the
    bytes are real, the network is simulated — the runtime runs in one
    process); a single channel prices every hop, a sequence of channels
    (or a ``netsim.simulator.NetworkPath``) prices hop k with entry k.
    ``wire_kind`` per hop: 'ae8' when that cut has an AE, else 'int8'
    ('f32' for the exactness oracle).  ``ae`` may be one AE dict (first
    cut) or a ``{cut: ae}`` map.

    Each stage runs as its own jitted program and each hop through the
    jitted wire codec (``wire.encode_activation`` -> bytes ->
    ``wire.decode_activation``), so hop ``encode_s``/``decode_s`` hold
    the codec and ``stage_s`` the layers alone.  The many-client server
    side, where the decode is the prologue of one batched tail program,
    is :class:`TailServer`.
    """

    def __init__(self, model, params, split_layer, *,
                 ae: Optional[dict] = None,
                 channel=None, protocol: str = "tcp",
                 quantize: bool = True, obs=None,
                 faults: Optional[FaultPlan] = None,
                 recovery: Optional[RecoveryPolicy] = None):
        self.part: Partition = make_partition(model, params, split_layer, ae)
        self.channel, self.protocol = channel, protocol
        self.quantize = quantize
        self.hops = self._resolve_hops(channel, protocol)
        self.obs = NULL if obs is None else obs
        # fault injection + recovery: only consulted when a plan is
        # present — ``faults=None`` leaves the zero-fault fast path (and
        # its SEI1 byte streams) completely untouched
        self.faults = faults
        self.recovery = recovery if recovery is not None else RecoveryPolicy()

    def _resolve_hops(self, channel, protocol) -> list:
        """Per-hop (protocol, channel) pairs; None entries skip pricing."""
        n = len(self.part.splits)
        if channel is None:
            return [None] * n
        if isinstance(channel, Channel):
            return [(protocol, channel)] * n
        hops = []
        for h in channel:                    # NetworkPath | sequence
            if isinstance(h, Channel):
                hops.append((protocol, h))
            elif h is None:
                hops.append(None)
            else:                            # a NetworkConfig-shaped hop
                hops.append((h.protocol, h.channel))
        if len(hops) != n:
            raise ValueError(f"{n} cuts need {n} priced hops, got {len(hops)}")
        return hops

    # ------------------------------------------------------------ stages ----
    def _encode(self, f, ae):
        return W.encode_activation(f, ae, quantize=self.quantize)

    def _price_hop(self, k: int, nbytes: int, stream: int) -> tuple:
        """netsim-priced transfer of hop k: (transfer_s, transport meta)."""
        if self.hops[k] is None:
            return 0.0, {}
        proto, ch = self.hops[k]
        tr = simulate_transfer(proto, nbytes, ch, stream=stream + 137 * k)
        return tr.duration_s, {"n_packets": tr.n_packets,
                               "n_transmissions": tr.n_transmissions,
                               "loss_fraction": tr.loss_fraction}

    def infer(self, x, *, iters: int = 3, stream: int = 0,
              rid: int = 0) -> RuntimeResult:
        """Timed stage -> wire -> stage ... execution of one input batch.

        ``rid`` is the request id the fault plan keys its deterministic
        draws on (ignored when no plan is installed).
        """
        if self.faults is not None:
            logits, stage_s, hops, extra = self._run_recovering(
                x, iters=iters, stream=stream, rid=rid)
            return self._package(logits, stage_s, hops, extra)
        logits, stage_s, hops = self._run_eager(x, iters=iters,
                                                stream=stream)
        return self._package(logits, stage_s, hops)

    def _run_eager(self, x, *, iters: int, stream: int) -> tuple:
        """Stage by stage: each stage's jit, then the hop's jitted codec
        through real wire bytes."""
        cur = jnp.asarray(x)
        stage_s, hops = [], []
        for k in range(self.part.n_stages):
            s, cur = timeit_blocked(self.part.stage(k), cur, iters=iters)
            stage_s.append(s)
            if k >= len(self.part.splits):
                break
            ae_k = self.part.ae_map.get(self.part.splits[k])
            encode_s, buf = timeit_blocked(
                lambda v: W.to_bytes(self._encode(v, ae_k)), cur, iters=iters)
            transfer_s, meta = self._price_hop(k, len(buf), stream)
            decode_s, cur = timeit_blocked(
                lambda b: W.decode_activation(W.from_bytes(b), ae_k),
                buf, iters=iters)
            hops.append({"cut": self.part.splits[k], "bytes": len(buf),
                         "encode_s": encode_s, "transfer_s": transfer_s,
                         "decode_s": decode_s, **meta})
        return cur, stage_s, hops

    # ------------------------------------------------------- recovery ----
    def _encode_rung(self, f, ae_k, kind: str) -> bytes:
        """Encode the boundary activation at one degradation rung, as a
        checksummed (SEI2) frame.  Rung 0 is the hop's nominal codec;
        lower rungs re-encode locally from the same activation
        (ae8 -> int8 -> f32), so a downgrade never needs a round-trip."""
        if kind == "ae8":
            pkt = W.encode_activation(f, ae_k, quantize=True)
        else:
            pkt = W.encode_activation(f, None, quantize=(kind == "int8"))
        return W.to_bytes(pkt, checksum=True)

    @staticmethod
    def _payload_lo(buf: bytes) -> int:
        """First payload byte of an SEI2 frame (corruption is aimed past
        the header so detection falls on the CRC, not the magic)."""
        return 6 + 4 * buf[5] + 8

    def _run_stage_faulted(self, k: int, cur, *, iters, rid, plan,
                           counts, rec):
        """Stage k under injected stage exceptions: retry until the plan
        stops faulting (bounded by ``max_consecutive``), charging one
        stage execution per crashed attempt."""
        attempt = 0
        while True:
            try:
                if plan.stage_fault(rid, k, attempt):
                    raise FaultError(
                        f"injected fault in stage {k} (attempt {attempt})")
                s, out = timeit_blocked(self.part.stage(k), cur, iters=iters)
                break
            except FaultError:
                counts["stage"] += 1
                rec["retries"] += 1
                attempt += 1
        # every crashed attempt ran the stage up to the fault: charge a
        # full execution each so the accounting prices the retries
        return s * (1 + attempt), out

    def _recover_hop(self, k: int, cur, *, iters, stream, rid,
                     counts, rec, t: float):
        """Hop k under the fault plan: attempt loop with RTO-derived
        timeouts, backoff, codec downgrade, and local-fallback
        escalation.  Returns ``(boundary, hop_dict, t, fell_back)``."""
        plan, pol = self.faults, self.recovery
        cut = self.part.splits[k]
        ae_k = self.part.ae_map.get(cut)
        ladder = downgrade_ladder(W.wire_kind(ae_k, self.quantize))
        ch_k = None if self.hops[k] is None else self.hops[k][1]
        last_hop = k == len(self.part.splits) - 1
        events, tmeta = [], {}
        rung, corruptions, attempt = 0, 0, 0
        fell_back = False

        def encode(rung_kind):
            return timeit_blocked(
                lambda v: self._encode_rung(v, ae_k, rung_kind), cur,
                iters=iters)

        enc_s, buf = encode(ladder[rung])
        events.append(("encode", "encode", enc_s))
        t += enc_s
        while True:
            if attempt >= pol.max_attempts or (
                    pol.deadline_s is not None and t >= pol.deadline_s):
                # budget exhausted: degrade to running the rest locally
                if not pol.local_fallback:
                    raise RecoveryExhausted(
                        f"hop {k}: {attempt} attempts, "
                        f"t={t:.3f}s of budget {pol.deadline_s}")
                rec["local_fallback"] = True
                fell_back = True
                break
            fate = plan.transfer_fault(rid, k, attempt)
            if last_hop and plan.blackout_at(t):
                fate = "blackout"     # server leg is dark: attempt times out
            if fate in ("drop", "blackout"):
                counts[fate] += 1
                lost_s = pol.timeout_s(ch_k, len(buf))
                back = pol.backoff_s(attempt, seed=plan.seed, rid=rid,
                                     hop=k, channel=ch_k)
                events.append((f"{fate}-timeout", "transfer", lost_s))
                events.append(("backoff", "transfer", back))
                t += lost_s + back
                rec["timeouts"] += 1
                rec["backoff_s"] += back
                rec["retries"] += 1
                attempt += 1
                continue
            transfer_s, tmeta = self._price_hop(k, len(buf),
                                                stream + 7919 * attempt)
            if fate == "corrupt":
                counts["corrupt"] += 1
                events.append(("transfer", "transfer", transfer_s))
                t += transfer_s
                bad = plan.corrupt_bytes(buf, rid, k, attempt,
                                         lo=self._payload_lo(buf))
                try:
                    W.from_bytes(bad)
                    raise AssertionError(
                        "corrupted SEI2 frame decoded cleanly")
                except W.WireError as e:
                    rec["log"].append(
                        {"event": "corrupt", "hop": k, "attempt": attempt,
                         "error": str(e)})
                corruptions += 1
                back = pol.backoff_s(attempt, seed=plan.seed, rid=rid,
                                     hop=k, channel=ch_k)
                events.append(("backoff", "transfer", back))
                t += back
                rec["backoff_s"] += back
                rec["retries"] += 1
                if corruptions >= pol.downgrade_after \
                        and rung + 1 < len(ladder):
                    rung += 1
                    corruptions = 0
                    rec["downgrades"].append(
                        {"hop": k, "to": ladder[rung], "attempt": attempt})
                    enc_s, buf = encode(ladder[rung])
                    events.append(("re-encode", "encode", enc_s))
                    t += enc_s
                attempt += 1
                continue
            # delivered — possibly late (straggling tail server)
            if fate == "straggle":
                counts["straggle"] += 1
                events.append(("straggle", "transfer", plan.straggle_s))
                t += plan.straggle_s
            events.append(("transfer", "transfer", transfer_s))
            t += transfer_s
            dec_s, cur = timeit_blocked(
                lambda b, kk=ladder[rung]: W.decode_activation(
                    W.from_bytes(b), ae_k if kk == "ae8" else None),
                buf, iters=iters)
            events.append(("decode", "decode", dec_s))
            t += dec_s
            break
        hop = {"cut": cut, "bytes": len(buf),
               "encode_s": sum(d for _, b, d in events if b == "encode"),
               "transfer_s": sum(d for _, b, d in events if b == "transfer"),
               "decode_s": sum(d for _, b, d in events if b == "decode"),
               "attempts": attempt + (0 if fell_back else 1),
               "kind": ladder[rung], "delivered": not fell_back,
               "events": events, **tmeta}
        return cur, hop, t, fell_back

    def _run_recovering(self, x, *, iters: int, stream: int,
                        rid: int) -> tuple:
        """The faulted/recovery execution: the eager stage chain wrapped
        in the retry/backoff/degradation machinery of
        :class:`~repro.runtime.faults.RecoveryPolicy`.

        Codec downgrade re-encodes from the raw boundary activation.
        Frames ship as SEI2 (CRC32-checksummed), so corruption is
        detected, never decoded; zero-fault runs (``faults=None``) never
        enter here.
        """
        plan = self.faults
        counts = {"drop": 0, "corrupt": 0, "straggle": 0, "stage": 0,
                  "blackout": 0}
        rec = {"retries": 0, "timeouts": 0, "backoff_s": 0.0,
               "downgrades": [], "local_fallback": False, "log": []}
        t = 0.0
        cur = jnp.asarray(x)
        stage_s, hops = [], []
        for k in range(self.part.n_stages):
            s, cur = self._run_stage_faulted(k, cur, iters=iters, rid=rid,
                                             plan=plan, counts=counts,
                                             rec=rec)
            stage_s.append(s)
            t += s
            if k >= len(self.part.splits):
                break
            cur, hop, t, fell_back = self._recover_hop(
                k, cur, iters=iters, stream=stream, rid=rid,
                counts=counts, rec=rec, t=t)
            hops.append(hop)
            if fell_back:
                # the server leg is unreachable within budget: the edge
                # runs every remaining stage itself (codec skipped — the
                # exact boundary activation feeds the next stage)
                for j in range(k + 1, self.part.n_stages):
                    s, cur = self._run_stage_faulted(
                        j, cur, iters=iters, rid=rid, plan=plan,
                        counts=counts, rec=rec)
                    stage_s.append(s)
                    t += s
                break
        rec["t_virtual_s"] = t
        obs = self.obs
        if obs.enabled:
            now = obs.tracer.wall_now()
            for name, v in counts.items():
                if v:
                    obs.metrics.counter(f"runtime.fault.{name}").inc(v)
            obs.metrics.counter("runtime.retry.attempts").inc(rec["retries"])
            obs.metrics.counter("runtime.retry.timeouts").inc(rec["timeouts"])
            obs.metrics.counter("runtime.retry.backoff_s").inc(
                rec["backoff_s"])
            obs.metrics.counter("runtime.retry.downgrades").inc(
                len(rec["downgrades"]))
            if rec["local_fallback"]:
                obs.metrics.counter("runtime.retry.local_fallback").inc()
            obs.metrics.record("runtime.retry.t_virtual_s", now, t)
        extra = {"degraded": bool(rec["downgrades"]) or rec["local_fallback"],
                 "local_fallback": rec["local_fallback"],
                 "recovery": {**rec, "faults": counts}}
        return cur, stage_s, hops, extra

    def _package(self, logits, stage_s, hops,
                 extra_meta: Optional[dict] = None) -> RuntimeResult:
        result = RuntimeResult(
            np.asarray(logits), self.part.split_layer,
            stage_s[0],
            sum(h["encode_s"] for h in hops),
            sum(h["transfer_s"] for h in hops),
            sum(h["decode_s"] for h in hops),
            sum(stage_s[1:]),
            sum(h["bytes"] for h in hops),
            {**(dict(hops[0]) if len(hops) == 1 else {"hops": hops}),
             **(extra_meta or {})},
            splits=self.part.splits, stage_s=tuple(stage_s),
            hops=tuple(hops))
        obs = self.obs
        if obs.enabled:
            # anchor the reconstructed timeline so successive infers on
            # one recorder don't overlap (the real elapsed time, warmup
            # included, always exceeds the min-estimator total)
            end = obs.tracer.wall_now()
            result.trace = build_infer_spans(
                stage_s, hops, self.part.splits,
                base=max(0.0, end - result.total_s))
            obs.tracer.extend(result.trace.walk())
            for k, s in enumerate(stage_s):
                obs.metrics.record(labelled("runtime.stage_s", k=k), end, s)
            for k, h in enumerate(hops):
                obs.metrics.record(labelled("runtime.hop_bytes", k=k), end,
                                   h["bytes"])
        else:
            result.trace = build_infer_spans(stage_s, hops, self.part.splits)
        return result

    def reference(self, x) -> np.ndarray:
        """Unsplit forward of the same params (equivalence oracle)."""
        return np.asarray(self.part.full(jnp.asarray(x)))


# -------------------------------------------------------- multi-client ----
@dataclass
class TailRequest:
    client_id: int
    payload: bytes                   # serialized wire packet
    t_submit: float = 0.0            # on the server's recorder's wall clock
    t_admit: float = 0.0             # (both stay 0.0 with telemetry off)


@dataclass
class _Staging:
    """Host staging of one payload kind: every slot's codes (and row
    scales, for the int8 kinds) side by side in the order of the slots,
    so one step uploads the whole pool in one transfer."""
    shape: tuple                     # one request's payload shape
    codes: np.ndarray                # (n_slots * client_batch, *shape[1:])
    scales: Optional[np.ndarray]     # (n_slots * rows per request, 1) f32

    @property
    def nbytes(self) -> int:
        return self.codes.nbytes + (0 if self.scales is None
                                    else self.scales.nbytes)


class TailServer:
    """Server side of the split runtime under N edge clients.

    Requests (wire byte strings) queue; each :meth:`step` admits up to
    ``n_slots`` of them into the slot pool, copies each frame's codes and
    scales into its slot's rows of a host staging buffer, uploads the
    whole pool in one transfer and runs **one** program for it: the wire
    decode as the prologue of the batched tail
    (``Partition.served_tail``).  Empty slots keep the codes they last
    held and their outputs are discarded.  The program compiles once for
    the pool shape — batch composition changes per step without
    recompiling, the same discipline ``ContinuousBatcher`` applies to
    decode streams.  Each payload kind (``ae8``, ``int8``, ``f32``) has
    its own staging and program, so frames of several kinds (the codec
    downgrade ladder's) share a step, one upload and one program per
    kind present.  A frame that does not parse, or whose per-request
    shape is not the partition's boundary (the AE latent for ``ae8``) at
    ``client_batch``, raises :class:`~repro.runtime.wire.WireError` and
    leaves the pool; the other admitted requests are served by the next
    step.

    ``obs`` (a ``repro.obs.Recorder``; the free null recorder by default)
    times each serving step as wall spans on the ``tail_server`` track:
    ``server.step`` holds ``server.admit``, ``server.inputs`` (one
    ``server.frame`` per admitted request, each holding ``server.parse``
    and ``server.stage``, then one ``server.upload`` of the pool per
    kind), ``server.tail`` and ``server.fetch`` (the host waits there
    for the logits).  It also records ``runtime.queue_wait_s``
    (admission minus submission, per request) and the
    ``runtime.queue_depth`` left after admission.
    """

    TRACK = "tail_server"

    def __init__(self, part: Partition, *, n_slots: int = 4,
                 client_batch: int = 1,
                 faults: Optional[FaultPlan] = None, obs=None):
        self.part = part
        self.pool = SlotPool(n_slots)
        self.queue: deque = deque()
        self.client_batch = client_batch
        self._feat = part.boundary_shape(client_batch)[1:]
        self.n_batches = 0
        self.n_served = 0
        self.occupancy: list = []
        # fault plan: integrity-check admissions, honour blackout windows
        self.faults = faults
        self.n_rejected = 0
        self.rejected: list = []
        self.n_blackout_steps = 0
        self.obs = NULL if obs is None else obs
        self._staging: dict = {}     # payload kind -> _Staging

    def submit(self, client_id: int, payload: bytes) -> bool:
        """Queue one wire payload.  With a fault plan installed the frame
        is integrity-checked on admission (corrupted frames are rejected
        and counted — the client's retry loop re-sends, the server never
        decodes garbage).  Returns whether the request was accepted."""
        if self.faults is not None:
            try:
                W.from_bytes(payload)
            except W.WireError:
                self.n_rejected += 1
                self.rejected.append(client_id)
                return False
        self.queue.append(TailRequest(client_id, payload,
                                      self.obs.tracer.wall_now()))
        return True

    def _span(self, name: str):
        return self.obs.tracer.span(name, tid=self.TRACK, cat="runtime")

    def _stage(self, slot: int, pkt: W.WirePacket) -> None:
        """Copy one parsed frame into ``slot``'s rows of its kind's
        staging, after checking its shape against the partition's."""
        kind = pkt.kind
        shape = (self.client_batch,) + self._feat
        if kind == "ae8":
            if self.part.ae is None:
                raise W.WireError("ae8 frame, but the partition has no "
                                  "bottleneck AE to decode it")
            shape = shape[:-1] + (self.part.ae["enc"]["w"].shape[-1],)
        if tuple(pkt.shape) != shape:
            raise W.WireError(f"frame layout {kind} {tuple(pkt.shape)} "
                              f"is not the pool's {kind} {shape}")
        st = self._staging.get(kind)
        if st is None:
            n = len(self.pool)
            rows = int(np.prod(shape[:-1]))
            st = self._staging[kind] = _Staging(
                shape, np.zeros((n * shape[0],) + shape[1:], pkt.data.dtype),
                None if kind == "f32" else np.zeros((n * rows, 1),
                                                    np.float32))
        b = shape[0]
        np.copyto(st.codes[slot * b:(slot + 1) * b], pkt.data)
        if st.scales is not None:
            r = len(pkt.scales)
            np.copyto(st.scales[slot * r:(slot + 1) * r], pkt.scales)

    def step(self, now: Optional[float] = None) -> dict:
        """Serve up to ``n_slots`` queued requests in one batched forward.

        Returns ``{client_id: logits}`` for the requests served this step.
        ``now`` (a virtual-clock timestamp) lets a fault plan's blackout
        windows apply: a step inside a window serves nothing.
        """
        if (self.faults is not None and now is not None
                and self.faults.blackout_at(now)):
            self.n_blackout_steps += 1
            return {}
        if not (self.queue or self.pool.any_active()):
            return {}
        obs = self.obs
        with self._span("server.step") as step_span:
            with self._span("server.admit"):
                t_admit = obs.tracer.wall_now()
                admitted = []
                while self.queue and self.pool.free_slots():
                    req = self.queue.popleft()
                    req.t_admit = t_admit
                    self.pool.admit(req)
                    admitted.append(req)
            active = self.pool.occupied()
            if obs.enabled:
                wait = obs.metrics.histogram("runtime.queue_wait_s")
                for req in admitted:
                    wait.observe(req.t_admit - req.t_submit)
                obs.metrics.gauge("runtime.queue_depth").set(len(self.queue))
                step_span.args.update(admitted=len(active),
                                      queued=len(self.queue))
            kinds = {}                   # slot -> payload kind
            with self._span("server.inputs"):
                for slot, req in active:
                    with self._span("server.frame") as frame_span:
                        if obs.enabled:
                            frame_span.args.update(rid=req.client_id,
                                                   slot=slot)
                        try:
                            with self._span("server.parse"):
                                pkt = W.from_bytes(req.payload)
                            with self._span("server.stage"):
                                self._stage(slot, pkt)
                        except W.WireError:
                            # the frame leaves the pool; the others stay
                            # admitted for the next step
                            self.pool.release(slot)
                            self.n_rejected += 1
                            self.rejected.append(req.client_id)
                            raise
                        kinds[slot] = pkt.kind
                # one transfer of each kind's pool; a copy, so the staging
                # can be rewritten while a device array of it lives
                boundary = {}
                for kind in dict.fromkeys(kinds.values()):
                    st = self._staging[kind]
                    with self._span("server.upload") as upload_span:
                        boundary[kind] = jax.device_put(
                            (st.codes, st.scales), may_alias=False)
                        if obs.enabled:
                            upload_span.args.update(
                                frames=sum(k == kind
                                           for k in kinds.values()),
                                bytes=st.nbytes)
            # one program per kind for the whole pool (shape is static:
            # n_slots * client_batch): decode prologue + the tail
            with self._span("server.tail"):
                logits = {k: self.part.served_tail(k)(bd)
                          for k, bd in boundary.items()}
            with self._span("server.fetch"):
                host = {k: np.asarray(v) for k, v in logits.items()}
        out = {}
        b = self.client_batch
        for slot, req in active:
            out[req.client_id] = host[kinds[slot]][slot * b:(slot + 1) * b]
            self.pool.release(slot)
        self.n_batches += 1
        self.n_served += len(active)
        self.occupancy.append(len(active))
        return out

    def drain(self) -> dict:
        """Step until the queue and pool are empty; merged results."""
        results = {}
        while self.queue or self.pool.any_active():
            results.update(self.step())
        return results


def run_clients(model, params, split_layer: int, client_inputs, *,
                ae: Optional[dict] = None, n_slots: int = 4,
                quantize: bool = True) -> tuple:
    """Convenience driver: N clients each run the head locally, their wire
    payloads are served by one TailServer.  Returns
    ``({client_id: logits}, server)``.
    """
    part = make_partition(model, params, split_layer, ae)
    xs = [jnp.asarray(x) for x in client_inputs]
    bsz = xs[0].shape[0]
    server = TailServer(part, n_slots=n_slots, client_batch=bsz)
    for cid, x in enumerate(xs):
        f = part.head(x)
        pkt = W.encode_activation(f, ae, quantize=quantize)
        server.submit(cid, W.to_bytes(pkt))
    return server.drain(), server
