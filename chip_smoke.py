#!/usr/bin/env python3
"""Chip smoke run: the paper's VGG16 split, served end to end on one TPU.

One chip (no arguments): the full-width ``vgg16()`` (138,357,544
parameters, 224x224x3, 1000 classes, random weights from ``--seed``)
goes through the entry points a user calls:

    Study -> profile -> candidates -> bottlenecks -> simulate -> suggest
          -> deploy() -> SplitRuntime.infer on the ae8 wire (Pallas codec)
          -> deploy(serve=True): a TailServer answering client requests

and every result is checked against a reference: the f32-wire split
against a plain float32 unsplit forward, the codec kernels against
``kernels/ref.py``, and the served logits against the single-client
runtime.

Four chips (``--chips 4``): only the path where the wire crosses devices,
``core.split.multipod_split_step`` on a (pod=2, data=2) mesh with
llama3.2-3b at its published widths cut to 4 layers, compared with the
single-program forward, first with no bottleneck, then with the int8 wire.

Everything runs in this one process: a chip belongs to one process.
Earlier lines report phases and checks; the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
Without a TPU, or when any check fails, the script exits non-zero and
prints no such line.

Run from the repository root:  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from contextlib import contextmanager

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

VGG16_PARAMS = 138_357_544
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# ---------------------------------------------------------------- tolerances
# Every check compares a relative error, ||got - want|| / ||want||, unless
# it says otherwise.  The stage programs run at the TPU's default matmul
# precision: each conv and matmul rounds its f32 operands to bf16 (unit
# roundoff 2^-9) and accumulates in f32.  The Pallas codec kernels do the
# same: Mosaic's default contraction of f32 blocks is not f32 (against an
# f32 reference on a TPU v5e the kernel's row scales differed by 1.1e-3 and
# 4.8% of its codes moved, up to 2 steps).  Programs compiled for different
# batch sizes may round different intermediates, so checks that compare
# two programs of the same layers run both under
# jax.default_matmul_precision("highest") (f32 to within summation order).
# For scale: the logits of two different inputs differ by far more than
# any tolerance here (printed as "logit spread").

# f32-wire split vs the unsplit forward at "highest": the split adds no
# arithmetic, so the gap is the stages' bf16 operand rounding across
# VGG16's 16 weight layers, about sqrt(16) * 2^-9 = 0.8% if the layers'
# errors add at random.
TOL_SPLIT_VS_UNSPLIT = 3e-2
# Kernel vs reference codec on one boundary activation, the reference at
# the default precision the kernel computes in: both round the operands to
# bf16 and accumulate in f32, so they differ only in summation order
# (~1e-6 relative); a code can move by one step only where z / scale lies
# that close to a rounding boundary, and the row scales (row amax / 127)
# agree to that order.
TOL_CODE_STEP = 1
TOL_CODE_FLIP_FRACTION = 1e-3
TOL_SCALE = 1e-4
# Logits after the kernel decode vs after the reference decode of the same
# codes, one tail program: the decoded activations differ by ~1e-6; the
# tail's bf16 operand rounding can turn that into one bf16 step (2^-8) of
# a rare element.
TOL_KERNEL_DECODE_LOGITS = 1e-3
# Served vs single-client logits, both at "highest": identical frames and
# decode; the tail runs at batch n_slots in the server and 1 in the
# runtime, so only the f32 summation order may differ.
TOL_SERVED_VS_SINGLE = 1e-4
# Multi-pod pipeline vs the single program, both at "highest": the same f32
# arithmetic in another summation order.
TOL_PIPELINE_F32 = 1e-4
# ... with the int8 wire at the cut: as above, plus codes that move by one
# step where the two programs' f32 residual streams straddle a rounding
# boundary (1/127 of a row's amax each).
TOL_PIPELINE_INT8 = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Records each comparison with its tolerance and reason."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, value: float, tol: float, reason: str):
        ok = bool(value <= tol)          # NaN fails
        log(f"check {name}: {value!r} <= {tol!r} "
            f"{'ok' if ok else 'FAILED'} ({reason})")
        if not ok:
            self.failed.append(name)

    def require(self, name: str, ok: bool, detail: str = ""):
        log(f"check {name}: {'ok' if ok else 'FAILED'} {detail}".rstrip())
        if not ok:
            self.failed.append(name)


class CompileClock:
    """Sums XLA backend-compile seconds reported by JAX's monitoring."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def __call__(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


@contextmanager
def phase(clock: CompileClock, name: str):
    s0, n0, t0 = clock.seconds, clock.count, time.perf_counter()
    yield
    log(f"phase {name}: wall_s={time.perf_counter() - t0!r} "
        f"compile_s={clock.seconds - s0!r} programs={clock.count - n0}")


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), np.finfo(np.float64).tiny))


def all_finite(*arrays) -> bool:
    import numpy as np
    return all(bool(np.isfinite(np.asarray(a)).all()) for a in arrays)


# ------------------------------------------------------------- one chip ----
def run_single_chip(model, clock: CompileClock, checks: Checks, *,
                    batch: int = 8, seed: int = 0, ae_steps: int = 3,
                    n_requests: int = 8, n_slots: int = 4,
                    expect_params=None) -> None:
    """The served split path of ``model`` through its entry points."""
    import jax
    import numpy as np

    from repro.api import QoSRequirements, Study
    from repro.kernels import ref
    from repro.kernels.bottleneck_compress import resolve_backend
    from repro.models.vgg import n_params
    from repro.runtime import wire as W
    from repro.runtime.engine import SplitRuntime

    backend = resolve_backend()
    checks.require("codec backend is the Pallas kernel", backend == "kernel",
                   f"(resolve_backend() -> {backend!r})")
    if backend != "kernel":
        raise SystemExit("chip_smoke: the codec would not run the Pallas "
                         "kernels; unset REPRO_BOTTLENECK_BACKEND")

    with phase(clock, "study"):
        study = Study(model, batch=batch, seed=seed)
        jax.block_until_ready(study.params)
    n = n_params(study.model, study.params)
    log(f"model {study.model.name}: params={n} input={study.model.input_shape}"
        f" classes={study.model.n_classes} batch={batch}")
    if expect_params is not None:
        checks.require("parameter count", n == expect_params,
                       f"({n} vs {expect_params})")

    with phase(clock, "profile"):
        study.profile()
    with phase(clock, "candidates"):
        study.candidates()
    log("candidates: " + ", ".join(
        f"{c.label}(cs={c.accuracy_proxy:.4f})" for c in study.candidate_list))
    with phase(clock, "bottlenecks"):
        study.bottlenecks(steps=ae_steps)
    with phase(clock, "simulate"):
        study.simulate()
    qos = QoSRequirements(max_latency_s=1.0)
    with phase(clock, "suggest"):
        best = study.suggest(qos)
    for v in study.verdicts:
        log(f"verdict {v.candidate.label}: latency_s={v.latency_s!r} "
            f"accuracy={v.accuracy!r}")
    if best is not None and best.candidate.kind == "SC":
        candidate = None                              # deploy the suggestion
        label = best.candidate.label
        log(f"suggested {label}: deploying it")
    else:
        label = study.split_candidates()[0].label
        candidate = label
        log(f"suggested {best.candidate.label if best else None}: nothing to "
            f"split, deploying the best-ranked SC candidate {label} by name")

    x = np.random.default_rng(seed + 1).standard_normal(
        (batch,) + tuple(study.model.input_shape)).astype(np.float32)

    # --- deploy + infer on the ae8 wire -----------------------------------
    with phase(clock, "deploy"):
        rt = study.deploy(candidate)
    part = rt.part
    cut, ae = part.split_layer, part.ae
    log(f"cut {label}: {part.describe()} wire={part.wire_kinds()} "
        f"boundary={part.boundary_shape(batch)}")
    checks.require("wire is ae8", part.wire_kinds() == ("ae8",),
                   f"({part.wire_kinds()})")
    with phase(clock, "infer ae8"):
        res = rt.infer(x, iters=2)
    hop = res.hops[0]
    log(f"infer ae8: wire_bytes={res.wire_bytes} stage_s={list(res.stage_s)}"
        f" encode_s={hop['encode_s']!r} decode_s={hop['decode_s']!r}"
        f" transfer_s={hop['transfer_s']!r} (host clock)")
    checks.require("ae8 logits finite, shape", all_finite(res.logits)
                   and res.logits.shape == (batch, study.model.n_classes),
                   f"({res.logits.shape})")

    # --- the codec programs take the kernel route -------------------------
    with phase(clock, "codec kernels vs reference"):
        f = part.head(x)
        enc_text = jax.jit(lambda v, a: W.encode_arrays(v, a)).lower(
            f, ae).compile().as_text()
        q, s = W.encode_arrays(f, ae)
        dec_text = jax.jit(lambda d, sc, a: W.decode_arrays("ae8", d, sc, a)
                           ).lower(q, s, ae).compile().as_text()
        checks.require("encode program holds tpu_custom_call",
                       "tpu_custom_call" in enc_text)
        checks.require("decode program holds tpu_custom_call",
                       "tpu_custom_call" in dec_text)
        c, l = f.shape[-1], q.shape[-1]
        q_k = np.asarray(q, np.int32).reshape(-1, l)
        f2, enc, dec = f.reshape(-1, c), ae["enc"], ae["dec"]
        def against(precision):
            with jax.default_matmul_precision(precision):
                q_ref, s_ref = jax.jit(ref.bottleneck_compress_ref)(
                    f2, enc["w"], enc["b"])
            dq = np.abs(q_k - np.asarray(q_ref, np.int32))
            log(f"kernel codec vs {precision}-precision reference: codes "
                f"max step {int(dq.max())}, moved {float((dq > 0).mean())!r};"
                f" row scales rel_err {rel_err(s, s_ref)!r}")
            return dq, rel_err(s, s_ref)

        against("highest")               # printed only: an f32 contraction
        dq, scale_err = against("default")
        checks("kernel codes max step", int(dq.max()), TOL_CODE_STEP,
               "same bf16 operands; one step at a rounding tie")
        checks("kernel codes moved fraction", float((dq > 0).mean()),
               TOL_CODE_FLIP_FRACTION, "ties are rare at ~1e-6 relative")
        checks("kernel row scales", scale_err, TOL_SCALE,
               "row amax, summation order only")
        f_ref = jax.jit(ref.bottleneck_decode_ref)(
            q.reshape(-1, l), s, dec["w"], dec["b"])
        logits_k = part.tail(W.decode_arrays("ae8", q, s, ae))
        logits_r = part.tail(f_ref.reshape(f.shape[:-1] + (c,)))
        checks("logits after kernel decode vs reference decode",
               rel_err(logits_k, logits_r), TOL_KERNEL_DECODE_LOGITS,
               "same codes; decode differs ~1e-6 before bf16 tail rounding")

    # --- split == unsplit on an f32 wire ----------------------------------
    with phase(clock, "f32 split vs unsplit"):
        rt32 = SplitRuntime(study.model, study.params, cut, quantize=False)
        out32 = rt32.infer(x, iters=1).logits
        with jax.default_matmul_precision("highest"):
            want = jax.jit(study.model.apply)(study.params, x)
        kinds = rt32.part.wire_kinds(rt32.quantize)
        checks.require("f32 wire", kinds == ("f32",), f"({kinds})")
        log(f"logit spread: rel_err between different inputs' unsplit "
            f"logits {rel_err(np.roll(want, 1, axis=0), want)!r}")
        checks("f32-wire split vs float32 unsplit forward",
               rel_err(out32, want), TOL_SPLIT_VS_UNSPLIT,
               "stages at default precision round operands to bf16")
        log(f"ae8 logits vs unsplit: rel_err={rel_err(res.logits, want)!r} "
            f"(the AE had {ae_steps} training steps; not checked)")

    # --- TailServer: edge heads -> frames -> one batched tail -------------
    # (at "highest", so that the server's batch-n_slots tail and the
    # runtime's batch-1 tail compute the same f32 values; see tolerances)
    highest = jax.default_matmul_precision("highest")
    with phase(clock, "serve"), highest:
        server = study.deploy(candidate, serve=True, n_slots=n_slots)
        sp = server.part
        clients = [x[i % batch][None] for i in range(n_requests)]
        for cid, xc in enumerate(clients):
            frame = W.to_bytes(W.encode_activation(sp.head(xc), sp.ae))
            server.submit(cid, frame)
        served = server.drain()
    log(f"serve: requests={n_requests} served={server.n_served} "
        f"batches={server.n_batches} occupancy={server.occupancy} "
        f"frame_bytes={len(frame)}")
    checks.require("every request answered",
                   sorted(served) == list(range(n_requests))
                   and server.n_served == n_requests)
    with phase(clock, "single-client runtime"), highest:
        worst, bitwise = 0.0, True
        for cid, xc in enumerate(clients):
            single = rt.infer(xc, iters=1).logits
            worst = max(worst, rel_err(served[cid], single))
            bitwise &= bool(np.array_equal(served[cid], single))
    log(f"served vs single bit-identical: {bitwise}; logit spread between "
        f"clients 0 and 1: {rel_err(served[1], served[0])!r}")
    checks("served vs single-client logits (worst client)", worst,
           TOL_SERVED_VS_SINGLE, "same frames; only the tail batch differs")


# ----------------------------------------------------------- four chips ----
def _reference_logits(params, cfg, tokens, ae):
    """Single-program forward applying the pipeline's int8 wire codec at
    the same cut (after the first half of the blocks)."""
    import jax
    import jax.numpy as jnp

    from repro.core import bottleneck as B
    from repro.models import transformer as T

    descs, n_groups = T.block_structure(cfg)
    x, positions, _ = T.embed_inputs(params, cfg, {"tokens": tokens})
    for g in range(n_groups):
        if g == n_groups // 2:
            q, s = B.encode_wire(ae, x.astype(jnp.float32))
            x = B.decode_wire(ae, q, s).astype(x.dtype)
        lp = jax.tree.map(lambda a, g=g: a[g], params["layers"]["l0"])
        x, _, _ = T.apply_layer_seq(lp, descs[0], x, cfg, positions,
                                    causal=True, window=cfg.sliding_window)
    x = T._apply_norm(params["final_norm"], x, cfg)
    return T.logits_from_x(params, cfg, x)


def run_multipod(devices, cfg, clock: CompileClock, checks: Checks, *,
                 batch: int = 8, seq: int = 16, n_micro: int = 4,
                 seed: int = 0) -> None:
    """``multipod_split_step`` on a (pod=2, data=2) mesh of ``devices``."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import bottleneck as B
    from repro.core.split import multipod_split_step
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as T

    descs, n_groups = T.block_structure(cfg)
    log(f"config {cfg.name}: d_model={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab} "
        f"layers={n_groups} dtype={cfg.dtype}")
    with phase(clock, "init"):
        mesh = make_mesh((2, len(devices) // 2), ("pod", "data"),
                         devices=devices)
        params = T.init_params(jax.random.PRNGKey(seed), cfg)
        ae = B.init_bottleneck(jax.random.PRNGKey(seed + 2), (cfg.d_model,),
                               rate=0.5)
        tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                    (batch, seq), 0, cfg.vocab)
        # the stage split is the group axis cut in two: pod p holds groups
        # [p * G/2, (p+1) * G/2) -- place them there before the step runs
        placed = dict(params)
        placed["layers"] = jax.device_put(params["layers"],
                                          NamedSharding(mesh, P("pod")))
        rep = NamedSharding(mesh, P())
        for k in params:
            if k != "layers":
                placed[k] = jax.device_put(params[k], rep)
        ae_placed = jax.device_put(ae, rep)
        jax.block_until_ready((placed, ae_placed))
    leaf = jax.tree.leaves(placed["layers"])[0]
    per_pod = {}
    for shard in leaf.addressable_shards:
        first = shard.index[0].start or 0
        per_pod.setdefault(first // (n_groups // 2), set()).add(
            (shard.device.id, f"layers[{first}:{shard.index[0].stop}]"))
    for pod in sorted(per_pod):
        held = sorted(per_pod[pod])
        log(f"stage {pod} (pod {pod}): devices {[d for d, _ in held]} hold "
            f"{held[0][1]}")
    checks.require("stages on distinct devices",
                   len(per_pod) == 2 and not ({d for d, _ in per_pod[0]}
                                              & {d for d, _ in per_pod[1]}))

    def step(p, a, toks):
        return multipod_split_step(p, cfg, {"tokens": toks}, mesh, ae=a,
                                   n_micro=n_micro,
                                   quantize_wire=a is not None)

    with jax.default_matmul_precision("highest"):
        with phase(clock, "pipeline f32 wire"):
            got = np.asarray(jax.jit(lambda p, t: step(p, None, t))(
                placed, tokens))
        with phase(clock, "single-program forward"):
            want = np.asarray(jax.jit(lambda p, t: T.logits_from_x(
                p, cfg, T.forward(p, cfg, {"tokens": t})["x"]))(params, tokens))
        with phase(clock, "pipeline int8 wire"):
            got8 = np.asarray(jax.jit(step)(placed, ae_placed, tokens))
        with phase(clock, "single-program forward int8 wire"):
            want8 = np.asarray(jax.jit(lambda p, a, t: _reference_logits(
                p, cfg, t, a))(params, ae, tokens))
    checks.require("pipeline logits finite, shape", all_finite(got, got8)
                   and got.shape == (batch, seq, cfg.vocab), f"({got.shape})")
    checks("pipeline (no bottleneck) vs T.forward", rel_err(got, want),
           TOL_PIPELINE_F32, "f32 at highest; summation order only")
    checks("pipeline int8 wire vs single program with the same codec",
           rel_err(got8, want8), TOL_PIPELINE_INT8,
           "f32 at highest; a code may move one step at a tie")


# ----------------------------------------------------------------- main ----
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served VGG16 path; 4: only the multi-pod "
                         "pipeline across four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ae-steps", type=int, default=3,
                    help="bottleneck training steps per SC candidate")
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()          # a TPU that fails to start raises here
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found: JAX's first device is "
                         f"{dev.platform!r}; this check runs only on a TPU")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} TPU devices, found {len(devices)}")
    log(f"devices: {[(d.id, d.device_kind) for d in devices]} "
        f"jax={jax.__version__} compile_cache={cache_dir}")

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    checks = Checks()
    t0 = time.perf_counter()
    if args.chips == 4:
        from repro.configs import get_config
        cfg = dataclasses.replace(get_config("llama3.2-3b"), n_layers=4,
                                  dtype="float32")
        run_multipod(devices[:4], cfg, clock, checks, seed=args.seed)
    else:
        from repro.models.vgg import vgg16
        run_single_chip(vgg16(), clock, checks, seed=args.seed,
                        ae_steps=args.ae_steps, expect_params=VGG16_PARAMS)
    log(f"total: wall_s={time.perf_counter() - t0!r} "
        f"compile_s={clock.seconds!r} programs={clock.count}")
    if checks.failed:
        raise SystemExit(f"chip_smoke: {len(checks.failed)} check(s) failed: "
                         f"{checks.failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
