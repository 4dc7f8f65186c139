"""One run of one cell: set-up, the open-loop window, the check, the result.

Set-up makes the weights, the bottleneck and a pool of input images on the
device from the seed (one jitted call of the configuration's reference
module), builds the server as ``Study.deploy(serve=True)`` does
(``make_partition`` then ``TailServer``), runs each image's edge head and
ae8 encode at batch 1 and frames it with ``wire.to_bytes``, and warms up
a full and a partial step.

The window is one open loop in one thread: submit every request that is
due, call ``step()`` while anything is queued, otherwise sleep until the
next due time.  A request's latency runs from its due time to the return
of the ``step()`` that hands its logits to the host.  When the window
closes no more requests are offered; the backlog is drained, every
request due in the window gets an answer, and every answer is compared
with the reference (``compare.py``) once the program's state is freed.

A traced run (``trace=True``) profiles the window's first
``TRACE_SECONDS``, with the profiler started before the window opens.
Its per-layer metrics read that span less its first ``TRACE_SKIP``: the
host-clock ones from the steps in it, the device ones from the trace.
"""
from __future__ import annotations

import gc
import importlib
import shutil
import tempfile
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass
from typing import Optional

import numpy as np

from chipbench import compare, stats, trace_reduce
from chipbench.spec import Spec
from chipbench.traffic import Schedule, make_schedule

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 2.0
TRACE_SKIP = 0.25
REF_BLOCK = 16            # images per call of the reference


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def seed32(seed: int) -> int:
    """A 31-bit PRNG seed derived from any whole number."""
    return int(np.random.default_rng([seed, 0]).integers(0, 2 ** 31 - 1))


class CompileClock:
    """Sums XLA backend-compile seconds reported by JAX's monitoring."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def __call__(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


@contextmanager
def compile_clock():
    import jax
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        yield clock
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)


@dataclass
class Served:
    """The program under test and the data it serves."""
    server: object
    frames: list
    params: list
    ae: dict
    images: object


@dataclass
class Window:
    """What the open loop saw, on the window's clock (seconds from its
    start).  ``done`` is NaN for a request never answered."""
    done: np.ndarray
    submitted: np.ndarray
    served_by: np.ndarray
    step_begin: np.ndarray
    step_end: np.ndarray
    step_served: np.ndarray
    answers: dict
    trace_stop: Optional[float] = None    # end of the traced span
    trace_stall_s: float = 0.0            # spent stopping the profiler


@dataclass
class Record:
    """Everything a metric reader may read (``metrics/<name>.py``)."""
    cfg: dict
    traffic: dict
    schedule: Schedule
    window: Window
    setup_s: float
    peaks: Optional[dict] = None
    trace: Optional[trace_reduce.TraceSummary] = None

    @property
    def seconds(self) -> float:
        return self.schedule.seconds

    def host_steps(self) -> np.ndarray:
        """Mask of the steps the host-clock metrics read: those inside the
        window; in a traced run, those inside the traced span less its
        first ``TRACE_SKIP``."""
        w = self.window
        if w.trace_stop is None:
            return w.step_end <= self.seconds
        return (w.step_begin >= TRACE_SKIP) & (w.step_end <= w.trace_stop)


def _import(path: str):
    mod, _, attr = path.partition(":")
    return getattr(importlib.import_module(mod), attr)


def _check_layout(model, params):
    """The benchmark's weights must have exactly the program's layout."""
    import jax
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise ValueError("the configuration's weights do not match the "
                         "program's parameter layout")


def setup(spec: Spec, cfg: dict, seed: int) -> Served:
    import jax

    from repro.runtime import wire as W
    from repro.runtime.engine import TailServer
    from repro.runtime.partition import make_partition

    if cfg["client_batch"] != 1:
        raise ValueError("frames are made one image per request")
    ref = spec.reference(cfg)
    params, ae, images = jax.jit(lambda k: ref.make_inputs(cfg, k))(
        jax.random.PRNGKey(seed32(seed)))
    model = _import(cfg["builder"])(**{k: cfg[k] for k in cfg["builder_args"]})
    _check_layout(model, params)
    part = make_partition(model, params, cfg["cut"], ae)
    server = TailServer(part, n_slots=cfg["n_slots"],
                        client_batch=cfg["client_batch"])
    frames = [W.to_bytes(W.encode_activation(part.head(images[i:i + 1]), ae))
              for i in range(cfg["frame_pool"])]
    # warm up: a full step (every slot) and a partial one
    for k in range(cfg["n_slots"]):
        server.submit(-1 - k, frames[k % len(frames)])
    server.step()
    server.submit(-1, frames[0])
    server.step()
    return Served(server, frames, params, ae, images)


def serve_window(server, frames: list, sched: Schedule, *,
                 trace_dir: Optional[str] = None,
                 trace_seconds: float = TRACE_SECONDS) -> Window:
    """The open loop over one window, then the untimed drain.  With
    ``trace_dir`` the loop annotates its calls, and the profiler, started
    before the window opens, records its first ``trace_seconds`` into
    ``trace_dir``."""
    import jax

    due, pick, seconds = sched.due, sched.pick, sched.seconds
    n = len(due)
    done = np.full(n, np.nan)
    submitted = np.full(n, np.nan)
    served_by = np.full(n, -1, np.int64)
    begins, ends, counts = [], [], []
    answers = {}
    clock = time.perf_counter
    tracing = trace_dir is not None
    if tracing:
        ann = jax.profiler.TraceAnnotation
        trace_stop = min(trace_seconds, seconds)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    else:
        ann = lambda name: nullcontext()     # noqa: E731
        trace_stop = None
    stall = 0.0
    i = 0

    def submit_due(now):
        nonlocal i
        with ann("submit"):
            while i < n and due[i] <= now:
                server.submit(i, frames[pick[i]])
                submitted[i] = clock() - t0
                i += 1

    def step():
        tb = clock() - t0
        with ann("serve_step"):
            out = server.step()
        te = clock() - t0
        for rid, logits in out.items():
            answers[rid] = logits
            done[rid] = te
            served_by[rid] = len(begins)
        begins.append(tb)
        ends.append(te)
        counts.append(len(out))

    t0 = clock()
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        if tracing and now >= trace_stop:
            jax.profiler.stop_trace()
            stall, tracing = clock() - t0 - now, False
        if i < n and due[i] <= now:
            submit_due(now)
        if server.queue:
            step()
        else:
            nxt = min(due[i] if i < n else seconds, seconds)
            with ann("wait_arrivals"):
                dt = t0 + nxt - clock()
                if dt > 0:
                    time.sleep(dt)
    if tracing:
        jax.profiler.stop_trace()
    # drain: everything due in the window is offered and answered
    submit_due(float("inf"))
    while server.queue or server.pool.any_active():
        step()
    return Window(done, submitted, served_by, np.asarray(begins),
                  np.asarray(ends), np.asarray(counts), answers,
                  trace_stop, stall)


def reference_outputs(spec: Spec, cfg: dict, served: Served, *,
                      dtype=None) -> tuple:
    """The reference's ``(logits, codes, scales)`` for every image of the
    pool, computed in blocks of ``REF_BLOCK`` images."""
    import jax
    import jax.numpy as jnp

    ref = spec.reference(cfg)
    dtype = dtype or jnp.float32
    fwd = jax.jit(lambda p, a, x: ref.forward(cfg, p, a, x, dtype=dtype))
    out = [jax.device_get(fwd(served.params, served.ae,
                              served.images[b:b + REF_BLOCK]))
           for b in range(0, cfg["frame_pool"], REF_BLOCK)]
    return tuple(np.concatenate(parts) for parts in zip(*out))


def device_info(devices, chips: int) -> dict:
    peak = 0
    for d in devices[:chips]:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(spec: Spec, cell_name: str, seed: int, seconds: float,
             trace: bool, *, t_start: Optional[float] = None,
             require_tpu: bool = True, compile_cache: bool = True,
             log=print) -> dict:
    """One run; returns the result object (the last line's JSON)."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    cell = spec.cell(cell_name)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devices[0].platform!r})")
    if len(devices) < cell["chips"]:
        raise NoAccelerator(f"the cell needs {cell['chips']} chips, JAX "
                            f"found {len(devices)}")
    if compile_cache:
        from repro.launch.cache import enable_compile_cache
        enable_compile_cache()
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(cell_name)
    peaks = spec.peaks(devices[0].device_kind) if trace else None
    sched = make_schedule(traffic, seconds, seed, cfg["frame_pool"])

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        with compile_clock() as clock:
            served = setup(spec, cfg, seed)
            setup_s = time.perf_counter() - t_start
            setup_compiles = (clock.count, clock.seconds)
            # what set-up made stays out of the window's collections
            gc.collect()
            gc.freeze()
            window = serve_window(served.server, served.frames, sched,
                                  trace_dir=trace_dir)
            gc.unfreeze()
            window_compiles = clock.count - setup_compiles[0]
        summary = (trace_reduce.summarize_dir(trace_dir, skip_s=TRACE_SKIP)
                   if trace else None)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device = device_info(devices, cell["chips"])
    n_steps = len(window.step_begin)

    # the program's state goes before the reference runs
    served.server = None
    gc.collect()
    jax.clear_caches()
    ref_logits = reference_outputs(spec, cfg, served)[0]
    verdict = compare.judge(window.answers, sched.pick, ref_logits,
                            cfg["limits"])

    rec = Record(cfg, traffic, sched, window, setup_s, peaks, summary)
    metrics = {}
    for m in spec.metrics_for(cell_name, trace):
        v = spec.reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    lag = stats.percentile((window.submitted - sched.due) * 1e3, 95)
    log(f"setup_s={setup_s!r} setup_compiles={setup_compiles[0]} "
        f"setup_compile_s={setup_compiles[1]!r} window_compiles="
        f"{window_compiles} requests={len(sched)} steps={n_steps} "
        f"mean_served_per_step={len(window.answers) / max(n_steps, 1)!r} "
        f"generator_lag_ms_p95={lag!r} trace_stall_s={window.trace_stall_s!r}")
    result = {"correct": verdict["correct"], "attempted": len(sched),
              "failed": verdict["failed"], "metrics": metrics,
              "device": device}
    if summary is not None:
        totals = sorted(([f"idle in {k}", v] for k, v in
                         summary.idle_by_host.items()), key=lambda kv: -kv[1])
        longest = [[f"longest gap in {k}", v] for k, v in summary.longest_gaps]
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": (totals + longest)[:10]}
    result["window_compiles"] = window_compiles
    result["generator_lag_ms_p95"] = lag
    result["checks"] = verdict["checks"]
    return result
