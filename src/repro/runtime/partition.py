"""Executable stage chain of a :class:`LayeredModel` at a legal cut list.

This is the *live* counterpart of ``core.split``: where ``SplitPlan`` only
names a design point, a :class:`Partition` is a chain of K+1 jitted
callables that actually run the stages — the first on the "device"
process, the middle stages on intermediate tiers, the last on the
"server" process — with each inter-stage activation crossing between them
through the wire codec (``runtime.wire``).  Legality goes through
``core.split.validate_cuts`` so the runtime and the planner can never
disagree about which cut lists exist.

The historical 1-cut head/tail vocabulary is preserved exactly:
``head`` is stage 0 (layers ``[0, splits[0]]``) and ``tail`` is
everything after the first cut, so ``tail(head(x)) == apply(x)`` for any
number of cuts.

Every program takes its own layers' parameters (and the AEs of the hops
it encodes or decodes) as jit arguments, never as compiled-in constants:
a full-width VGG16 tail compiles in seconds and fits the persistent
compile cache.  The callables bind those weights themselves, held on the
device as the matmuls consume them (:func:`device_weights`).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bottleneck as B
from repro.core.split import validate_cuts
from repro.models.layered import LayeredModel
from repro.runtime import wire as W

# ``jax_default_matmul_precision`` values at which a TPU multiplies f32
# operands in one bf16 pass, rounding each operand to bf16 first
_ONE_BF16_PASS = (None, "default", "bfloat16", "BF16_BF16_F32")


def _rounds_to_bf16(platform: str, precision) -> bool:
    return platform == "tpu" and precision in _ONE_BF16_PASS


def device_weights(params: list, platform: str, precision) -> list:
    """The layer parameters as a matmul at ``precision`` (the value of
    ``jax_default_matmul_precision``) on ``platform`` consumes them.

    A TPU at one-pass bf16 precision rounds every f32 operand of a conv
    or dense matmul to bf16.  There each layer's f32 kernel (``"w"``) is
    returned rounded to bf16: a program reads it at two bytes, and casting
    it back to f32 inside the program is exact, so the MXU sees the
    operands it would have rounded itself.  Biases stay f32.  Where the
    precision does not round (the CPU, or ``"highest"``), ``params``
    comes back as it is."""
    if not _rounds_to_bf16(platform, precision):
        return params
    return _bf16_kernels(params)


@jax.jit
def _bf16_kernels(params: list) -> list:
    return [{k: v.astype(jnp.bfloat16)
             if k == "w" and v.dtype == jnp.float32 else v
             for k, v in p.items()} if isinstance(p, dict) else p
            for p in params]


def _is_single_ae(ae: dict) -> bool:
    """One bottleneck AE ({'enc': .., 'dec': ..}) vs a cut -> AE map."""
    return "enc" in ae and "dec" in ae


def _donate(argnum: int) -> tuple:
    """Donate a program's boundary input (argument ``argnum``, after
    the weights) where buffers can alias.  Donation is a no-op on hosts
    without buffer aliasing (CPU XLA warns and ignores it), so it is only
    requested where it can land."""
    return (argnum,) if jax.devices()[0].platform != "cpu" else ()


def _decode_pinned(kind: str, data, scales, ae: Optional[dict],
                   backend: Optional[str]):
    """Boundary decode as a stage prologue.  The barrier pins the codec
    subgraph: XLA may not fold the next stage's layers into the decode's
    float math, which keeps the decoded activation the eager byte path's
    (``wire._decode_jit`` compiles the same subgraph) to the bit."""
    return jax.lax.optimization_barrier(
        W.decode_arrays(kind, data, scales, ae, backend=backend))


class _Bound:
    """One of a partition's jitted programs with its weights bound:
    ``prog(*inputs)`` is ``fn(weights[a:b], aes, *inputs)``, the weights
    as the precision in force at the call consumes them."""

    __slots__ = ("fn", "part", "a", "b", "aes")

    def __init__(self, fn, part: "Partition", a: int, b: int, aes: tuple):
        self.fn, self.part, self.a, self.b, self.aes = fn, part, a, b, aes

    def __call__(self, *inputs):
        return self.fn(self.part._weights()[self.a:self.b], self.aes, *inputs)

    def lower(self, *inputs):
        """The program lowered for ``inputs`` (its weights as arguments)."""
        return self.fn.lower(self.part._weights()[self.a:self.b], self.aes,
                             *inputs)


@dataclass
class Partition:
    """Stage executables for an ordered cut list.

    ``split_layer`` accepts the historical scalar cut or a cut sequence;
    the normalised tuple lives in :attr:`splits` and the scalar field is
    rebound to the first (edge-side) cut.  ``stage(k)(x)`` runs stage k;
    ``head``/``tail`` keep the 1-cut vocabulary (stage 0 / everything
    after the first cut).  The bottleneck AEs (when present) live in the
    wire codec, not here — the partition is codec-agnostic so the same
    stage chain can ship f32, int8 or AE-compressed payloads.  ``ae`` may
    be a single AE dict (attached to the first cut) or a ``{cut: ae}``
    map; :attr:`ae_map` is the normalised form.
    """
    model: LayeredModel
    params: list
    split_layer: object              # int | ordered cut sequence
    ae: Optional[dict] = None
    _stages: list = field(default=None, repr=False)
    _tail: object = field(default=None, repr=False)
    _served: dict = field(default_factory=dict, repr=False)
    _dev: dict = field(default_factory=dict, repr=False)

    def __post_init__(self):
        self.splits = validate_cuts(self.model, self.split_layer)
        self.split_layer = self.splits[0]
        if self.ae is None:
            self.ae_map = {}
        elif _is_single_ae(self.ae):
            self.ae_map = {self.splits[0]: self.ae}
        else:
            self.ae_map = dict(self.ae)
            self.ae = self.ae_map.get(self.splits[0])
        self._platform = jax.devices()[0].platform
        self._dtypes = jax.tree.map(jnp.result_type, self.params)
        bounds = self._bounds()
        self._stages = [self._program(a, b)
                        for a, b in zip(bounds, bounds[1:])]
        self._tail = (self._stages[1] if len(self.splits) == 1 else
                      self._program(bounds[1], bounds[-1]))

    # ----------------------------------------------------------- weights ----
    def _bounds(self) -> tuple:
        return ((0,) + tuple(c + 1 for c in self.splits)
                + (len(self.model.layers),))

    def _weights(self) -> list:
        """The parameters as this call's matmuls consume them
        (:func:`device_weights`), put on the device once per rule."""
        prec = jax.config.jax_default_matmul_precision
        key = _rounds_to_bf16(self._platform, prec)
        w = self._dev.get(key)
        if w is None:
            w = self._dev[key] = jax.device_put(
                device_weights(self.params, self._platform, prec))
        return w

    def _program(self, a: int, b: int, aes: tuple = (), *,
                 decode=None) -> _Bound:
        """Layers ``[a, b)`` as one jitted program, bound to their weights
        and to ``aes``, with ``decode(boundary, aes)`` as its prologue when
        given (the boundary is then donated where it can be).  The jitted
        function is a lambda, so its device program is named
        ``jit__lambda``."""
        layers, dtypes = self.model.layers[a:b], self._dtypes[a:b]

        def run(w, aes, x):
            if decode is not None:
                x = decode(x, aes)
            for l, p, dt in zip(layers, w, dtypes):
                # the device copy back in the parameters' dtypes (exact)
                x = l.apply(jax.tree.map(lambda v, t: v.astype(t), p, dt), x)
            return x
        fn = jax.jit(lambda w, aes, x: run(w, aes, x),
                     donate_argnums=_donate(2) if decode else ())
        return _Bound(fn, self, a, b, aes)

    # ------------------------------------------------------------ stages ----
    @property
    def n_stages(self) -> int:
        return len(self.splits) + 1

    def stage(self, k: int):
        """The jitted stage-k callable (layers between cuts k-1 and k)."""
        return self._stages[k]

    def head(self, x: jax.Array) -> jax.Array:
        """Device side: layers [0, splits[0]] -> first boundary activation."""
        return self._stages[0](x)

    def tail(self, f: jax.Array) -> jax.Array:
        """Everything after the first cut: boundary activation -> logits."""
        return self._tail(f)

    def full(self, x: jax.Array) -> jax.Array:
        """Unsplit reference forward (equivalence oracle)."""
        return self.tail(self.head(x))

    def forward_stages(self, x: jax.Array) -> jax.Array:
        """Run the whole stage chain sequentially (no codec) — equal to
        :meth:`full` by construction; the multi-stage equivalence oracle."""
        for s in self._stages:
            x = s(x)
        return x

    # ------------------------------------------------------------ served ----
    def wire_kinds(self, quantize: bool = True) -> tuple:
        """Per-hop payload kind ('f32' | 'int8' | 'ae8') — static given
        the AE map and the quantize flag."""
        return tuple(W.wire_kind(self.ae_map.get(c), quantize)
                     for c in self.splits)

    def served_tail(self, kind: str):
        """The tail server's program for ``kind`` payloads:
        ``(data, scales) -> logits``, the hop-0 decode as its prologue
        then everything after the first cut.

        ``served_tail(kind)(boundary)`` equals ``tail`` of the eager
        decode (``wire.decode_activation``) for any number of cuts;
        ``data`` may stack many requests' payloads along its leading axis
        (``scales`` their rows in the same order), so one call decodes
        and serves a whole pool.  The boundary is donated where it can
        be; cached per kind.  A lambda like the stage jits, so its device
        program is named ``jit__lambda``.
        """
        if kind not in self._served:
            self._served[kind] = self._program(
                self.splits[0] + 1, len(self.model.layers), (self.ae,),
                decode=lambda bd, ae: _decode_pinned(kind, *bd, ae[0], None))
        return self._served[kind]

    # ------------------------------------------------------------ shapes ----
    def boundary_shape(self, batch: int = 1, hop: int = 0) -> tuple:
        """Activation shape crossing wire hop ``hop`` (with batch dim)."""
        return tuple(self.model.activation_shapes(
            self.params, batch)[self.splits[hop]])

    def describe(self) -> str:
        m = self.model
        if len(self.splits) == 1:
            return (f"{m.name}: head=[0..{self.split_layer}] "
                    f"tail=[{self.split_layer + 1}..{len(m.layers) - 1}]"
                    f"{' +ae' if self.ae is not None else ''}")
        bounds = self._bounds()
        stages = " | ".join(f"stage{i}=[{a}..{b - 1}]"
                            for i, (a, b) in enumerate(zip(bounds, bounds[1:])))
        aes = sorted(self.ae_map)
        return f"{m.name}: {stages}{' +ae@' + str(aes) if aes else ''}"


def make_partition(model: LayeredModel, params, split_layer,
                   ae: Optional[dict] = None) -> Partition:
    """Build (and legality-check) a runnable partition at one cut (int)
    or an ordered cut list (sequence)."""
    return Partition(model, params, split_layer, ae)


def head_with_encoder(part: Partition, x: jax.Array) -> jax.Array:
    """Paper-faithful edge stage: head layers + AE encoder (f32 latent).

    Thin wrapper over ``core.bottleneck.head_forward`` kept for parity
    checks between the runtime path and the simulator's SC forward.
    """
    return B.head_forward(part.model, part.params, part.ae,
                          part.split_layer, x)
