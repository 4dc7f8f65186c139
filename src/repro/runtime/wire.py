"""The split-point wire format: what actually crosses the network.

Three payload kinds, all self-describing byte strings (header + payload)
so the tail server can decode without out-of-band shape agreement:

* ``f32``  — raw float32 activation (debug / exactness oracle);
* ``int8`` — symmetric per-row int8 quantisation of the raw activation
             (+ one f32 scale per row), no AE;
* ``ae8``  — bottleneck-AE encoder projection fused with the int8
             quantisation — the Pallas ``bottleneck_compress`` path,
             routed through the pure-JAX reference on hosts without a TPU
             (``kernels.bottleneck_compress.resolve_backend``).

Decoding reverses the chain on the server: parse -> dequantise -> (AE
decoder) -> boundary activation for ``Partition.tail``.

The codec exists at two altitudes:

* **array layer** (:func:`encode_arrays` / :func:`decode_arrays`) —
  pure-JAX, jittable transforms between the boundary activation and the
  device-resident wire tensors ``(data, scales)``.  The byte layer runs
  them jitted, and ``Partition.served_tail`` runs the decode as the
  prologue of the tail program (the AE passed as an argument).
* **byte layer** (:func:`frame_arrays` / :func:`to_bytes` /
  :func:`from_bytes`) — the self-describing framing.  ``frame_arrays``
  writes the header *around* the codec's int8 + scales output (one
  ``b"".join`` over buffer views, no intermediate numpy copies), and
  ``from_bytes`` parses into views over the received buffer.

Two frame versions share one parser:

* ``SEI1`` — the original header (magic | kind u8 | ndim u8 | dims
  u32*).  The default everywhere; byte streams are bit-identical to
  what earlier revisions shipped.
* ``SEI2`` — the checksummed header (``checksum=True``): identical
  layout plus two u32 CRC32s (data, scales) between the dims and the
  payload, so in-flight corruption is *detected* — a typed
  :class:`WireError`, never a garbage decode.  The fault-injection
  runtime ships SEI2 on faulted paths only.

Every malformed input — bad magic, unknown kind, truncation at any
field boundary, CRC mismatch — raises :class:`WireError` (a
``ValueError``) naming the offset it died at.
"""
from __future__ import annotations

import functools
import struct
import zlib
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bottleneck_compress import bottleneck_compress_any
from repro.kernels.bottleneck_decompress import bottleneck_decompress_any

MAGIC = b"SEI1"
MAGIC2 = b"SEI2"   # checksummed frames: dims are followed by 2 u32 CRC32s
_KINDS = ("f32", "int8", "ae8")

_KIND_DTYPE = {"f32": np.float32, "int8": np.int8, "ae8": np.int8}


class WireError(ValueError):
    """Malformed or corrupted wire bytes: bad magic, unknown kind,
    truncation at a field boundary, or a CRC32 mismatch.  Subclasses
    ``ValueError`` so pre-existing ``except ValueError`` sites keep
    working; the message carries the offset where parsing failed."""


def wire_kind(ae: Optional[dict], quantize: bool = True) -> str:
    """The payload kind one hop ships: 'ae8' when the cut has an AE,
    else 'int8' ('f32' with ``quantize=False``)."""
    if ae is not None:
        return "ae8"
    return "int8" if quantize else "f32"


@dataclass(frozen=True)
class WirePacket:
    """Decoded in-memory form of one wire transfer."""
    kind: str                        # 'f32' | 'int8' | 'ae8'
    shape: tuple                     # payload tensor shape (B, *spatial, L)
    data: np.ndarray                 # f32 (kind f32) or int8 codes
    scales: Optional[np.ndarray]     # f32 (N, 1) row scales (int8 kinds)
    checksum: bool = False           # SEI2 frame (per-array CRC32s)

    @property
    def nbytes(self) -> int:
        """Serialized size: header (6 + 4*ndim [+ 8 CRC]) + payload
        [+ scales]."""
        n = 6 + 4 * len(self.shape) + (8 if self.checksum else 0)
        n += self.data.nbytes
        return n + (self.scales.nbytes if self.scales is not None else 0)


# ------------------------------------------------------------ array layer ----
def encode_arrays(f: jax.Array, ae: Optional[dict] = None, *,
                  quantize: bool = True,
                  backend: Optional[str] = None) -> tuple:
    """Jittable edge-side codec core: activation -> ``(data, scales)``.

    The wire tensors stay on device: int8 codes + f32 ``(N, 1)`` row
    scales for the quantised kinds, ``(f32 data, None)`` for 'f32'.  The
    kind itself is a static function of ``(ae, quantize)`` —
    :func:`wire_kind` — so a jitted closure over fixed ``ae`` traces one
    payload layout.
    """
    if ae is not None:
        q, s = bottleneck_compress_any(
            jnp.asarray(f, jnp.float32), ae["enc"]["w"], ae["enc"]["b"],
            backend=backend)
        return q, s.reshape(-1, 1)
    if not quantize:
        return jnp.asarray(f, jnp.float32), None
    q, s = _quantize_rows(jnp.asarray(f, jnp.float32))
    return q, s.reshape(-1, 1)


def decode_arrays(kind: str, data: jax.Array, scales: Optional[jax.Array],
                  ae: Optional[dict] = None, *,
                  backend: Optional[str] = None) -> jax.Array:
    """Jittable server-side codec core: ``(data, scales)`` -> activation.

    'ae8' routes dequantise + AE-decoder through the fused
    ``bottleneck_decompress`` kernel path (pure-JAX reference off-TPU),
    so composing this with the next stage's layers under one ``jit``
    keeps the f32 latent in VMEM.
    """
    if kind == "f32":
        return jnp.asarray(data)
    shape = tuple(data.shape)
    if kind == "ae8":
        if ae is None:
            raise ValueError("ae8 payload needs the bottleneck AE to decode")
        return bottleneck_decompress_any(
            jnp.asarray(data), jnp.asarray(scales).reshape(-1, 1),
            ae["dec"]["w"], ae["dec"]["b"], backend=backend)
    z = (jnp.asarray(data).reshape(-1, shape[-1]).astype(jnp.float32)
         * jnp.asarray(scales).reshape(-1, 1))
    return z.reshape(shape)


# The byte path runs the codec math compiled, as ``Partition.served_tail``
# runs its decode prologue: op-by-op dispatch and XLA compile constant
# divisions differently (1-ulp scale drift), so the eager decode matches
# the served tail's to the bit only through one jitted core.  ``ae`` is a
# pytree argument (no retrace per table entry); ``quantize``/``backend``
# are static.
@functools.partial(jax.jit, static_argnames=("quantize", "backend"))
def _encode_jit(f, ae, *, quantize: bool, backend: Optional[str]):
    return encode_arrays(f, ae, quantize=quantize, backend=backend)


@functools.partial(jax.jit, static_argnames=("kind", "backend"))
def _decode_jit(kind: str, data, scales, ae, *, backend: Optional[str]):
    return decode_arrays(kind, data, scales, ae, backend=backend)


# ----------------------------------------------------------- encode side ----
def encode_activation(f: jax.Array, ae: Optional[dict] = None, *,
                      quantize: bool = True) -> WirePacket:
    """Edge-side codec: boundary activation -> wire packet.

    ``ae`` present: AE-encoder + int8 (kind ``ae8``, the compressed wire of
    paper §III with DESIGN.md §3's quantisation).  ``ae`` absent: raw int8
    (kind ``int8``) or raw f32 when ``quantize=False``.
    """
    kind = wire_kind(ae, quantize)
    data, scales = _encode_jit(f, ae, quantize=quantize, backend=None)
    return WirePacket(kind, tuple(data.shape), np.asarray(data),
                      None if scales is None else np.asarray(scales))


def _quantize_rows(f: jax.Array, scale: float = 127.0) -> tuple:
    """Symmetric per-row int8 over the channel axis (no projection).

    Returns ``(q int8 shaped like f, scales f32 (N, 1))``.
    """
    f2 = f.reshape(-1, f.shape[-1])
    amax = jnp.max(jnp.abs(f2), axis=-1, keepdims=True)
    s = jnp.where(amax > 0, amax / scale, 1.0)
    q = jnp.clip(jnp.round(f2 / s), -127, 127).astype(jnp.int8)
    return q.reshape(f.shape), s


# ----------------------------------------------------------- byte format ----
def _header(kind: str, shape: tuple, *, crcs: Optional[tuple] = None) -> bytes:
    magic = MAGIC if crcs is None else MAGIC2
    head = magic + struct.pack("<BB", _KINDS.index(kind), len(shape))
    head += struct.pack(f"<{len(shape)}I", *shape)
    if crcs is not None:
        head += struct.pack("<II", *crcs)
    return head


def _buffer_view(a, dtype) -> memoryview:
    """A C-contiguous byte view over ``a`` without copying when possible
    (device arrays on CPU backends and contiguous numpy arrays alias).
    Viewing through ``uint8`` keeps the empty array (a batch of 0) legal,
    where ``memoryview.cast`` refuses a zero in the shape."""
    arr = np.ascontiguousarray(np.asarray(a, dtype))
    return memoryview(arr.reshape(-1).view(np.uint8))


def frame_arrays(kind: str, data, scales=None, *, checksum: bool = False) -> bytes:
    """Zero-copy framing of the codec's wire tensors.

    Writes the self-describing header *around* the kernel's
    ``(data, scales)`` output: the only copy is the single ``join`` into
    the outgoing buffer — no intermediate ``WirePacket`` and no numpy
    detour.  ``to_bytes(encode_activation(f, ...))`` and
    ``frame_arrays(kind, *encode_arrays(f, ...))`` produce identical
    bytes.

    ``checksum=True`` emits an SEI2 frame: two u32 CRC32s (data, scales
    — 0 when there are no scales) follow the dims, so the receiver can
    reject in-flight corruption.  The default stays SEI1, bit-identical
    to the historical framing.
    """
    dview = _buffer_view(data, _KIND_DTYPE[kind])
    sview = None if scales is None else _buffer_view(scales, np.float32)
    crcs = None
    if checksum:
        crcs = (zlib.crc32(dview), 0 if sview is None else zlib.crc32(sview))
    parts = [_header(kind, tuple(data.shape), crcs=crcs), dview]
    if sview is not None:
        parts.append(sview)
    return b"".join(parts)


def to_bytes(pkt: WirePacket, *, checksum: Optional[bool] = None) -> bytes:
    """Serialise: MAGIC | kind u8 | ndim u8 | dims u32* [| crc u32 x2]
    | payload [| scales].  ``checksum`` defaults to the packet's own
    flag (``False`` for packets built by :func:`encode_activation`)."""
    if checksum is None:
        checksum = pkt.checksum
    return frame_arrays(pkt.kind, pkt.data, pkt.scales, checksum=checksum)


def _need(buf, end: int, what: str, off: int):
    if len(buf) < end:
        raise WireError(
            f"truncated frame: {what} at offset {off} needs {end} bytes, "
            f"buffer has {len(buf)}")


def from_bytes(buf: bytes) -> WirePacket:
    """Parse one frame (either version).  Raises :class:`WireError` on
    bad magic, unknown kind id, truncation at any field boundary, or —
    for SEI2 frames — a per-array CRC32 mismatch."""
    magic = bytes(buf[:4])
    if magic not in (MAGIC, MAGIC2):
        raise WireError("not a split-wire payload (bad magic)")
    checksum = magic == MAGIC2
    _need(buf, 6, "kind/ndim header", 4)
    kind_id, ndim = struct.unpack_from("<BB", buf, 4)
    if kind_id >= len(_KINDS):
        raise WireError(f"unknown wire kind id {kind_id} at offset 4")
    kind = _KINDS[kind_id]
    _need(buf, 6 + 4 * ndim, f"{ndim} u32 dims", 6)
    shape = struct.unpack_from(f"<{ndim}I", buf, 6)
    off = 6 + 4 * ndim
    crcs = None
    if checksum:
        _need(buf, off + 8, "CRC32 pair", off)
        crcs = struct.unpack_from("<II", buf, off)
        off += 8
    n_elems = int(np.prod(shape, dtype=np.int64))
    itemsize = np.dtype(_KIND_DTYPE[kind]).itemsize
    _need(buf, off + n_elems * itemsize, f"{kind} payload", off)
    data = np.frombuffer(buf, _KIND_DTYPE[kind], n_elems, off).reshape(shape)
    if crcs is not None and zlib.crc32(buf[off:off + n_elems * itemsize]) \
            != crcs[0]:
        raise WireError(f"CRC mismatch in data array at offset {off}")
    if kind == "f32":
        return WirePacket(kind, shape, data, None, checksum)
    s_off = off + n_elems * itemsize
    n_rows = n_elems // shape[-1] if ndim and shape[-1] else 0
    _need(buf, s_off + 4 * n_rows, f"{n_rows} f32 row scales", s_off)
    scales = np.frombuffer(buf, np.float32, n_rows,
                           s_off).reshape(n_rows, 1)
    if crcs is not None and zlib.crc32(buf[s_off:s_off + 4 * n_rows]) \
            != crcs[1]:
        raise WireError(f"CRC mismatch in scales array at offset {s_off}")
    return WirePacket(kind, shape, data, scales, checksum)


# ----------------------------------------------------------- decode side ----
def decode_activation(pkt: WirePacket, ae: Optional[dict] = None,
                      corrupt_mask: Optional[np.ndarray] = None) -> jax.Array:
    """Server-side codec: wire packet -> boundary activation.

    ``corrupt_mask`` (flat, 1=keep) zeroes lost UDP chunks *on the wire
    representation* before dequantisation — same receiver semantics as
    ``netsim.simulator.chunk_mask_from_packets``.
    """
    data = pkt.data
    if corrupt_mask is not None:
        data = data * corrupt_mask.reshape(data.shape).astype(data.dtype)
    if pkt.kind == "ae8" and ae is None:
        raise ValueError("ae8 payload needs the bottleneck AE to decode")
    return _decode_jit(pkt.kind, jnp.asarray(data),
                       None if pkt.scales is None else jnp.asarray(pkt.scales),
                       ae, backend=None)


def roundtrip(f: jax.Array, ae: Optional[dict] = None, *,
              quantize: bool = True) -> jax.Array:
    """encode -> bytes -> parse -> decode (the full wire path, no network)."""
    return decode_activation(
        from_bytes(to_bytes(encode_activation(f, ae, quantize=quantize))), ae)
