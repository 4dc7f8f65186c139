"""Operations and bytes of the served path, from a configuration's shapes.

The per-layer table follows the program's VGG builder layer for layer
(conv and ReLU pairs, 2x2 max-pools, flatten, three linear layers with
ReLUs between them), so a cut after layer ``i`` means the same layers
here as in the program.  Mult-adds use the conv/linear rules of the
program's ``core/stats.py``: a conv costs ``h * w * kh * kw * cin * cout``
per image, a linear layer ``fin * fout``.  One mult-add is two
operations.  Bytes are the least any schedule must move: each weight once,
the stage's input and its output, in the configuration's dtype.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

F32 = 4
BF16 = 2


@dataclass(frozen=True)
class LayerRow:
    kind: str                 # conv | relu | pool | flatten | linear
    out_shape: tuple          # per image, channels last
    macs: int                 # per image
    params: dict = field(default_factory=dict)   # leaf name -> shape

    @property
    def weights(self) -> int:
        return sum(int(np.prod(s)) for s in self.params.values())

    @property
    def weight_bytes(self) -> int:
        """The matrix ``w`` at two bytes, the bias ``b`` at four."""
        return sum(int(np.prod(s)) * (BF16 if k == "w" else F32)
                   for k, s in self.params.items())


def layer_table(cfg: dict) -> list:
    hw, c = cfg["input_hw"], cfg["in_ch"]
    rows = []
    for spec in cfg["plan"]:
        if spec == "M":
            if hw < 2:            # the builder skips pools of a 1x1 map
                continue
            hw //= 2
            rows.append(LayerRow("pool", (hw, hw, c), 0))
            continue
        rows.append(LayerRow("conv", (hw, hw, spec), hw * hw * 9 * c * spec,
                             {"w": (3, 3, c, spec), "b": (spec,)}))
        c = spec
        rows.append(LayerRow("relu", (hw, hw, c), 0))
    feat = hw * hw * c
    rows.append(LayerRow("flatten", (feat,), 0))
    dims = [feat, cfg["classifier_width"], cfg["classifier_width"],
            cfg["n_classes"]]
    for i in range(3):
        rows.append(LayerRow("linear", (dims[i + 1],), dims[i] * dims[i + 1],
                             {"w": (dims[i], dims[i + 1]),
                              "b": (dims[i + 1],)}))
        if i < 2:
            rows.append(LayerRow("relu", (dims[i + 1],), 0))
    return rows


def macs(cfg: dict, start: int = 0, stop: int | None = None) -> int:
    """Mult-adds per image of layers ``[start, stop)``."""
    return sum(r.macs for r in layer_table(cfg)[start:stop])


def tail_macs(cfg: dict) -> int:
    """Mult-adds per image of the layers after the cut."""
    return macs(cfg, cfg["cut"] + 1)


def boundary(cfg: dict) -> tuple:
    """``(rows, channels)`` of the boundary activation of one image: the
    codec works on one row per spatial position."""
    shape = layer_table(cfg)[cfg["cut"]].out_shape
    return int(np.prod(shape[:-1])), int(shape[-1])


def decode_cost(cfg: dict, images: int = 1) -> tuple:
    """``(operations, bytes)`` of the ae8 decode: dequantise the int8
    codes by their row scales, then the decoder's ``(L, C)`` matmul and
    bias.  Bytes: codes, scales, decoder weights and bias, output."""
    n, c = boundary(cfg)
    n *= images
    l = cfg["ae_latent"]
    ops = n * l + 2 * n * l * c + n * c
    nbytes = n * l + n * F32 + l * c * BF16 + c * F32 + n * c * F32
    return ops, nbytes


def tail_cost(cfg: dict, images: int) -> tuple:
    """``(operations, bytes)`` of the tail stage over ``images`` images."""
    rows = layer_table(cfg)[cfg["cut"] + 1:]
    n, c = boundary(cfg)
    ops = 2 * images * sum(r.macs for r in rows)
    nbytes = (sum(r.weight_bytes for r in rows)
              + images * n * c * F32 + images * cfg["n_classes"] * F32)
    return ops, nbytes


def request_ops(cfg: dict) -> int:
    """Operations the server does for one answered frame: its decode and
    its share of the tail (padding slots are not counted)."""
    return decode_cost(cfg)[0] + tail_cost(cfg, 1)[0]


def least_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of operations over peak compute and bytes
    over peak memory bandwidth."""
    return max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
