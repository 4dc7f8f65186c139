"""Measured calibration: run the split runtime over a (config, split)
grid and emit cost tables the simulators consume.

The analytic models in ``core.scenarios`` (FLOPs / effective throughput)
are guesses; this module replaces them with *measurements* taken by
executing the real head/tail stages and the real wire codec on the
attached hardware — the paper §IV hardware-in-the-loop methodology (see
``core.scenarios.HILPlatform``), extended to a whole grid of cuts.

The table implements the :class:`repro.api.types.CostModel` protocol:
``netsim.simulator.measure_flow(..., cost=table)`` and
``fleet.planner.DeploymentPlanner(cost=table)`` look entries up by
``(scenario kind, split layer)`` and fall back to the analytic model for
cells the grid didn't cover.
"""
from __future__ import annotations

import json
from dataclasses import asdict, dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.split import validate_cut
from repro.runtime import wire as W
from repro.runtime.engine import timeit_blocked
from repro.runtime.partition import make_partition


@dataclass(frozen=True)
class CalEntry:
    """Measured costs of one (scenario kind, split) cell.

    Times and bytes are for one forward of the *calibration batch*
    (``CalibrationTable.batch`` frames); consumers that need a different
    batch size scale linearly (``measure_flow`` does this) or divide by
    the table batch for per-frame costs (the planner does).
    """
    head_s: float                    # edge-side stage compute
    tail_s: float                    # server-side stage compute
    wire_bytes: int                  # actual serialized payload size
    encode_s: float = 0.0            # edge-side codec
    decode_s: float = 0.0            # server-side codec

    @property
    def edge_s(self) -> float:
        """Edge wall clock as the planner prices it: head compute + codec."""
        return self.head_s + self.encode_s

    @property
    def server_s(self) -> float:
        return self.decode_s + self.tail_s


@dataclass
class CalibrationTable:
    """(kind, split) -> :class:`CalEntry`, JSON-serialisable."""
    model_name: str
    batch: int
    entries: dict = field(default_factory=dict)
    meta: dict = field(default_factory=dict)

    @staticmethod
    def key(kind: str, split: Optional[int]) -> str:
        return kind if split is None else f"{kind}@{split}"

    def put(self, kind: str, split: Optional[int], entry: CalEntry):
        self.entries[self.key(kind, split)] = entry

    def lookup(self, kind: str, split: Optional[int] = None) -> Optional[CalEntry]:
        return self.entries.get(self.key(kind, split))

    def flow_times(self, kind: str, split: Optional[int] = None,
                   batch: Optional[int] = None) -> Optional[dict]:
        """The measured replacement for
        ``core.scenarios.scenario_times_and_payload`` — same keys, plus the
        provenance marker.  None when the cell wasn't calibrated.

        With ``batch``, times quoted at the table's calibration batch are
        rescaled linearly to ``batch`` frames (first-order model;
        re-calibrate at the serving batch for exact numbers).  This is
        the :class:`repro.api.types.CostModel` flow interface.
        """
        e = self.lookup(kind, split)
        if e is None:
            return None
        if kind == "LC":
            times = {"edge_s": e.head_s, "server_s": 0.0, "wire_bytes": 0,
                     "cost_source": "measured"}
        elif kind == "RC":
            times = {"edge_s": 0.0, "server_s": e.tail_s,
                     "wire_bytes": e.wire_bytes, "cost_source": "measured"}
        else:
            times = {"edge_s": e.edge_s, "server_s": e.server_s,
                     "wire_bytes": e.wire_bytes, "cost_source": "measured"}
        if batch is not None:
            from repro.api.types import scale_flow_times
            times = scale_flow_times(times, self.batch or batch, batch)
        return times

    def server_cost(self, split: Optional[int], platform):
        """Measured per-replica service-time model of the server stage
        (the :class:`repro.api.types.CostModel` server interface): the
        wall clock of the executed tail stage, normalised to one request.
        None when the cell wasn't calibrated.
        """
        from repro.serving.engine import BatchCostModel
        entry = self.lookup("SC" if split is not None else "RC", split)
        if entry is None:
            return None
        per_item = entry.server_s / max(1, self.batch)
        return BatchCostModel.from_measured(per_item, platform.flops_per_s)

    def splits(self) -> list:
        return sorted(int(k.split("@")[1]) for k in self.entries
                      if "@" in k)

    # -------------------------------------------------------- persistence ----
    def to_json(self, path: str):
        doc = {"model_name": self.model_name, "batch": self.batch,
               "meta": self.meta,
               "entries": {k: asdict(e) for k, e in self.entries.items()}}
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=1)

    @classmethod
    def from_json(cls, path: str) -> "CalibrationTable":
        with open(path) as fh:
            doc = json.load(fh)
        t = cls(doc["model_name"], doc["batch"], meta=doc.get("meta", {}))
        for k, e in doc["entries"].items():
            t.entries[k] = CalEntry(**e)
        return t


def calibrate(model, params, splits: Sequence[int], *,
              ae_map: Optional[dict] = None, batch: int = 1,
              x: Optional[np.ndarray] = None, iters: int = 3,
              quantize: bool = True, include_rc: bool = True,
              include_lc: bool = True, seed: int = 0) -> CalibrationTable:
    """Measure per-stage compute and wire payload over a split grid.

    Runs on this host (HIL: the measured hardware stands in for both edge
    and server — scale or re-measure per platform for heterogeneous
    deployments).  ``ae_map``: split -> trained bottleneck AE; splits
    without an entry ship the raw int8 activation.

    ``x`` may be any input pytree the model consumes (a transformer
    layered view takes a batch dict); the calibration batch is its
    leading dim.
    """
    ae_map = dict(ae_map or {})
    if x is None:
        rng = np.random.default_rng(seed)
        x = rng.standard_normal((batch,) + tuple(model.input_shape)
                                ).astype(np.float32)
    x = jax.tree.map(jnp.asarray, x)
    leaves = jax.tree.leaves(x)
    batch = int(leaves[0].shape[0])  # the table's batch is x's, always
    table = CalibrationTable(model.name, batch,
                             meta={"iters": iters, "quantize": quantize,
                                   "n_splits": len(splits)})

    full_s, _ = timeit_blocked(lambda v: model.apply(params, v), x,
                               iters=iters)
    if include_lc:
        table.put("LC", None, CalEntry(full_s, 0.0, 0))
    if include_rc:
        input_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves)
        table.put("RC", None, CalEntry(0.0, full_s, input_bytes))

    for split in splits:
        validate_cut(model, split)
        ae = ae_map.get(split)
        part = make_partition(model, params, split, ae)
        head_s, f = timeit_blocked(part.head, x, iters=iters)
        enc_s, pkt = timeit_blocked(
            lambda v: W.encode_activation(v, ae, quantize=quantize), f,
            iters=iters, warmup=1)
        buf = W.to_bytes(pkt)
        dec_s, f_hat = timeit_blocked(
            lambda b: W.decode_activation(W.from_bytes(b), ae), buf,
            iters=iters, warmup=1)
        tail_s, _ = timeit_blocked(part.tail, f_hat, iters=iters)
        table.put("SC", split,
                  CalEntry(head_s, tail_s, len(buf), enc_s, dec_s))
    return table
