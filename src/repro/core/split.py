"""Model partitioning: head / bottleneck / tail (paper §III) and the TPU
multi-pod adaptation (DESIGN.md §3).

Two execution mappings of the same split:

* **edge/server** (paper-faithful): `head_forward` on the sensing device,
  payload over the simulated network (``repro.netsim``), `tail_forward` on
  the server — see ``repro.core.bottleneck`` for the pieces.
* **multi-pod pipeline** (TPU adaptation): the cut becomes a cross-pod
  stage boundary; ``multipod_split_step`` runs a 2-stage microbatched
  pipeline under ``shard_map`` where the inter-stage hop is a
  ``lax.ppermute`` over the ``pod`` axis carrying the bottleneck-compressed
  activation — the paper's head/AE/tail triple with the TCP channel
  replaced by the pod-to-pod link.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layered import LayeredModel
from repro.models import transformer as T
from repro.core import bottleneck as B


@dataclass(frozen=True)
class SplitPlan:
    """A concrete SC design point: one or more ordered cuts.

    The portable form of an SC candidate (``repro.api.types.SplitCandidate``
    carries one of these as its executable payload via ``.plan()``).
    ``splits`` is the canonical ordered cut list; the historical scalar
    ``split_layer`` stays as the first (edge-side) cut, so every 1-cut
    consumer keeps working unchanged — ``SplitPlan(4)`` and
    ``SplitPlan(4, splits=(4,))`` are the same design point.
    """
    split_layer: int              # first cut (after this layer index)
    compression: float = 0.5      # bottleneck rate (paper: 50%)
    wire_dtype_bytes: int = 4
    splits: tuple = None          # full ordered cut list; (split_layer,) if None

    def __post_init__(self):
        if self.splits is None:
            cuts = () if self.split_layer is None else (int(self.split_layer),)
        else:
            cuts = normalize_cuts(self.splits)
        object.__setattr__(self, "splits", cuts)
        if self.split_layer is None and cuts:
            object.__setattr__(self, "split_layer", cuts[0])

    @property
    def n_stages(self) -> int:
        return len(self.splits) + 1

    def describe(self, model: LayeredModel) -> str:
        """Human-readable stage layout of this plan on ``model``
        (legality-checked through :func:`validate_cuts`)."""
        cuts = validate_cuts(model, self.splits)
        if len(cuts) == 1:
            return (f"head=[0..{self.split_layer}] "
                    f"bottleneck(rate={self.compression}) "
                    f"tail=[{self.split_layer + 1}..{len(model.layers) - 1}]")
        bounds = (0,) + tuple(c + 1 for c in cuts) + (len(model.layers),)
        stages = " | ".join(f"stage{i}=[{a}..{b - 1}]"
                            for i, (a, b) in enumerate(zip(bounds, bounds[1:])))
        return f"{stages} bottleneck(rate={self.compression})"


def legal_cuts(model: LayeredModel) -> list[int]:
    """All legal cut indices of ``model`` (ascending layer order)."""
    return model.cut_points()


def validate_cut(model: LayeredModel, split_layer: int) -> int:
    """Check a cut index against the model's legality rule.

    Single authority for "is this split executable" — the runtime partition,
    the planner and the examples all route through here so an illegal cut
    fails loudly with the legal alternatives instead of silently producing a
    head/tail pair that can never run.
    """
    cuts = model.cut_points()
    if split_layer not in cuts:
        raise ValueError(
            f"cut after layer {split_layer} is not legal for {model.name!r}; "
            f"legal cuts: {cuts}")
    return split_layer


def normalize_cuts(splits) -> tuple:
    """Coerce a scalar cut or an iterable of cuts into the canonical
    ordered cut tuple (the ``splits`` convention: ints, ascending).

    Strict monotonicity is enforced here, at the point every cut list is
    constructed (``SplitPlan``, ``SplitCandidate``, the planners), so a
    shuffled or duplicated list fails loudly instead of silently pricing
    empty/overlapping stages; per-cut *legality* against a model stays
    with :func:`validate_cuts`.
    """
    if not hasattr(splits, "__iter__"):
        return (int(splits),)
    cuts = tuple(int(s) for s in splits)
    if any(b <= a for a, b in zip(cuts, cuts[1:])):
        raise ValueError(f"cut list {cuts} must be strictly increasing "
                         f"(every stage needs at least one layer)")
    return cuts


def validate_cuts(model: LayeredModel, splits) -> tuple:
    """Check an ordered cut list against the model's legality rule.

    The multi-cut extension of :func:`validate_cut` and, like it, the
    single legality authority: a legal cut list is non-empty, strictly
    increasing (each stage runs at least one layer — enforced by
    :func:`normalize_cuts`), and every cut is individually legal.
    Returns the normalised tuple.
    """
    cuts = normalize_cuts(splits)
    if not cuts:
        raise ValueError(f"need at least one cut for {model.name!r}; "
                         f"legal cuts: {model.cut_points()}")
    for c in cuts:
        validate_cut(model, c)
    return cuts


def legal_cut_lists(model: LayeredModel, n_cuts: int) -> list:
    """Every legal ordered cut list with exactly ``n_cuts`` cuts.

    The K-way search space of the multi-tier planner: all strictly
    increasing ``n_cuts``-combinations of :func:`legal_cuts`.  The lists
    grow combinatorially and the planners enumerate them per search, so
    they are cached on the model instance (layer structure is immutable
    in practice) — treat the returned list as read-only.
    """
    import itertools
    if n_cuts < 1:
        raise ValueError(f"n_cuts must be >= 1, got {n_cuts}")
    cache = (model.__dict__.setdefault("_cut_lists_cache", {})
             if hasattr(model, "__dict__") else None)
    if cache is not None and n_cuts in cache:
        return cache[n_cuts]
    out = list(itertools.combinations(legal_cuts(model), n_cuts))
    if cache is not None:
        cache[n_cuts] = out
    return out


def wire_payload_bytes(model: LayeredModel, params, plan: SplitPlan,
                       batch: int = 1, *, sample=None) -> int:
    """Bytes crossing the first (edge-side) wire hop per ``batch`` frames
    under ``plan`` — see :func:`hop_payload_bytes` for the whole chain.

    ``sample``: example input (array or pytree) for models whose
    ``input_shape`` alone cannot describe the input — see
    ``LayeredModel.activation_shapes``.
    """
    return hop_payload_bytes(model, params, plan, batch, sample=sample)[0]


def hop_payload_bytes(model: LayeredModel, params, plan: SplitPlan,
                      batch: int = 1, *, sample=None) -> list:
    """Per-hop wire payloads (bytes per ``batch`` frames) of a K-cut plan.

    Hop k carries the activation after cut ``plan.splits[k]``, compressed
    at the plan's bottleneck rate (one AE per cut, same rate — the
    analytic counterpart of the runtime's per-hop codec).
    """
    shapes = model.activation_shapes(params, batch, sample=sample)
    return [batch * B.payload_bytes(shapes[c][1:], plan.compression,
                                    plan.wire_dtype_bytes)
            for c in plan.splits]


# ------------------------------------------------ multi-pod pipeline step ----
def _stack_stages(layer_params, n_groups: int, n_stages: int):
    """(G, ...) group-stacked params -> (n_stages, G/n_stages, ...)."""
    def re(x):
        return x.reshape((n_stages, n_groups // n_stages) + x.shape[1:])
    return jax.tree.map(re, layer_params)


def multipod_split_step(params, cfg, batch: dict, mesh, *, ae: Optional[dict],
                        n_micro: int = 4, shard_fn=None,
                        quantize_wire: bool = False):
    """2-stage pipelined forward across the ``pod`` mesh axis.

    Uniform-stack architectures only (period-1 block structure).  The head
    stage (pod 0) embeds + runs the first half of the blocks and *encodes*
    the residual stream with the bottleneck AE; the compressed latent
    crosses pods via ``ppermute``; the tail stage (pod 1) decodes and runs
    the rest + LM head.  Microbatches keep both pods busy (GPipe-style,
    bubble = 1/(n_micro+1)).

    Returns per-token logits of the last microbatch wave (B, S, V) — enough
    for validation; the training driver reduces a loss instead.
    """
    descs, n_groups = T.block_structure(cfg)
    assert len(descs) == 1, "pipeline demo supports uniform stacks"
    assert n_groups % 2 == 0
    stages = _stack_stages(params["layers"], n_groups, 2)
    tokens = batch["tokens"]
    bsz, seq = tokens.shape
    assert bsz % n_micro == 0
    mb = bsz // n_micro

    stage_spec = jax.tree.map(lambda _: P("pod"), stages)
    out_spec = P(None, None, None)

    def run_stage(stage_params, x):
        positions = jnp.arange(x.shape[1])

        def body(x, lp):
            y, _, _ = T.apply_layer_seq(lp["l0"], descs[0], x, cfg, positions,
                                        causal=True, window=cfg.sliding_window)
            return y, None

        x, _ = jax.lax.scan(body, x, stage_params)
        return x

    def pipeline(stages_local, tokens_all):
        # stages_local: (1, G/2, ...) — this pod's stage
        stage_id = jax.lax.axis_index("pod")
        my_stage = jax.tree.map(lambda x: x[0], stages_local)
        mbs = tokens_all.reshape(n_micro, mb, seq)
        # one extra drain wave so the last microbatch clears the tail stage
        mbs = jnp.concatenate([mbs, jnp.zeros((1, mb, seq), mbs.dtype)], 0)

        def wave(carry, mb_tokens):
            recv = carry  # latent arriving from the other pod (previous wave)
            x0 = params["embed"][mb_tokens]                    # head input
            if ae is None:
                x1 = recv
            elif quantize_wire:
                x1 = B.decode_wire(ae, *recv)
            else:
                x1 = B.decode(ae, recv)
            x = jnp.where(stage_id == 0, x0, x1.astype(x0.dtype))
            y = run_stage(my_stage, x)
            if ae is None:
                wire = y
            elif quantize_wire:  # int8 codes + per-token scales on the link
                wire = B.encode_wire(ae, y.astype(jnp.float32))
            else:
                wire = B.encode(ae, y.astype(jnp.float32))
            sent = jax.tree.map(
                lambda t: jax.lax.ppermute(t, "pod", [(0, 1), (1, 0)]), wire)
            return sent, y

        latent_c = (B.latent_channels(cfg.d_model, 0.5) if ae is not None
                    else cfg.d_model)
        if ae is None:
            init = jnp.zeros((mb, seq, latent_c), cfg.jdtype)
        elif quantize_wire:
            init = (jnp.zeros((mb, seq, latent_c), jnp.int8),
                    jnp.ones((mb, seq, 1), jnp.float32))
        else:
            init = jnp.zeros((mb, seq, latent_c), jnp.float32)
        _, ys = jax.lax.scan(wave, init, mbs)
        # wave i's tail output (valid on pod 1) is microbatch i-1
        tail_out = ys[1:]                                      # (n_micro, mb, S, D)
        x = T._apply_norm(params["final_norm"], tail_out, cfg)
        logits = T.logits_from_x(params, cfg, x)
        logits = logits.reshape(bsz, seq, -1)
        # pod 0 holds head garbage; zero it and share pod 1's result
        valid = jnp.where(stage_id == 1, 1.0, 0.0).astype(logits.dtype)
        return jax.lax.psum(logits * valid, "pod")

    f = jax.shard_map(pipeline, mesh=mesh,
                      in_specs=(stage_spec, P()), out_specs=out_spec,
                      check_vma=False)
    return f(stages, tokens)
