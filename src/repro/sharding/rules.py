"""Logical-axis sharding rules: param/batch/cache pytrees -> PartitionSpecs.

Scheme (DESIGN.md §6): 2D FSDP-style weight sharding over ("data","model"),
experts over "model", batch over ("pod","data"), sequence-parallel residual
stream (seq over "model"), decode KV caches sharded batch->data /
seq->model.  Every candidate axis is divisibility-checked against the mesh
and silently dropped when it does not divide (whisper-tiny's 6 heads,
long_500k's batch=1, ...), so one rule set serves all 40 combos.
"""
from __future__ import annotations

import re
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# (path-regex, spec template). First match wins. Templates are tuples of
# mesh-axis names (or None); a leading "+G" marks group-stacked params.
PARAM_RULES = [
    (r"embed$", (None, "model")),
    (r"head$", (None, "model")),
    (r"(attn|cross)/w[qkv]$", ("data", "model")),
    (r"(attn|cross)/wo$", ("model", "data")),
    (r"(attn|cross)/b[qkv]$", ("model",)),
    (r"ffn/router$", (None, None)),
    (r"ffn/w_(gate|up)$", {2: ("data", "model"), 3: ("model", "data", None)}),
    (r"ffn/w_down$", {2: ("model", "data"), 3: ("model", None, "data")}),
    (r"ffn/shared/w_(gate|up)$", ("data", "model")),
    (r"ffn/shared/w_down$", ("model", "data")),
    (r"ffn/(w_in|b_in)$", {2: ("data", "model"), 1: ("model",)}),
    (r"ffn/w_out$", ("model", "data")),
    (r"mamba/in_proj$", ("data", "model")),
    (r"mamba/out_proj$", ("model", "data")),
    (r"mamba/conv$", (None, "model")),
    (r"mamba/conv_b$", ("model",)),
    (r"mamba/x_proj$", ("model", None)),
    (r"mamba/dt_proj$", (None, "model")),
    (r"mamba/(dt_bias|D)$", ("model",)),
    (r"mamba/A_log$", ("model", None)),
    (r"tm/w[rkvg]$", ("data", "model")),
    (r"tm/wo$", ("model", "data")),
    (r"cm/w_k$", ("data", "model")),
    (r"cm/w_v$", ("model", "data")),
    (r"cm/w_r$", ("data", "model")),
    (r"enc/proj$", (None, "model")),
    (r"enc/pos$", (None, "model")),
    (r"projector/w1$", (None, "model")),
    (r"projector/w2$", ("data", "model")),
]

CACHE_RULES = [
    (r"/(k|v)$", (None, "data", "model", None, None)),
    (r"/kv_pos$", (None, "data", "model")),
    (r"/(ck|cv)$", (None, "data", None, "model", None)),
    (r"/conv$", (None, "data", None, "model")),
    (r"/ssm$", (None, "data", "model", None)),
    (r"/(tm_prev|cm_prev)$", (None, "data", "model")),
    (r"/wkv$", (None, "data", "model", None, None)),
]


def _path_str(path) -> str:
    parts = []
    for pk in path:
        if hasattr(pk, "key"):
            parts.append(str(pk.key))
        elif hasattr(pk, "idx"):
            parts.append(str(pk.idx))
    return "/".join(parts)


def _sanitize(spec: tuple, shape: tuple, axis_sizes: dict) -> P:
    """Drop sharding on axes that do not divide the dim."""
    out = []
    for dim, ax in zip(shape, spec):
        if ax is None:
            out.append(None)
        else:
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= axis_sizes[a]
            out.append(ax if dim % n == 0 else None)
    return P(*out)


def _resolve(rules, path: str, shape: tuple, axis_sizes: dict,
             stacked: bool) -> P:
    for pat, tmpl in rules:
        if re.search(pat, path):
            if isinstance(tmpl, dict):  # select by rank (sans group axis)
                tmpl = tmpl.get(len(shape) - (1 if stacked else 0))
                if tmpl is None:
                    return P()
            spec = ((None,) + tuple(tmpl)) if stacked else tuple(tmpl)
            if len(spec) != len(shape):  # rank mismatch -> replicate
                return P()
            return _sanitize(spec, shape, axis_sizes)
    return P()


def mesh_axis_sizes(mesh: Mesh) -> dict:
    d = dict(zip(mesh.axis_names, mesh.devices.shape))
    d.setdefault("pod", 1)
    return d


def batch_axes(mesh: Mesh):
    """The composite data-parallel axis: ("pod","data") on multi-pod."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def param_specs(params_tree, mesh: Mesh, profile: str = "train"):
    """PartitionSpec pytree matching a param (shape-)pytree.

    profile="train": 2D FSDP sharding over ("data","model").
    profile="inference": weights sharded over "model" only (replicated
    across "data") — kills the per-step weight all-gathers that dominate
    decode (§Perf hillclimb 2) at the cost of 16x weight HBM.
    """
    sizes = mesh_axis_sizes(mesh)

    def one(path, leaf):
        ps = _path_str(path)
        stacked = ps.startswith("layers/") or "/layers/" in ps
        spec = _resolve(PARAM_RULES, ps, leaf.shape, sizes, stacked)
        if profile == "inference":
            spec = P(*[None if ax == "data" else ax for ax in spec])
        return spec

    return jax.tree_util.tree_map_with_path(one, params_tree)


def cache_specs(cache_tree, mesh: Mesh):
    sizes = mesh_axis_sizes(mesh)

    def one(path, leaf):
        return _resolve(CACHE_RULES, _path_str(path), leaf.shape, sizes, False)

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def batch_specs(batch_tree, mesh: Mesh):
    """tokens/labels (B,S) -> batch over ("pod","data"); frontends likewise."""
    sizes = mesh_axis_sizes(mesh)
    dp = batch_axes(mesh)
    dp = dp[0] if len(dp) == 1 else dp

    def one(path, leaf):
        spec = (dp,) + (None,) * (len(leaf.shape) - 1)
        return _sanitize(spec, leaf.shape, sizes)

    return jax.tree_util.tree_map_with_path(one, batch_tree)


# Each kind maps to a list of candidate specs; the first whose sharded dims
# all divide is used ("heads" falls back to sequence sharding when the head
# count doesn't divide the model axis — llama3.2-3b's 24 heads, whisper's 6).
ACT_SPECS = {
    "residual": lambda dp: [P(dp, "model", None)],
    "heads": lambda dp: [P(dp, None, "model", None), P(dp, "model", None, None)],
    "ffn_hidden": lambda dp: [P(dp, None, "model")],
    "moe_experts": lambda dp: [P(dp, "model", None, None)],
    "mamba_inner": lambda dp: [P(dp, None, "model")],
    "mamba_state": lambda dp: [P(dp, "model", None)],
    "wkv_state": lambda dp: [P(dp, "model", None, None)],
    "logits": lambda dp: [P(dp, None, "model")],
    "decode_residual": lambda dp: [P("data", None, None)],
    "decode_logits": lambda dp: [P("data", "model")],
}


def _fits(spec, shape, sizes) -> bool:
    for dim, ax in zip(shape, spec):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = 1
        for a in axes:
            n *= sizes[a]
        if dim % n:
            return False
    return True


def make_shard_fn(mesh: Optional[Mesh], *, head_seq_fallback: bool = False):
    """The shard_fn hook models accept: pins activation shardings.

    ``head_seq_fallback=True`` is the §Perf optimisation: when the head
    count doesn't divide the model axis, shard the attention *sequence*
    dim instead of leaving q/k/v effectively replicated (default False =
    the recorded baseline).
    """
    if mesh is None:
        return lambda x, kind: x
    sizes = mesh_axis_sizes(mesh)
    dp = batch_axes(mesh)
    dp = dp[0] if len(dp) == 1 else dp

    def shard_fn(x, kind):
        fn = ACT_SPECS.get(kind)
        if fn is None:
            return x
        candidates = fn(dp)
        if not head_seq_fallback:
            candidates = candidates[:1]
        for spec in candidates:
            if _fits(tuple(spec), x.shape, sizes):
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, spec))
        spec = _sanitize(tuple(candidates[0]), x.shape, sizes)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return shard_fn


def to_shardings(spec_tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))
