"""Logical-axis sharding rules (MaxText-style) for the LM zoo.

Parameters and activations are annotated with *logical* axes; this module
resolves them against whatever mesh is active (single-pod (data, model) or
multi-pod (pod, data, model)), dropping mesh axes that do not divide the
dimension (e.g. kv_heads=4 stays replicated under model=16, Megatron-style).

  batch   -> (pod, data)     data parallel
  vocab   -> model           embedding / lm_head / router... tensor parallel
  heads   -> model           attention-head TP
  ffn     -> model           MLP TP
  experts -> (data, model) when the expert count covers both axes
             (deepseek-v3: 256 experts over 256 chips), else model
  seq     -> model           sequence/context parallel (long prefill)
  embed   -> None            replicated (ZeRO handled by optimizer sharding)
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

LOGICAL = {
    "batch": ("pod", "data"),
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "experts": ("data", "model"),
    "experts_1d": ("model",),
    "seq": ("model",),
    "embed": (),
    "layers": (),
    None: (),
}

# FSDP mode: every weight sharded on its EMBED (d_model) dim over the
# model axis; activations stay batch-sharded over (pod, data). GSPMD
# all-gathers each layer's weights transiently (bf16) and reduce-scatters
# its gradients - for few-B-param models at ~1M tokens/step this is ~8x
# less wire volume than per-layer TP activation all-reduces (hillclimb #2;
# run with accum=1 so weight-grad reductions fire once per step).
LOGICAL_FSDP = {
    **LOGICAL,
    "embed": ("model",),
    "vocab": (),
    "heads": (),
    "kv_heads": (),
    "ffn": (),
    "seq": (),
}

# Pure-DP mode: params replicated, batch over every mesh axis, one
# gradient all-reduce per step. For few-B-param models at ~1M tokens/step
# the per-layer TP activation all-reduces dwarf a single 2-byte/param
# gradient reduction (hillclimb #2 napkin math + measurement).
LOGICAL_DP = {
    **LOGICAL,
    "batch": ("pod", "data", "model"),
    "vocab": (),
    "heads": (),
    "kv_heads": (),
    "ffn": (),
    "seq": (),
}

RULESETS = {"tp": LOGICAL, "fsdp": LOGICAL_FSDP, "dp": LOGICAL_DP}


def _axes_in_mesh(mesh, names: tuple[str, ...]) -> tuple[str, ...]:
    return tuple(a for a in names if a in mesh.axis_names)


def resolve_spec(mesh, logical: tuple, shape: tuple[int, ...],
                 mode: str = "tp") -> P:
    """Map logical axes -> PartitionSpec, dropping non-dividing axes."""
    rules = RULESETS[mode]
    parts = []
    for dim, name in zip(shape, logical):
        axes = _axes_in_mesh(mesh, rules.get(name, ()))
        total = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        if axes and dim % total == 0 and dim >= total:
            parts.append(axes if len(axes) > 1 else axes[0])
        else:
            # try a prefix of the axes (e.g. experts over model only)
            ok = None
            for cut in range(len(axes) - 1, 0, -1):
                t = int(np.prod([mesh.shape[a] for a in axes[-cut:]]))
                if dim % t == 0 and dim >= t:
                    ok = axes[-cut:] if cut > 1 else axes[-1]
                    break
            parts.append(ok)
    return P(*parts)


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the
    domain-decomposed MD code mixes per-device values (halo ghosts, local
    tables) with replicated scalars, which the checker cannot express.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_ACTIVE_MODE = ["tp"]


def set_mode(mode: str):
    """Set the ruleset used by activation `shard()` constraints."""
    _ACTIVE_MODE[0] = mode


def shard(x: jax.Array, *logical) -> jax.Array:
    """Activation sharding constraint; no-op when no mesh is active."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.axis_names:
        return x
    spec = resolve_spec(mesh, logical, x.shape, _ACTIVE_MODE[0])
    return jax.lax.with_sharding_constraint(x, spec)


# name(-suffix) -> logical axes for parameter trees. Matched on the last
# path components; first match wins. Leading stacked-layer dims are handled
# by left-padding with None.
PARAM_RULES: list[tuple[tuple[str, ...], tuple]] = [
    (("embed",), ("vocab", "embed")),
    (("lm_head",), ("embed", "vocab")),
    (("attn", "wq"), ("embed", "heads", None)),
    (("attn", "wk"), ("embed", "kv_heads", None)),
    (("attn", "wv"), ("embed", "kv_heads", None)),
    (("attn", "wo"), ("heads", None, "embed")),
    (("attn", "bq"), ("heads", None)),
    (("attn", "bk"), ("kv_heads", None)),
    (("attn", "bv"), ("kv_heads", None)),
    # MLA
    (("attn", "wq_a"), ("embed", None)),
    (("attn", "wq_b"), (None, "heads", None)),
    (("attn", "wkv_a"), ("embed", None)),
    (("attn", "wk_b"), (None, "heads", None)),
    (("attn", "wv_b"), (None, "heads", None)),
    # dense MLP
    (("mlp", "wi"), ("embed", "ffn")),
    (("mlp", "wg"), ("embed", "ffn")),
    (("mlp", "wo"), ("ffn", "embed")),
    # MoE
    (("moe", "router"), ("embed", "experts_1d")),
    (("moe", "wi"), ("experts", "embed", None)),
    (("moe", "wg"), ("experts", "embed", None)),
    (("moe", "wo"), ("experts", None, "embed")),
    (("moe", "sh_wi"), ("embed", "ffn")),
    (("moe", "sh_wg"), ("embed", "ffn")),
    (("moe", "sh_wo"), ("ffn", "embed")),
    # Mamba2
    (("ssm", "in_proj"), ("embed", "ffn")),
    (("ssm", "out_proj"), ("ffn", "embed")),
    (("ssm", "conv_w"), (None, "ffn")),
    (("ssm", "conv_b"), ("ffn",)),
    (("ssm", "norm_w"), ("ffn",)),
]


def _path_names(path) -> tuple[str, ...]:
    out = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            out.append(str(p.key))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            out.append(p.name)
    return tuple(out)


def param_pspec(path_names: tuple[str, ...], ndim: int) -> tuple:
    for suffix, logical in PARAM_RULES:
        if len(path_names) >= len(suffix) and \
                tuple(path_names[-len(suffix):]) == suffix:
            pad = ndim - len(logical)
            return ("layers",) * pad + logical if pad >= 0 else logical[:ndim]
    return (None,) * ndim


def param_shardings(mesh, params_tree, mode: str = "tp") -> Any:
    """NamedSharding tree for a parameter pytree (by path-name rules)."""
    def f(path, leaf):
        logical = param_pspec(_path_names(path), leaf.ndim)
        return NamedSharding(mesh, resolve_spec(mesh, logical, leaf.shape,
                                                mode))
    return jax.tree_util.tree_map_with_path(f, params_tree)


def param_pspecs(mesh, params_tree) -> Any:
    def f(path, leaf):
        logical = param_pspec(_path_names(path), leaf.ndim)
        return resolve_spec(mesh, logical, leaf.shape)
    return jax.tree_util.tree_map_with_path(f, params_tree)


def opt_shardings(mesh, params_tree) -> Any:
    """ZeRO-1: optimizer moments inherit the parameter sharding, then any
    still-replicated dim is additionally sharded over spare DP axes (pod
    first, then data) when divisible - optimizer state never needs to be
    replicated across data parallelism."""
    def f(path, leaf):
        logical = param_pspec(_path_names(path), leaf.ndim)
        spec = list(resolve_spec(mesh, logical, leaf.shape))
        spec += [None] * (leaf.ndim - len(spec))
        used = set()
        for s in spec:
            if s is None:
                continue
            used.update(s if isinstance(s, tuple) else (s,))
        for ax in ("pod", "data", "model"):
            if ax in used or ax not in mesh.axis_names:
                continue
            n = mesh.shape[ax]
            for d in range(leaf.ndim):
                if spec[d] is None and leaf.shape[d] % n == 0 and \
                        leaf.shape[d] >= n:
                    spec[d] = ax
                    used.add(ax)
                    break
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(f, params_tree)
