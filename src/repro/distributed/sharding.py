"""Logical-axis sharding rules (MaxText-style) and activation constraints.

Weights and activations are annotated with LOGICAL axis names; a rule set
maps them to mesh axes. Changing the parallelism layout (the hillclimbing
lever) means changing rules, not model code.

Default layout on mesh ("pod", "data", "model") / ("data", "model"):

  weights:  embed (d_model dim)  -> data      (FSDP / ZeRO-3)
            mlp / heads / vocab  -> model     (TP)
            expert               -> model     (EP)
  acts:     batch                -> pod+data  (DP)
            kv_seq (decode)      -> model     (decode attention splits KV)
            kv_seq (long ctx)    -> data+model (context/sequence parallel)
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


Rules = Dict[str, Optional[Tuple[str, ...]]]


def _mesh_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def base_rules(mesh: Mesh, cfg=None) -> Rules:
    """Training/prefill layout. The activation residual stream must be
    sharded over 'model' between blocks (otherwise a 64-group command-r
    scan carry needs 100+GB/device). Two variants:

      * attention archs: shard the SEQUENCE dim ("seq" -> model). FFN/qkv
        einsums contract d_model, so s-sharded activations need NO gather;
        attention gathers only K/V (small under GQA). Megatron-SP flavored.
      * ssm/hybrid archs (mamba/rwkv scans iterate the seq axis, which
        cannot be a sharded scan axis): shard d_model ("act_embed" -> model)
        and pay the per-block all-gather.
    """
    has_pod = "pod" in _mesh_axes(mesh)
    batch = ("pod", "data") if has_pod else ("data",)
    seq_shardable = cfg is None or all(
        b == "attn" for b in getattr(cfg, "block_pattern", ("attn",)))
    return {
        # weights
        "embed": ("data",),          # FSDP shard dim
        "mlp": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "vocab": ("model",),
        "expert": ("model",),
        "rwkv_heads": ("model",),
        "mamba_inner": ("model",),
        "layers": None,              # stacked scan dim — replicated
        # activations
        "batch": batch,
        "seq": ("model",) if seq_shardable else None,
        "act_embed": None if seq_shardable else ("model",),
        "act_heads": ("model",),
        "kv_seq": None,
        "frontend": None,
        None: None,
    }


def decode_rules(mesh: Mesh, cfg=None) -> Rules:
    r = base_rules(mesh, cfg)
    # decode: small per-step compute; shard the KV cache along sequence
    # (flash-decode style) because kv_heads may be < mesh model size.
    r["seq"] = None                  # decode S == 1
    r["act_embed"] = None
    r["kv_seq"] = ("model",)
    r["kv_heads"] = None
    r["act_heads"] = None
    return r


def long_context_rules(mesh: Mesh, cfg=None) -> Rules:
    r = decode_rules(mesh, cfg)
    has_pod = "pod" in _mesh_axes(mesh)
    # batch=1: give both axes to the sequence dim (context parallelism)
    r["batch"] = None
    r["kv_seq"] = ("pod", "data", "model") if has_pod else ("data", "model")
    return r


RULESETS = {
    "train": base_rules,
    "prefill": base_rules,
    "decode": decode_rules,
    "long": long_context_rules,
}

_state = threading.local()


@contextmanager
def use_rules(rules: Rules):
    prev = getattr(_state, "rules", None)
    _state.rules = rules
    try:
        yield
    finally:
        _state.rules = prev


def current_rules() -> Optional[Rules]:
    return getattr(_state, "rules", None)


def spec_for(axes: Sequence[Optional[str]], rules: Optional[Rules] = None,
             mesh: Optional[Mesh] = None, shape=None) -> P:
    """Map logical axes -> PartitionSpec under the active rules. When
    ``shape`` is known, an assignment that does not divide evenly is SKIPPED
    rather than consumed — so e.g. an 8-expert dim on a 16-way model axis
    leaves the axis free for the mlp dim behind it (mixtral would otherwise
    end up with replicated expert weights)."""
    rules = rules or current_rules()
    if rules is None or axes is None:
        return P()
    out, used = [], set()
    for i, ax in enumerate(axes):
        mesh_ax = rules.get(ax) if ax is not None else None
        if mesh_ax is None:
            out.append(None)
            continue
        mesh_ax = tuple(a for a in mesh_ax if a not in used)
        if not mesh_ax:
            out.append(None)
            continue
        if shape is not None and mesh is not None:
            size = _axis_size(mesh, mesh_ax)
            if size <= 0 or shape[i] % max(size, 1) != 0:
                out.append(None)      # leave the mesh axis available
                continue
        used.update(mesh_ax)
        out.append(mesh_ax if len(mesh_ax) > 1 else mesh_ax[0])
    return P(*out)


def constrain(x, *axes):
    """with_sharding_constraint via logical axes; no-op outside a rule set.
    Divisibility-aware: an indivisible dim skips its assignment, leaving the
    mesh axis for later dims."""
    rules = current_rules()
    if rules is None:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    spec = spec_for(axes, rules, mesh, shape=tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, spec)


def _axis_size(mesh, name) -> int:
    try:
        return int(np.prod([dict(zip(mesh.axis_names, mesh.axis_sizes))[n]
                            for n in ((name,) if isinstance(name, str) else name)]))
    except Exception:
        return 1


def _drop_indivisible(spec: P, shape, mesh) -> P:
    if mesh is None or not getattr(mesh, "axis_names", None):
        return spec
    out = []
    for dim, assignment in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if assignment is None:
            out.append(None)
            continue
        size = _axis_size(mesh, assignment)
        out.append(assignment if size > 0 and dim % size == 0 else None)
    return P(*out)


def is_axes_leaf(x) -> bool:
    """A logical-axes leaf is None or a PLAIN tuple of str/None. NamedTuples
    (KVCache etc.) fail the exact-type check and recurse as pytree nodes."""
    return x is None or (type(x) is tuple and all(
        isinstance(e, (str, type(None))) for e in x))


def make_shardings(axes_tree, mesh: Mesh, rules: Optional[Rules] = None,
                   shapes_tree=None):
    """NamedSharding tree from a logical-axes tree (for jit in_shardings).
    If ``shapes_tree`` is given, indivisible dims fall back to replication."""
    specs = make_specs(axes_tree, mesh, rules, shapes_tree)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


def make_specs(axes_tree, mesh: Mesh, rules: Optional[Rules] = None,
               shapes_tree=None):
    """PartitionSpec tree; if ``shapes_tree`` is given, indivisible dims are
    dropped to replication per-leaf."""
    rules = rules or base_rules(mesh)
    if shapes_tree is None:
        return jax.tree_util.tree_map(
            lambda axes: spec_for(axes, rules, mesh), axes_tree,
            is_leaf=is_axes_leaf)

    def one(axes, shaped):
        if axes is None:
            return P()
        spec = spec_for(axes, rules, mesh, shape=tuple(shaped.shape))
        return _drop_indivisible(spec, shaped.shape, mesh)

    return jax.tree_util.tree_map(one, axes_tree, shapes_tree,
                                  is_leaf=is_axes_leaf)
