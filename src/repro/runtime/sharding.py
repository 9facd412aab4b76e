"""Logical-axis sharding rules (MaxText-style) + divisibility-safe resolution.

A *rule set* maps logical axis names to mesh axis names.  ``resolve`` turns a
logical spec tuple (one entry per tensor dim) into a PartitionSpec, dropping
any mesh axis whose size does not divide the dimension -- this keeps every
in_sharding legal (GSPMD requires divisibility for inputs) while degrading
gracefully for small models on big meshes (e.g. whisper-tiny's 6 heads).

Profiles:
  dense_small -- TP on heads/mlp/vocab; DP on batch; weights replicated.
  dense_fsdp  -- dense_small + weights' embed dim sharded over data (ZeRO-3).
  moe_fsdp    -- dense_fsdp + experts over model (EP) with expert-mlp
                 fallback TP when n_experts < model size.
  tiny        -- DP only (whisper-tiny, lstm-rnnt).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

LogicalRules = Tuple[Tuple[str, Tuple[str, ...]], ...]

# data-parallel mesh axes (pod folds into DP on the multi-pod mesh)
DP = ("pod", "data")

PROFILES: Dict[str, Dict[str, Tuple[str, ...]]] = {
    "tiny": {
        "batch": DP,
        "seq": (),
        "embed": (),
        "heads": (),
        "kv": (),
        "head_dim": (),
        "mlp": ("model",),
        "mlp2": (),
        "vocab": ("model",),
        "experts": (),
        "expert_mlp": (),
        "layers": (),
        "state": (),
    },
    "dense_small": {
        "batch": DP,
        "seq": (),
        "embed": (),
        "heads": ("model",),
        "kv": ("model",),
        "head_dim": ("model",),  # fallback when kv-heads % model != 0
        "mlp": ("model",),
        "mlp2": (),
        "vocab": ("model",),
        "experts": (),
        "expert_mlp": ("model",),
        "layers": (),
        "state": (),
    },
}
PROFILES["dense_fsdp"] = dict(PROFILES["dense_small"], embed=("data",))
PROFILES["moe_fsdp"] = dict(
    PROFILES["dense_fsdp"], experts=("model",), expert_mlp=("model",),
)


def rules_for(profile: str) -> Dict[str, Tuple[str, ...]]:
    return PROFILES[profile]


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check (``check_vma``) off.

    Every shard_map body in this repo disables the check (int8-compressed
    psum and capacity-dispatch MoE both confuse it), so one helper covers
    them all.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def resolve(
    logical: Optional[Tuple[Optional[str], ...]],
    shape: Sequence[int],
    rules: Dict[str, Tuple[str, ...]],
    mesh: Mesh,
) -> P:
    """Logical spec tuple -> PartitionSpec, enforcing divisibility."""
    if logical is None:
        return P()
    assert len(logical) == len(shape), (logical, shape)
    used = set()
    out = []
    for dim, name in zip(shape, logical):
        if name is None or name not in rules:
            out.append(None)
            continue
        axes = []
        prod = 1
        for ax in rules[name]:
            if ax not in mesh.shape or ax in used:
                continue
            if dim % (prod * mesh.shape[ax]) == 0:
                axes.append(ax)
                prod *= mesh.shape[ax]
        used.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(tuple(axes))
    return P(*out)


def tree_shardings(specs_tree, shapes_tree, rules, mesh):
    """Map parallel (logical-spec, shape) trees to NamedShardings."""

    def leaf(spec, arr):
        shape = arr.shape if hasattr(arr, "shape") else arr
        return NamedSharding(mesh, resolve(spec, shape, rules, mesh))

    return jax.tree_util.tree_map(
        leaf, specs_tree, shapes_tree,
        is_leaf=lambda s: s is None or (
            isinstance(s, tuple) and all(isinstance(x, (str, type(None))) for x in s)
        ),
    )


def make_constrain(rules, mesh):
    """Returns constrain(x, logical_tuple) applying with_sharding_constraint.

    Degrades to identity when no mesh is active (single-device smoke tests).
    """
    if mesh is None or np.prod(list(mesh.shape.values())) == 1:
        return lambda x, logical=None: x

    def constrain(x, logical=None):
        if logical is None:
            return x
        spec = resolve(tuple(logical), x.shape, rules, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return constrain


def batch_logical(batch_tree) -> Any:
    """Default logical specs for an input batch: shard dim0 over DP axes."""

    def leaf(x):
        nd = len(x.shape)
        return ("batch",) + (None,) * (nd - 1)

    return jax.tree_util.tree_map(leaf, batch_tree)


def engine_state_shardings(state_tree, rules, mesh) -> Any:
    """NamedShardings for a continuous-batching slot state.

    The slot dimension IS the batch dimension: every per-layer ``h``/``c``
    row (and the per-slot ``len`` counter) spreads over the data-parallel
    mesh axes, so a multi-device serving deployment scales slots across
    devices while each stream's integer math stays on one shard (keeping
    the bit-exactness contract intact -- no cross-row collectives exist in
    the decode step).  Degrades to fully-replicated specs when the slot
    count does not divide the DP axes (``resolve`` divisibility rule).
    """
    if rules is None:
        rules = rules_for("tiny")
    specs = state_logical(state_tree)
    return tree_shardings(specs, state_tree, rules, mesh)


def engine_block_sharding(shape: Sequence[int], rules, mesh) -> NamedSharding:
    """NamedSharding for a per-step engine input block: the slot dim leads.

    Covers the ``(S,)`` token/active vectors of the one-token step and the
    ``(S, K)`` token block + ``(S,)`` valid-length vector of the chunked
    prefill step.  Dim 0 is the slot axis and spreads over the data-parallel
    mesh axes -- the SAME placement ``engine_state_shardings`` gives the slot
    state, so the jitted step sees consistently-sharded operands and never
    needs a resharding collective on its inputs.  Falls back to replication
    when the slot count does not divide the DP axes (``resolve``).
    """
    if rules is None:
        rules = rules_for("tiny")
    logical = ("batch",) + (None,) * (len(shape) - 1)
    return NamedSharding(mesh, resolve(logical, shape, rules, mesh))


def pool_row_shardings(row_tree, rules, mesh) -> Any:
    """NamedShardings for a batch-1 state-pool row being swapped back in.

    A pool row is ``slice_state``'s output shape: every leaf keeps its
    leading batch axis (of size 1), so the same logical specs that place the
    full slot state (``engine_state_shardings``) apply verbatim -- and the
    ``resolve`` divisibility rule necessarily drops the DP axes on the
    size-1 batch dim, replicating the row.  Routing swap-ins through this
    helper keeps pool pages and slot tensors on one placement policy: the
    jitted resume write then scatters the row into the (possibly
    DP-sharded) slot axis without the engine ever hand-picking devices.
    """
    if rules is None:
        rules = rules_for("tiny")
    specs = state_logical(row_tree)
    return tree_shardings(specs, row_tree, rules, mesh)


def fleet_device_groups(n_shards: int, devices=None):
    """Partition the local devices into ``n_shards`` contiguous,
    equal-size, disjoint groups -- the fleet router's shard placement.

    Each per-shard engine gets its own device group (and mesh), so a shard
    death is a *device-group* event: the survivors' slot tensors live on
    other devices and are untouched.  Leftover devices (when the count does
    not divide) stay unused rather than unbalancing shards.  Returns
    ``None`` when there are fewer devices than shards -- the co-located CPU
    test mode, where every shard shares the default device and placement is
    a no-op (run under ``XLA_FLAGS=--xla_force_host_platform_device_count``
    to get real groups on CPU).
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if devices is None:
        devices = jax.devices()
    if len(devices) < n_shards:
        return None
    k = len(devices) // n_shards
    return [list(devices[i * k:(i + 1) * k]) for i in range(n_shards)]


def fleet_meshes(n_shards: int, devices=None):
    """One single-axis ``("data",)`` mesh per fleet shard over disjoint
    device groups (``fleet_device_groups``), or ``[None] * n_shards`` when
    there are not enough devices (mesh-less co-located engines).

    The ``data`` axis matches the DP axes the engine's slot-state shardings
    resolve against (``engine_state_shardings`` / ``engine_block_sharding``
    with the ``tiny`` profile), so each shard's slot axis spreads over its
    own devices and never touches a neighbour shard's.
    """
    groups = fleet_device_groups(n_shards, devices)
    if groups is None:
        return [None] * n_shards
    return [Mesh(np.asarray(g), ("data",)) for g in groups]


def state_logical(state_tree) -> Any:
    """Decode cache/state logical specs, keyed on (leaf name, rank).

    KV caches shard (batch, kv-heads); SSM/RG-LRU states shard (batch, inner
    dim).  Stacked-layer tensors have the layer dim first; per-layer lists
    (whisper, lstm) have batch first.
    """

    def walk(path, x):
        shape = x.shape
        nd = len(shape)
        name = ""
        for p in reversed(path):
            k = getattr(p, "key", None)
            if isinstance(k, str):
                name = k
                break
        if nd == 0:
            return None
        if name in ("k", "v"):
            if nd == 5:  # (L, B, S, KVH, D)
                return (None, "batch", None, "kv", "head_dim")
            if nd == 4:  # (B, S, KVH, D)  [whisper lists]
                return ("batch", None, "kv", "head_dim")
        if name in ("k_scale", "v_scale"):
            if nd == 4:  # (L, B, S, KVH)
                return (None, "batch", None, "kv")
            if nd == 3:
                return ("batch", None, "kv")
        if name == "h":
            if nd == 4:  # mamba (L, B, d_inner, N)
                return (None, "batch", "mlp", None)
            if nd == 3:  # rg-lru (L, B, d_rnn)
                return (None, "batch", "mlp")
            if nd == 2:  # lstm (B, d)
                return ("batch", "mlp")
        if name == "conv":
            if nd == 4:  # (L, B, K-1, D)
                return (None, "batch", None, "mlp")
            if nd == 3:
                return ("batch", None, "mlp")
        if name == "c" and nd == 2:  # lstm cell state
            return ("batch", "mlp")
        # fallback: stacked-layer tensors (L, B, ...) vs direct (B, ...)
        if nd >= 3:
            return (None, "batch") + (None,) * (nd - 2)
        return ("batch",) + (None,) * (nd - 1)

    flat, treedef = jax.tree_util.tree_flatten_with_path(state_tree)
    return jax.tree_util.tree_unflatten(
        treedef, [walk(p, l) for p, l in flat])
