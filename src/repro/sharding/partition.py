"""Logical-axis sharding rules (DP/TP/EP/SP + FSDP 'embed' axis).

Every parameter is declared with a tuple of *logical* axis names; these
map onto physical mesh axes:

    batch    -> ('pod', 'data')    data parallel (pod is outer DP)
    embed    -> 'data'             FSDP: weight-shard over the data axis,
                                   all-gathered per layer by GSPMD/scan
    vocab    -> 'model'            TP on the embedding/logits dim
    heads    -> 'model'            TP on attention heads
    kv_heads -> 'model'            TP on KV heads (replicated if indivisible)
    mlp      -> 'model'            TP on the FFN hidden dim
    experts  -> 'model'            EP: expert dim over the model axis
    seq      -> 'data'             SP for long-context decode (batch=1)
    layers   -> (unsharded)        the scan axis

Divisibility is checked against the actual mesh: any dim that does not
divide evenly falls back to replication for that dim (e.g. granite's
kv=1 MQA heads).  A mesh axis is never used twice in one spec.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


LOGICAL_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "embed": ("data",),
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "experts": ("model",),
    "seq": ("data",),
    "seq_sp": ("pod", "data"),
    "layers": (),
    "null": (),
}

# Parallelism profiles: the mesh is fixed (16x16 / 2x16x16) but the
# LOGICAL->PHYSICAL mapping is a per-arch choice (§Perf lever).
#   tp_fsdp — TP over 'model' + batch over 'data' + FSDP weight-shard
#             over 'data' (the baseline; right for >=50B models).
#   dp_fsdp — pure data parallel over BOTH axes + FSDP weight storage
#             over 'model' (gathered per layer); right for small dense
#             models where TP collectives dwarf compute. Not valid for
#             MoE archs (the expert shard_map needs 'model').
PROFILES: dict[str, dict] = {
    "tp_fsdp": LOGICAL_RULES,
    # tp_sp_fsdp — tp_fsdp + Megatron-style sequence parallelism: the
    # residual stream between layers is sharded over 'model' on the SEQ
    # dim ("seq_tp"), so the per-layer saved activations (the remat x
    # stack — 95 GB/dev for deepseek-67b train!) shrink by the model
    # size; GSPMD inserts the all-gather/reduce-scatter pairs at the
    # attention boundary.
    "tp_sp_fsdp": dict(LOGICAL_RULES, seq_tp=("model",)),
    "dp_fsdp": {
        "batch": ("pod", "data", "model"),
        "embed": ("model",),     # FSDP storage shard, gathered per layer
        "vocab": (),
        "heads": (),
        "kv_heads": (),
        "mlp": (),
        "experts": (),
        "seq": (),
        "seq_sp": (),
        "layers": (),
        "null": (),
    },
}


def _mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def spec_for(logical_axes: tuple, shape: tuple, mesh: Mesh,
             rules: dict | None = None) -> P:
    """Resolve logical axes -> PartitionSpec, honoring divisibility and
    never reusing a mesh axis."""
    rules = rules or LOGICAL_RULES
    sizes = _mesh_axis_sizes(mesh)
    used: set[str] = set()
    out: list = []
    for dim, name in zip(shape, logical_axes):
        if name is None or name not in rules:
            out.append(None)
            continue
        phys = [a for a in rules[name] if a in sizes and a not in used]
        # drop trailing axes until the dim divides
        while phys and dim % int(np.prod([sizes[a] for a in phys])):
            phys = phys[1:]
        if not phys:
            out.append(None)
        else:
            used.update(phys)
            out.append(tuple(phys) if len(phys) > 1 else phys[0])
    return P(*out)


def named_sharding(logical_axes: tuple, shape: tuple, mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, spec_for(logical_axes, shape, mesh))


def cache_spec_for(logical_axes: tuple, shape: tuple, mesh: Mesh) -> P:
    """KV-cache sharding: prefer kv_heads over 'model'; when the head
    count does not divide the model axis (MQA/GQA), shard the cache
    SEQUENCE dim over 'model' instead (flash-decoding style KV-parallel
    attention) so the cache never replicates across the model axis."""
    sizes = _mesh_axis_sizes(mesh)
    sp = list(spec_for(logical_axes, shape, mesh))
    used = {a for dim in sp if dim
            for a in (dim if isinstance(dim, tuple) else (dim,))}
    if "model" in sizes and "model" not in used and "seq" in logical_axes:
        i = logical_axes.index("seq")
        if sp[i] is None and shape[i] % sizes["model"] == 0:
            sp[i] = "model"
    return P(*sp)


@dataclass(frozen=True)
class MeshContext:
    """Distribution context threaded through model code. ``None`` mesh =
    single-device (smoke tests); all helpers become no-ops."""

    mesh: Mesh | None = None
    profile: str = "tp_fsdp"

    @property
    def rules(self) -> dict:
        return PROFILES[self.profile]

    @property
    def batch_axes(self) -> tuple[str, ...]:
        if self.mesh is None:
            return ()
        return tuple(a for a in self.rules["batch"] if a in self.mesh.axis_names)

    @property
    def model_axis(self) -> str | None:
        if self.mesh is None or "model" not in self.mesh.axis_names:
            return None
        return "model"

    def axis_size(self, name: str) -> int:
        if self.mesh is None:
            return 1
        sizes = _mesh_axis_sizes(self.mesh)
        return sizes.get(name, 1)

    @property
    def data_shards(self) -> int:
        return int(np.prod([self.axis_size(a) for a in self.batch_axes])) or 1

    def constrain(self, x, *logical_axes):
        """with_sharding_constraint via logical axes (no-op without mesh)."""
        if self.mesh is None:
            return x
        spec = spec_for(tuple(logical_axes), x.shape, self.mesh, self.rules)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def sharding(self, logical_axes: tuple, shape: tuple) -> NamedSharding | None:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, spec_for(logical_axes, shape,
                                                 self.mesh, self.rules))


NULL_CTX = MeshContext(None)
