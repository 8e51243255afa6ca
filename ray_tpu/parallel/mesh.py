"""Device mesh construction and sharding rules.

The TPU-native replacement for everything the reference delegates to external
parallelism frameworks (SURVEY.md §2.3: TP/PP via Accelerate/DeepSpeed/Alpa;
SP/CP/EP absent): parallelism here is a *named mesh axis*, and a strategy is a
set of PartitionSpec rules over those axes.

Axes (any subset, any sizes whose product = device count):
- ``dp``  — data parallel (batch dim; grads psum over dp)
- ``fsdp`` — fully-sharded data parallel (params sharded over fsdp, gathered
  per-layer; batch also sharded — zero-3 style)
- ``tp``  — tensor parallel (hidden/heads dims; activations all-reduce over tp)
- ``pp``  — pipeline parallel (layers dim; activations ppermute between stages)
- ``sp``  — sequence/context parallel (sequence dim; ring attention/Ulysses)
- ``ep``  — expert parallel (experts dim; all_to_all token dispatch)
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

AXIS_ORDER = ("pp", "dp", "fsdp", "sp", "ep", "tp")


@dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh shape. -1 on at most one axis = fill with remaining devices."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1

    def axis_sizes(self) -> dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    def resolve(self, num_devices: int) -> dict[str, int]:
        sizes = self.axis_sizes()
        unknown = [a for a, s in sizes.items() if s == -1]
        if len(unknown) > 1:
            raise ValueError("at most one mesh axis may be -1")
        known = math.prod(s for s in sizes.values() if s != -1)
        if unknown:
            if num_devices % known:
                raise ValueError(
                    f"{num_devices} devices not divisible by fixed axes product {known}"
                )
            sizes[unknown[0]] = num_devices // known
        if math.prod(sizes.values()) != num_devices:
            raise ValueError(
                f"mesh {sizes} does not cover {num_devices} devices"
            )
        return sizes


def create_mesh(config: MeshConfig | None = None, devices=None, **axis_sizes):
    """Build a jax Mesh. ICI-aware ordering: the innermost (fastest-varying)
    axes are tp/ep/sp — the axes with the heaviest collectives — so their
    collectives ride neighbouring ICI links; pp/dp are outermost, matching the
    scaling-book recipe (DCN-tolerant axes outermost)."""
    import jax
    from jax.sharding import Mesh

    if config is None:
        config = MeshConfig(**axis_sizes)
    devices = np.asarray(devices if devices is not None else jax.devices())
    sizes = config.resolve(devices.size)
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    return Mesh(devices.reshape(shape), AXIS_ORDER)


def single_axis_mesh(axis: str = "dp", devices=None):
    import jax
    from jax.sharding import Mesh

    devices = np.asarray(devices if devices is not None else jax.devices())
    shape = tuple(devices.size if a == axis else 1 for a in AXIS_ORDER)
    return Mesh(devices.reshape(shape), AXIS_ORDER)


# ---------------------------------------------------------------------------
# Logical-axis sharding rules (flax-style rules, applied to pytrees)
# ---------------------------------------------------------------------------

# Default rules for transformer-family models (models/transformer.py annotates
# params with these logical names).
DEFAULT_RULES: dict[str, tuple] = {
    "batch": ("dp", "fsdp"),
    "seq": ("sp",),
    "embed": ("fsdp",),
    "mlp": ("tp",),
    "heads": ("tp",),
    "kv": (),
    "vocab": ("tp",),
    "layers": ("pp",),
    "expert": ("ep",),
}


def logical_to_spec(logical_axes: tuple, rules: dict | None = None):
    """('batch','seq','embed') -> PartitionSpec(('dp','fsdp'), 'sp', 'fsdp')."""
    from jax.sharding import PartitionSpec as P

    rules = rules or DEFAULT_RULES
    out = []
    for name in logical_axes:
        mapped = rules.get(name, ())
        if isinstance(mapped, str):
            mapped = (mapped,)
        if len(mapped) == 0:
            out.append(None)
        elif len(mapped) == 1:
            out.append(mapped[0])
        else:
            out.append(tuple(mapped))
    return P(*out)


def shard_pytree(tree, mesh, spec_fn):
    """device_put a pytree with per-leaf NamedShardings from spec_fn(path, leaf)."""
    import jax
    from jax.sharding import NamedSharding

    def place(path, leaf):
        spec = spec_fn(path, leaf)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(place, tree)


def shard_by_logical_axes(tree, logical_axes, mesh, rules: dict | None = None):
    """device_put a pytree whose leaves a parallel tree of logical-axis tuples
    names (models' ``param_logical_axes``), each by ``logical_to_spec``."""

    def spec_for(path, _leaf):
        node = logical_axes
        for p in path:
            node = node[p.key]
        return logical_to_spec(node, rules)

    return shard_pytree(tree, mesh, spec_for)


def replicate_pytree(tree, mesh):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.tree.map(lambda x: jax.device_put(x, NamedSharding(mesh, P())), tree)
