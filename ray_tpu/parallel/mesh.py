"""Device-mesh construction: the substrate of every parallelism strategy.

This is the TPU-native answer to the reference's per-strategy plumbing
(SURVEY §2.4): where the reference wires NCCL process groups per strategy
(DDP via torch PGs, collective groups via cupy NCCL), here every strategy —
DP / ZeRO / TP / PP / SP / EP — is an *axis of one jax Mesh*, and XLA
inserts the collectives (psum over `dp`, reduce-scatter over `fsdp`,
ppermute over `sp`, all-to-all over `ep`) that ride ICI.  The exception is
fsdp's all-gather: a model states it where a layer is used (gpt2.py
`backbone`): left alone, the partitioner's cost model may keep the weights
sharded and gather the batch instead.

Axis conventions (matching the scaling-book vocabulary):
  dp    — data parallel (gradient psum)
  fsdp  — ZeRO-style parameter/optimizer sharding inside each layer, never
          on a dim a scan walks (one layer gathered on use); also batch
  tp    — tensor parallel (intra-layer, megatron-style)
  pp    — pipeline stages
  sp    — sequence/context parallel (ring attention)
  ep    — expert parallel (MoE all-to-all)
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

AXIS_ORDER = ("dp", "fsdp", "pp", "sp", "ep", "tp")


@dataclass
class MeshConfig:
    """Logical mesh shape.  Unspecified axes default to 1 and are dropped
    unless keep_unit_axes is set (kept axes still appear in PartitionSpecs,
    which makes specs portable across scales)."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1
    keep_unit_axes: bool = True

    def axis_sizes(self) -> Dict[str, int]:
        return {name: int(getattr(self, name)) for name in AXIS_ORDER}

    def total_devices(self) -> int:
        n = 1
        for v in self.axis_sizes().values():
            n *= v
        return n

    @classmethod
    def for_devices(cls, n: int, *, tp: int = 1, sp: int = 1, fsdp: int = 1) -> "MeshConfig":
        """Fill the dp axis with whatever is left after explicit axes."""
        rest = tp * sp * fsdp
        if n % rest:
            raise ValueError(f"{n} devices not divisible by tp*sp*fsdp={rest}")
        return cls(dp=n // rest, tp=tp, sp=sp, fsdp=fsdp)


def make_mesh(config: MeshConfig, devices: Optional[Sequence] = None):
    """Build a jax.sharding.Mesh laid out so the fastest-varying axes (tp,
    last in AXIS_ORDER) map to nearest ICI neighbors — tensor-parallel
    collectives are the most latency-sensitive, so they get the shortest
    rings (the standard v4/v5 layout recipe)."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    sizes = config.axis_sizes()
    needed = config.total_devices()
    if needed > len(devices):
        raise ValueError(f"mesh needs {needed} devices, have {len(devices)}")
    devices = list(devices)[:needed]
    if config.keep_unit_axes:
        names = list(AXIS_ORDER)
        shape = [sizes[a] for a in names]
    else:
        names = [a for a in AXIS_ORDER if sizes[a] > 1] or ["dp"]
        shape = [sizes[a] for a in names]
    arr = np.array(devices).reshape(shape)
    return Mesh(arr, axis_names=tuple(names))


def shard_map_compat(fn, mesh, in_specs, out_specs, manual_axes=None):
    """``jax.shard_map`` with this package's conventions (no replication
    check) — the single shared wrapper for every SPMD helper here.

    manual_axes: restrict manual collectives to this subset of mesh axes —
    the REST stay compiler-managed ("auto") inside the body, so e.g. a
    GPipe schedule manual over pp can keep tp-sharded in-stage matmuls
    with XLA-inserted collectives (pp×tp composition)."""
    import jax

    kw = {}
    if manual_axes is not None and set(manual_axes) != set(mesh.axis_names):
        kw["axis_names"] = frozenset(manual_axes)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False, **kw
    )


def data_pspec(mesh) -> "object":
    """PartitionSpec for a [batch, ...] input: batch sharded over every
    data-ish axis present (dp and fsdp both consume batch)."""
    from jax.sharding import PartitionSpec as P

    batch_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
    return P(batch_axes if batch_axes else None)


def prune_pspec(spec, mesh, drop: Sequence[str] = ()) -> "object":
    """`spec` without the axes the mesh lacks or has at size 1 (a
    tp-annotated model on a pure-dp mesh), and without those in `drop`."""
    from jax.sharding import PartitionSpec as P

    def prune(entry):
        axes = tuple(
            a
            for a in (entry if isinstance(entry, tuple) else (entry,))
            if a in mesh.axis_names and mesh.shape[a] > 1 and a not in drop
        )
        return axes[0] if len(axes) == 1 else (axes or None)

    return P(*(prune(entry) for entry in spec))


def replicated_pspec() -> "object":
    from jax.sharding import PartitionSpec as P

    return P()


def batch_size_multiple(mesh) -> int:
    """Global batch must divide by this (product of data axes)."""
    n = 1
    for a in ("dp", "fsdp"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n
