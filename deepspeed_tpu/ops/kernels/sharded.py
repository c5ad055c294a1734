"""Mosaic kernels on a multi-device mesh.

A compiled Pallas kernel is a custom call the GSPMD partitioner cannot
split, and JAX refuses to lower one inside a multi-device ``jit``
("Mosaic kernels cannot be automatically partitioned").  So on a mesh of
more than one device a kernel runs under ``shard_map`` with every mesh
axis manual: the caller names the mesh axes each operand dim prefers, a
dim the axes do not divide stays whole (every device of those axes then
computes the same rows), and the kernel sees per-device shapes.

The mesh is the one the trace runs under: the enclosing ``shard_map``'s
(pipeline stages, ring/Ulysses bodies — only its still-automatic axes
are taken) or the tracing engine's ambient mesh
(``parallel.sequence.scoped_to``).  Interpret-mode kernels are plain
ops that partition like any other and never come here.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec

Axes = Union[str, Tuple[str, ...], None]


def free_mesh_axes() -> Dict[str, int]:
    """Sizes of the mesh axes a kernel traced here must be mapped over:
    ``{}`` when the trace targets one device."""
    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty:
        sizes = dict(zip(ctx.axis_names, ctx.axis_sizes))
        return {a: n for a, n in sizes.items() if a not in ctx.manual_axes}
    from deepspeed_tpu.parallel.sequence import get_global_mesh

    mesh = get_global_mesh()
    if mesh is None or mesh.devices.size == 1:
        return {}
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dim_spec(shape: Sequence[int], prefs: Dict[int, Axes], sizes: Dict[str, int]) -> PartitionSpec:
    """``prefs[dim]`` where those axes (the ones of ``sizes`` larger
    than 1) divide ``shape[dim]``; the dim whole otherwise."""
    spec: list = [None] * len(shape)
    for dim, axes in prefs.items():
        names = (axes,) if isinstance(axes, str) else tuple(axes or ())
        names = tuple(a for a in names if sizes.get(a, 1) > 1)
        if names and shape[dim] % math.prod(sizes[a] for a in names) == 0:
            spec[dim] = names if len(names) > 1 else names[0]
    return PartitionSpec(*spec)


def spec_axes(spec: PartitionSpec) -> Tuple[str, ...]:
    """The mesh axes a spec shards over, in dim order."""
    out: list = []
    for entry in spec:
        if entry is not None:
            out.extend((entry,) if isinstance(entry, str) else entry)
    return tuple(out)


def shard_call(fn: Callable, operands: Sequence, in_specs: Sequence[Optional[PartitionSpec]],
               out_specs, sizes: Dict[str, int]):
    """``fn(*operands)`` with the axes of ``sizes`` manual.  ``None``
    operands pass through (their spec is ignored)."""
    live = [i for i, x in enumerate(operands) if x is not None]

    def body(*present):
        full = list(operands)
        for i, x in zip(live, present):
            full[i] = x
        return fn(*full)

    ctx = jax.sharding.get_abstract_mesh()
    if ctx.empty:
        from deepspeed_tpu.parallel.sequence import get_global_mesh

        kw = {"mesh": get_global_mesh()}
    else:
        # nested in a partly-manual region: the context mesh, and only
        # the axes it has not already made manual
        kw = {"axis_names": set(sizes)}
    mapped = jax.shard_map(
        body, in_specs=tuple(in_specs[i] for i in live), out_specs=out_specs,
        check_vma=False, **kw,
    )
    return mapped(*(operands[i] for i in live))
