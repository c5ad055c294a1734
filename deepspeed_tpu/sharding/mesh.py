"""Mesh derivation from device topology, including 2-level hybrid
ICI×DCN meshes for multi-slice scale-out.

``build_mesh()`` resolves the configured axis sizes over the available
devices and — when the devices span more than one *granule* (a TPU
slice, a host process, or a ``DS_DCN_SLICES``-simulated slice) —
arranges them ``create_hybrid_device_mesh``-style so only the
DCN-tolerant outer axes (``pipe``, ``data``) cross the slow inter-slice
links while ``model``/``seq`` stay inside a slice's ICI domain (the
T5X/scaling-book recipe, SNIPPETS.md [1]; the reference tunes NCCL
hierarchies for the same reason, SURVEY §2.6).

The returned :class:`MeshTopology` is the descriptor the comm layer's
policy table keys on: per-axis ICI/DCN factoring, slice count, and
link-kind queries (``crosses_dcn``), so collective strategy selection
can stay dense intra-slice and compress inter-slice (docs/comm.md).
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from deepspeed_tpu.utils.logging import logger

# Canonical axis order: outermost (slowest-varying, most DCN-tolerant)
# first.  pipe and data tolerate slower links; model/seq need the
# fastest ICI, so they are innermost (adjacent device ids share a
# physical link on TPU slices).
MESH_AXES: Tuple[str, ...] = ("pipe", "data", "fsdp", "seq", "model", "expert")

LINK_ICI = "ici"
LINK_DCN = "dcn"
LINK_MIXED = "ici+dcn"


def resolve_mesh_shape(cfg, n_devices: int) -> Dict[str, int]:
    """Fill in the -1 ("remaining") axis and validate the product."""
    sizes = {ax: int(getattr(cfg, ax)) for ax in MESH_AXES}
    free = [ax for ax, s in sizes.items() if s == -1]
    if len(free) > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {free}")
    fixed = 1
    for ax, s in sizes.items():
        if s != -1:
            if s < 1:
                raise ValueError(f"mesh axis {ax} must be >=1 or -1, got {s}")
            fixed *= s
    if free:
        rem, mod = divmod(n_devices, fixed)
        if mod:
            raise ValueError(f"{n_devices} devices not divisible by fixed axes product {fixed}")
        sizes[free[0]] = rem
    total = int(np.prod(list(sizes.values())))
    if total != n_devices:
        raise ValueError(f"Mesh {sizes} covers {total} devices but {n_devices} are available")
    return sizes


def split_dcn_ici(sizes: Dict[str, int], n_granules: int) -> Optional[Tuple[Dict[str, int], Dict[str, int]]]:
    """Factor each axis into (DCN, ICI) parts: the granule count is
    absorbed by the outermost (most DCN-tolerant) axes first — ``pipe``
    and ``data`` ride the slow inter-granule links, while
    ``model``/``seq`` stay inside a granule's ICI domain.  Returns
    ``(dcn_sizes, ici_sizes)`` or None when the granule count cannot be
    factored into the axis sizes."""
    dcn = {ax: 1 for ax in sizes}
    ici = dict(sizes)
    left = n_granules
    # outermost first; tolerate meshes missing some canonical axes
    order = [ax for ax in MESH_AXES if ax in ici] + [ax for ax in ici if ax not in MESH_AXES]
    for ax in order:
        if left == 1:
            break
        f = math.gcd(left, ici[ax])
        # absorb the largest factor of `left` that divides this axis
        while f > 1 and left % f == 0 and ici[ax] % f == 0:
            dcn[ax] *= f
            ici[ax] //= f
            left //= f
            f = math.gcd(left, ici[ax])
    return None if left != 1 else (dcn, ici)


@dataclasses.dataclass(frozen=True)
class MeshTopology:
    """Per-axis ICI/DCN factoring of a device mesh — the topology
    descriptor layout and comm decisions key on."""

    sizes: Dict[str, int]
    dcn: Dict[str, int]
    ici: Dict[str, int]

    @classmethod
    def single_slice(cls, sizes: Dict[str, int]) -> "MeshTopology":
        return cls(sizes=dict(sizes), dcn={ax: 1 for ax in sizes}, ici=dict(sizes))

    @property
    def num_slices(self) -> int:
        return int(np.prod(list(self.dcn.values())))

    @property
    def slice_devices(self) -> int:
        return int(np.prod(list(self.ici.values())))

    def link(self, axis: str) -> str:
        """The link kind an exchange over ``axis`` rides: ``ici`` (all
        inside one slice), ``dcn`` (every hop crosses slices), or
        ``ici+dcn`` (a 2-level hierarchy)."""
        d, i = self.dcn.get(axis, 1), self.ici.get(axis, 1)
        if d > 1 and i > 1:
            return LINK_MIXED
        if d > 1:
            return LINK_DCN
        return LINK_ICI

    def crosses_dcn(self, axes) -> bool:
        names = axes if isinstance(axes, (tuple, list)) else (axes,)
        return any(self.dcn.get(a, 1) > 1 for a in names)

    def dcn_ranks(self, axes) -> int:
        names = axes if isinstance(axes, (tuple, list)) else (axes,)
        return int(np.prod([self.dcn.get(a, 1) for a in names]))

    def ici_ranks(self, axes) -> int:
        names = axes if isinstance(axes, (tuple, list)) else (axes,)
        return int(np.prod([self.ici.get(a, 1) for a in names]))

    def describe(self) -> str:
        if self.num_slices <= 1:
            return "single slice (all-ICI)"
        dcn = "×".join(str(self.dcn[ax]) for ax in MESH_AXES if ax in self.dcn)
        ici = "×".join(str(self.ici[ax]) for ax in MESH_AXES if ax in self.ici)
        return f"{self.num_slices} slices: dcn={dcn} ici={ici}"


# ---------------------------------------------------------------------------
# granule detection: what shares fast ICI?
# ---------------------------------------------------------------------------

def _granules(devices: Sequence) -> Optional[List[List]]:
    """Split ``devices`` into ICI granules: ``DS_DCN_SLICES=K``
    (simulation / explicit override) > TPU ``slice_index`` metadata >
    one-granule-per-process (multi-host without slice metadata)."""
    import jax

    env = os.environ.get("DS_DCN_SLICES", "")
    if env:
        k = int(env)
        if k > 1:
            if len(devices) % k:
                raise ValueError(
                    f"DS_DCN_SLICES={k} does not divide {len(devices)} devices"
                )
            per = len(devices) // k
            return [list(devices[i * per : (i + 1) * per]) for i in range(k)]
        return None
    slice_ids = [getattr(d, "slice_index", None) for d in devices]
    if all(s is not None for s in slice_ids) and len(set(slice_ids)) > 1:
        by: Dict[int, List] = {}
        for d, s in zip(devices, slice_ids):
            by.setdefault(s, []).append(d)
        groups = [by[s] for s in sorted(by)]
        if len({len(g) for g in groups}) == 1:
            return groups
        logger.warning("uneven slice_index granules; treating mesh as single-slice")
        return None
    if jax.process_count() > 1 and len(devices) == jax.device_count():
        by = {}
        for d in devices:
            by.setdefault(d.process_index, []).append(d)
        groups = [by[p] for p in sorted(by)]
        if len({len(g) for g in groups}) == 1:
            return groups
    return None


def _assemble_hybrid(granules: List[List], dcn: Dict[str, int], ici: Dict[str, int]) -> np.ndarray:
    """Place each granule's devices as one contiguous ICI block of the
    final mesh array: axis index = dcn_idx * ici_size + ici_idx, so
    within-block neighbors share ICI and only block boundaries cross
    DCN (the ``create_hybrid_device_mesh`` arrangement, built directly
    from the granule lists so it also works for simulated slices)."""
    ici_shape = tuple(ici[ax] for ax in MESH_AXES)
    dcn_shape = tuple(dcn[ax] for ax in MESH_AXES)
    final = tuple(d * i for d, i in zip(dcn_shape, ici_shape))
    out = np.empty(final, dtype=object)
    for gi, gdevs in enumerate(granules):
        didx = np.unravel_index(gi, dcn_shape)
        block = np.asarray(gdevs, dtype=object).reshape(ici_shape)
        slices = tuple(slice(d * i, (d + 1) * i) for d, i in zip(didx, ici_shape))
        out[slices] = block
    return out


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------

def build_mesh(cfg=None, devices: Optional[Sequence] = None):
    """Build the framework mesh over the given (default: all) devices
    and derive its :class:`MeshTopology`.

    Returns ``(mesh, topology)``.  Single-granule device sets get
    ``mesh_utils.create_device_mesh``'s arrangement (ICI-aware on a TPU,
    flat elsewhere); multi-granule sets get the 2-level
    hybrid arrangement (real TPU multi-slice/multi-host via
    ``mesh_utils.create_hybrid_device_mesh`` when its metadata is
    usable, else direct granule-block assembly)."""
    import jax
    from jax.sharding import Mesh

    if cfg is None:
        from deepspeed_tpu.config.config import MeshConfig

        cfg = MeshConfig()
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    sizes = resolve_mesh_shape(cfg, len(devices))
    shape = tuple(sizes[ax] for ax in MESH_AXES)

    granules = _granules(devices)
    dev_array = None
    topology = MeshTopology.single_slice(sizes)
    if granules is not None and len(granules) > 1:
        split = split_dcn_ici(sizes, len(granules))
        if split is not None:
            dcn, ici = split
            topology = MeshTopology(sizes=sizes, dcn=dcn, ici=ici)
            if jax.process_count() > 1 and not os.environ.get("DS_DCN_SLICES"):
                try:
                    from jax.experimental import mesh_utils

                    # process_is_granule: our dcn factors come from the
                    # granule count, so each process is one granule (the
                    # default groups by slice_index, which only matches
                    # when processes == slices)
                    dev_array = mesh_utils.create_hybrid_device_mesh(
                        tuple(ici[ax] for ax in MESH_AXES),
                        tuple(dcn[ax] for ax in MESH_AXES),
                        devices=devices,
                        process_is_granule=len(granules) == jax.process_count(),
                    )
                except Exception as e:
                    logger.warning(f"create_hybrid_device_mesh failed ({e}); assembling granule blocks directly")
            if dev_array is None:
                dev_array = _assemble_hybrid(granules, dcn, ici)
            logger.info(f"hybrid mesh: {topology.describe()}")
        else:
            logger.warning(
                f"{len(granules)} granules do not factor into mesh {sizes}; "
                "using flat device order (cross-slice collectives may ride slow links)"
            )
    if dev_array is None:
        # ICI-aware order within one slice (on a v5e 2x2 host a 4-way
        # axis becomes the physical ring 0-1-3-2, where the flat device
        # order would hop the diagonal twice); a plain reshape off-TPU
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices)
    mesh = Mesh(dev_array, MESH_AXES)
    logger.info(
        "mesh: " + " × ".join(f"{ax}={sizes[ax]}" for ax in MESH_AXES if sizes[ax] > 1 or ax == "data")
    )
    return mesh, topology


def derive_topology(mesh) -> MeshTopology:
    """Best-effort topology for a caller-provided mesh: factor the axis
    sizes by the granule count of its devices (DS_DCN_SLICES simulation,
    TPU slice metadata, or processes); all-ICI when single-granule or
    the factoring fails.  A mesh built by :func:`build_mesh` should use
    the topology returned alongside it instead."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    devices = list(mesh.devices.flat)
    granules = _granules(devices)
    if granules is None or len(granules) <= 1:
        return MeshTopology.single_slice(sizes)
    split = split_dcn_ici(sizes, len(granules))
    if split is None:
        return MeshTopology.single_slice(sizes)
    dcn, ici = split
    return MeshTopology(sizes=sizes, dcn=dcn, ici=ici)
