"""Static and per-lane configuration values of a union device, from its
geometry and element specs alone.

The benchmark's own derivation (after the simulator's
``make_config`` / ``make_union_config`` / ``make_dyn``): the padded
element grid every lane of one dispatch shares, and the values one lane
runs with.  Nothing here reads the simulator's engine.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Sequence

import numpy as np

from . import zns
from .elements import (ElementKind, ElementSpec, build_layout,
                       elements_per_zone, groups_per_zone)
from .geometry import FlashGeometry, ZoneGeometry
from .model import POLICY_SILENT, POLICY_TRADITIONAL

_BIG = 2**30


@dataclasses.dataclass(frozen=True)
class Static:
    """The padded static configuration the model walks lanes over."""

    kind: ElementKind
    n_elements: int
    n_groups: int
    per_group: int
    take: int
    zone_groups: int
    n_slots: int
    parallelism: int
    n_segments: int
    pages_per_block: int
    zone_pages: int
    n_zones: int
    max_active: int
    members: Dict[str, Dict[str, int]]


def _slot_stride(spec: ElementSpec, parallelism: int) -> int:
    if spec.kind in (ElementKind.BLOCK, ElementKind.HCHUNK):
        return parallelism
    if spec.kind is ElementKind.VCHUNK:
        return parallelism // spec.chunk
    return 1   # SUPERBLOCK, FIXED


def _member(flash: FlashGeometry, zone: ZoneGeometry, spec: ElementSpec):
    lay = build_layout(flash, spec, zone)
    elems = elements_per_zone(lay, zone)
    zgroups = groups_per_zone(lay, zone)
    return lay, {
        "n_elements": lay.n_elements,
        "per_group": lay.n_elements // lay.n_groups,
        "take": elems // zgroups,
        "zone_groups": zgroups,
        "slot_stride": _slot_stride(spec, zone.parallelism),
        "pages_per_element": lay.pages_per_element,
    }


def union_static(flash: FlashGeometry, zone: ZoneGeometry,
                 specs: Sequence[ElementSpec], max_active: int) -> Static:
    """The padded grid at the maximum geometry of ``specs``."""
    built = {s.name: _member(flash, zone, s) for s in specs}
    lays = [lay for lay, _ in built.values()]
    vals = [v for _, v in built.values()]
    n_groups = max(lay.n_groups for lay in lays)
    per_group = max(v["per_group"] for v in vals)
    return Static(
        kind=specs[0].kind,
        n_elements=n_groups * per_group,
        n_groups=n_groups,
        per_group=per_group,
        take=max(v["take"] for v in vals),
        zone_groups=max(v["zone_groups"] for v in vals),
        n_slots=max(zns.n_slots(s, zone.parallelism, zone.n_segments)
                    for s in specs),
        parallelism=zone.parallelism,
        n_segments=zone.n_segments,
        pages_per_block=flash.pages_per_block,
        zone_pages=zone.zone_pages(flash),
        n_zones=flash.n_blocks // zone.blocks_per_zone,
        max_active=max_active,
        members={name: v for name, (_, v) in built.items()},
    )


def lane_values(static: Static, spec: ElementSpec, *, zone_pages=None,
                wear_aware: bool = True,
                alloc_policy: str = "traditional") -> Dict[str, int]:
    """The effective values one lane of ``spec`` runs with."""
    policy = {"traditional": POLICY_TRADITIONAL,
              "silent": POLICY_SILENT}[alloc_policy]
    return {"zone_pages": static.zone_pages if zone_pages is None
            else zone_pages,
            "max_active": static.max_active, "n_zones": static.n_zones,
            "wear_aware": bool(wear_aware), **static.members[spec.name],
            "alloc_policy": policy, "wear_bound": _BIG}


def real_cells(static: Static, values: Dict[str, int]):
    """Boolean mask of the padded grid's cells that are this lane's own
    elements (a member's element ``(g, c)`` sits at ``g * per_group +
    c`` of the grid)."""
    ids = np.arange(static.n_elements)
    g, c = ids // static.per_group, ids % static.per_group
    return ((g < values["n_elements"] // values["per_group"])
            & (c < values["per_group"]))
