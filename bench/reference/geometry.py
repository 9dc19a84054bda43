"""Flash and zone geometry (paper §3, §6.1).

The benchmark's own copy of the simulator's geometry, so that the
reference imports nothing of the simulator.  Blocks are numbered
LUN-major: ``block = lun * blocks_per_lun + offset``.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class FlashGeometry:
    """Physical geometry of the emulated flash device (times in s)."""

    n_channels: int
    ways_per_channel: int
    blocks_per_lun: int
    pages_per_block: int
    page_bytes: int
    t_prog: float = 500e-6
    t_read: float = 50e-6
    t_erase: float = 5e-3
    t_xfer: float = 25e-6

    @property
    def n_luns(self) -> int:
        return self.n_channels * self.ways_per_channel

    @property
    def n_blocks(self) -> int:
        return self.n_luns * self.blocks_per_lun


@dataclasses.dataclass(frozen=True)
class ZoneGeometry:
    """Logical zone shape: P LUNs of parallelism x n_segments segments."""

    parallelism: int
    n_segments: int

    @property
    def blocks_per_zone(self) -> int:
        return self.parallelism * self.n_segments

    def zone_pages(self, flash: FlashGeometry) -> int:
        return self.blocks_per_zone * flash.pages_per_block
