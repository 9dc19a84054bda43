"""Multi-device ZNS arrays: log-structured RAID over emulated devices.

``ZNSArray`` stripes logical superzones across N :class:`ZNSDevice`
members at zone-chunk granularity with optional RAID-5-style
log-structured parity, and implements the same
:class:`repro.core.backend.ZoneBackend` surface as a single device --
``ZoneFS`` and everything above it mount either interchangeably.

:class:`ArrayEngine` is the engine-native port of the same state
machine: zone commands compile to encoded per-member op programs that
execute in ONE batched ``run_programs`` dispatch (K arrays with mixed
member counts / chunk sizes / parity / element specs per batch), with
the object ``ZNSArray`` kept as the bit-exactness oracle.
``repro.array.storm`` runs batched rebuild storms on top of it.
"""

from repro.array.engine import (ArrayEngine, ArrayResult, apply_commands,
                                fill_commands, merge_rebuild,
                                plan_rebuild, run_array_batch,
                                run_array_timing)
from repro.array.raid import (ArrayGeometry, SuperZoneInfo, TaggedTrace,
                              ZNSArray, data_device_of, locate_page,
                              member_chunk_pages, parity_device_of)
from repro.array.storm import StormScenario, rebuild_storm

__all__ = ["ArrayEngine", "ArrayGeometry", "ArrayResult", "StormScenario",
           "SuperZoneInfo", "TaggedTrace", "ZNSArray", "apply_commands",
           "data_device_of", "fill_commands",
           "locate_page", "member_chunk_pages", "merge_rebuild",
           "parity_device_of", "plan_rebuild", "rebuild_storm",
           "run_array_batch", "run_array_timing"]
