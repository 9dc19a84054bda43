"""ZoneEngine: the device state machine as a pure pytree + scan programs.

The legacy :class:`repro.core.device_legacy.LegacyZNSDevice` executes every
WRITE/FINISH/RESET as a stateful Python call with a host->JAX round-trip
per allocation.  This module inverts that ownership: **all** device state
lives in a :class:`DeviceState` pytree of ``jnp`` arrays, and every zone
command is a pure jit-compiled transition

    apply_op(state, op_row) -> (state, OpTrace)

so an encoded ``(n_ops, 4)`` int32 *op program* runs in a single
``lax.scan`` (:func:`run_program`) with no per-op host round-trips, and a
batch of programs (e.g. a DLWA occupancy sweep) runs in one vmapped scan
(:func:`run_programs`).  Semantics are bit-exact with the legacy device --
the differential property tests in ``tests/test_engine_diff.py`` replay
random op sequences through both.

Op encoding (all int32): ``[opcode, zone, n_pages, flags]`` with flags
bit0 = host write (0 -> dummy/device-internal write).  Rows may carry
extra trailing columns (the fleet layer appends a *tenant* tag in column
4, see :mod:`repro.fleet.tenants`); the engine only reads the first four.
Illegal ops (FULL write, overflow, allocation failure, active-zone limit)
never raise: they apply exactly the partial effects the legacy device
leaves behind after its ``RuntimeError`` (e.g. an overflowing write still
opens the zone) and report ``ok=0`` in the trace.

Static configuration is a frozen hashable :class:`EngineConfig`, so the
jitted transitions are compile-cached *per device geometry/spec*, not per
engine instance.  A subset of the config -- the knobs that affect
*values* but not *array shapes* -- can additionally be overridden per
call (and per batch lane) with a traced :class:`DynConfig`: effective
zone capacity in pages, the active-zone limit, the addressable zone
count, the allocator's wear-awareness, and (since the union-config
extension) the whole *element spec*: ``n_elements`` / ``per_group`` /
``take`` / ``zone_groups`` / ``slot_stride`` / ``pages_per_element``
become per-lane values on a padded static layout built at the max
geometry of a spec set (:func:`make_union_config`).  This is what lets
a single ``run_programs`` dispatch batch a *heterogeneous* fleet:
every lane shares the padded static shapes of the largest
geometry/spec while its ``DynConfig`` selects the member's effective
element granularity, geometry and allocator (see :mod:`repro.fleet`).

Units: ``n_pages``/``zone_pages``/``wp`` count flash pages; ``wear`` and
``block_erases`` count erase-block erasures; zones and elements are
indexed densely from 0.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import zns
from repro.core.alloc_exact import (AVAIL_ALLOCATED, AVAIL_FREE,
                                    AVAIL_INVALID, AVAIL_VALID)
from repro.core.elements import (ElementKind, ElementLayout, ElementSpec,
                                 build_layout, elements_per_zone,
                                 groups_per_zone, union_grid_ids)
from repro.core.geometry import FlashGeometry, ZoneGeometry

# ----------------------------------------------------------------------- #
# op + zone-state encodings
# ----------------------------------------------------------------------- #
OP_NOP, OP_ALLOC, OP_WRITE, OP_FINISH, OP_RESET, OP_READ = range(6)
F_HOST = 1  # flags bit0: host (vs dummy) write

ZONE_EMPTY, ZONE_OPEN, ZONE_FULL = 0, 1, 2

# DynConfig.alloc_policy values: TRADITIONAL keeps the legacy fixed
# element-grid mapping (a zone's whole element set is committed at ALLOC
# time); SILENT is the paper's on-the-fly allocation -- a zone is an
# arbitrary block collection sized to the write at hand, chosen as the
# cheapest per-LUN set under a wear-leveling bound and grown on demand.
POLICY_TRADITIONAL, POLICY_SILENT = 0, 1
_POLICY_NAMES = {"traditional": POLICY_TRADITIONAL,
                 "silent": POLICY_SILENT}

_BIG = 2**30  # sentinel wear for unavailable slots (matches allocator.py)


# ----------------------------------------------------------------------- #
# static config + state pytree
# ----------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class SpecValues:
    """The value-only, spec-derived subset of :class:`EngineConfig`:
    everything one element spec contributes that a lane can shadow
    through a :class:`DynConfig` on a padded union layout.  All ints;
    ``pages_per_element`` in pages, the rest count elements / groups /
    slots."""

    n_elements: int
    per_group: int
    take: int
    zone_groups: int
    slot_stride: int
    pages_per_element: int


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Hashable static description of one device geometry/element spec.

    All fields are compile-time constants (they determine array shapes
    and loop structure).  Page-unit fields: ``pages_per_block``,
    ``zone_pages``, ``pages_per_element``; block-unit:
    ``blocks_per_element``; the rest count elements / groups / zones /
    LUN columns.  The *value-only* subset (``zone_pages``,
    ``max_active``, ``n_zones``, ``wear_aware``, plus the spec-derived
    :class:`SpecValues` fields) can be shadowed per call by a
    :class:`DynConfig`.

    ``members`` lists the element specs this config can host per lane:
    a plain :func:`make_config` has exactly its own spec; a
    :func:`make_union_config` built at the max geometry of a spec set
    has one entry per member, each carrying the member's
    :class:`SpecValues`.
    """

    kind: ElementKind
    chunk: int
    wear_aware: bool
    n_elements: int
    n_groups: int
    per_group: int
    luns_per_group: int
    take: int            # elements taken per winning group
    zone_groups: int     # winning groups per zone
    slot_stride: int     # slot = rank * slot_stride + window_position
    n_slots: int
    parallelism: int
    n_segments: int
    pages_per_block: int
    zone_pages: int
    pages_per_element: int
    blocks_per_element: int
    n_zones: int
    max_active: int
    n_channels: int
    members: Tuple[Tuple[ElementSpec, SpecValues], ...] = ()

    @property
    def spec(self) -> ElementSpec:
        return ElementSpec(self.kind, self.chunk)

    def member_values(self, spec: ElementSpec) -> SpecValues:
        """The :class:`SpecValues` of a member spec (raises
        ``ValueError`` for a spec this config was not built over)."""
        for s, v in self.members:
            if s == spec:
                return v
        raise ValueError(
            f"spec {spec.name} is not a member of this config "
            f"(members: {[s.name for s, _ in self.members]})")


class DeviceState(NamedTuple):
    """The whole device as a pytree.  Element arrays carry one trailing
    *scratch* slot (index ``n_elements``) absorbing masked scatters."""

    elem_wear: jax.Array    # (n_elements + 1,) i32
    elem_avail: jax.Array   # (n_elements + 1,) i32
    elem_pages: jax.Array   # (n_elements + 1,) i32
    elem_zone: jax.Array    # (n_elements + 1,) i32
    zone_state: jax.Array   # (n_zones,) i32
    zone_wp: jax.Array      # (n_zones,) i32
    zone_host_wp: jax.Array  # (n_zones,) i32
    zone_elems: jax.Array   # (n_zones, n_slots) i32, -1 = unmapped/released
    zone_cols: jax.Array    # (n_zones, parallelism) i32 zone column -> LUN
    rr_next: jax.Array      # () i32 round-robin window start
    n_active: jax.Array     # () i32 OPEN zone count
    host_pages: jax.Array   # () i32
    dummy_pages: jax.Array  # () i32
    block_erases: jax.Array  # () i32
    alloc_calls: jax.Array  # () i32


class OpTrace(NamedTuple):
    """Per-op trace slice: enough to rebuild IO streams host-side."""

    op: jax.Array          # () i32
    zone: jax.Array        # () i32
    ok: jax.Array          # () bool
    wp_before: jax.Array   # () i32
    wp_after: jax.Array    # () i32
    host_delta: jax.Array  # () i32
    dummy_delta: jax.Array  # () i32
    erase_delta: jax.Array  # () i32
    elems: jax.Array       # (n_slots,) i32  zone slot row *after* the op
    cols: jax.Array        # (parallelism,) i32 zone column -> LUN
    opens: jax.Array       # () bool  an ALLOC or WRITE on an EMPTY zone:
                           #   the op ran the allocator


class DynConfig(NamedTuple):
    """Traced (per-call / per-batch-lane) overrides of the value-only
    :class:`EngineConfig` fields.

    Every field is a rank-0 array (or, under ``run_programs``, a
    ``(n_programs,)`` vector -- one value per lane).  Where the leaves
    live: :func:`make_dyn` returns 0-d *numpy* arrays on the host, and
    :func:`stack_dyn` stacks lanes there and moves the whole batch to
    the device in one transfer, so a fleet of hundreds of lanes costs
    one host-to-device copy instead of a device scalar per field and
    lane (a single-lane dyn is transferred by ``jit`` at the call):

    * ``zone_pages``  -- () i32, effective zone capacity in *pages*.
      Must be ``<= cfg.zone_pages``; a smaller value emulates a
      shorter-zone geometry (fewer segments) on the padded static
      shapes: writes seal at the effective capacity and FINISH frees the
      never-touched tail elements, so metrics match a device built with
      the smaller geometry outright (tested).  Exact for every element kind
      whose per-element page capacity is segment-count-independent
      (BLOCK / VCHUNK / HCHUNK / SUPERBLOCK); FIXED elements *are* the
      whole static zone, so FIXED lanes must keep the full capacity.
    * ``max_active``  -- () i32, open/active-zone limit
      (``<= cfg.max_active``).
    * ``n_zones``     -- () i32, addressable zones (``<= cfg.n_zones``);
      op rows are clipped into ``[0, n_zones)``.
    * ``wear_aware``  -- () bool, allocator policy: lowest-(wear, col)
      selection when true, first-fit by column when false.

    The spec axis (all () i32, defaulting to the primary member's
    bundle -- a plain config's own spec; select another union member
    with ``make_dyn(cfg, spec=...)``):

    * ``n_elements`` / ``per_group`` -- the lane's element count and
      group width.  A lane's element ``(g, c)`` lives at union id
      ``g * cfg.per_group + c``; columns ``>= per_group`` and groups
      ``>= n_elements // per_group`` of the padded grid are never
      allocated (they are selection-masked, not state-marked, because
      every lane of a batch shares one initial state).
    * ``take`` / ``zone_groups`` / ``slot_stride`` -- the lane's zone
      composition: ``zone_groups`` winning groups each contribute
      ``take`` elements, element rank ``r`` of window position ``p``
      mapping to zone slot ``r * slot_stride + p``.
    * ``pages_per_element`` -- the FINISH padding capacity per element;
      also derives the lane's per-(segment, column) slot map and its
      ``blocks_per_element = pages_per_element // cfg.pages_per_block``
      wear increment.

    All six are *values* on the padded static shapes, which is what
    lets one ``run_programs`` dispatch mix element specs per lane --
    element-exact vs a device built with the member spec outright
    (tested in ``tests/test_union_spec.py``).

    The allocation-policy axis (the paper's SilentZNS proposal):

    * ``alloc_policy`` -- () i32, :data:`POLICY_TRADITIONAL` (default)
      or :data:`POLICY_SILENT`.  Traditional commits the zone's whole
      element grid at ALLOC time (the legacy round-robin window +
      cheapest-groups fallback).  Silent sizes the claim to the op at
      hand: ALLOC claims ``ceil(n_pages / pages_per_rank)`` element
      ranks (at least one per group -- the parallelism floor stays
      ``zone_groups`` distinct groups) from the *cheapest* groups under
      the wear bound, and a WRITE that outruns the committed ranks
      claims more on the fly before it lands.  Traditional lanes are
      bit-identical to the pre-policy allocator (property-fuzzed in
      ``tests/test_silentzns_property.py``).
    * ``wear_bound`` -- () i32, silent-policy wear-leveling bound: an
      element is claimable only while its wear is within ``wear_bound``
      erases of the least-worn free element.  Defaults to unbounded;
      ignored by traditional lanes.
    """

    zone_pages: jax.Array
    max_active: jax.Array
    n_zones: jax.Array
    wear_aware: jax.Array
    n_elements: jax.Array
    per_group: jax.Array
    take: jax.Array
    zone_groups: jax.Array
    slot_stride: jax.Array
    pages_per_element: jax.Array
    alloc_policy: jax.Array
    wear_bound: jax.Array


def make_dyn(cfg: EngineConfig, *, zone_pages: Optional[int] = None,
             max_active: Optional[int] = None, n_zones: Optional[int] = None,
             wear_aware: Optional[bool] = None,
             spec: Optional[ElementSpec] = None,
             alloc_policy=None,
             wear_bound: Optional[int] = None) -> DynConfig:
    """A :class:`DynConfig` defaulting every field to ``cfg``'s value.

    ``spec`` selects a member of ``cfg.members`` (a union config's spec
    set) and fills the spec-derived fields with that member's
    :class:`SpecValues`; without it the lane runs the *primary*
    (first) member -- for a plain single-spec config that is the
    config's own spec, and for a union config it keeps dyn-less runs
    meaningful instead of mixing cross-member maxima into a spec no
    device has.

    ``alloc_policy`` is ``"traditional"`` / ``"silent"`` (or the
    :data:`POLICY_TRADITIONAL` / :data:`POLICY_SILENT` ints);
    ``wear_bound`` is the silent policy's wear-leveling bound in erases
    (``None`` = unbounded).  See :class:`DynConfig`.

    Overrides are validated eagerly: ``zone_pages`` / ``n_zones`` /
    ``max_active`` beyond the padded static config would index past the
    padded tables (silently wrong metrics), so out-of-range values
    raise ``ValueError`` here instead.  Shrinking ``zone_pages`` on a
    FIXED-kind lane is likewise rejected: FIXED elements *are* the
    whole static zone, so there is no smaller element set for the
    override to claim (see :class:`DynConfig`).  ``alloc_policy`` /
    ``wear_bound`` get the same treatment: an unknown policy or a
    negative bound would otherwise flow into the jitted selection as a
    silently-traditional lane or an always-empty claimable set.

    The leaves are 0-d numpy arrays (``np.int32``; ``np.bool_`` for
    ``wear_aware``): a lane's dyn stays on the host until
    :func:`stack_dyn` (or ``jit``, for a single lane) transfers it.
    """
    if spec is not None:
        sv = cfg.member_values(spec)
        kind = spec.kind
    elif cfg.members:
        spec0, sv = cfg.members[0]       # primary member
        kind = spec0.kind
    else:                                # hand-built config: own statics
        sv = SpecValues(cfg.n_elements, cfg.per_group, cfg.take,
                        cfg.zone_groups, cfg.slot_stride,
                        cfg.pages_per_element)
        kind = cfg.kind
    if zone_pages is not None:
        if not 0 < zone_pages <= cfg.zone_pages:
            raise ValueError(
                f"zone_pages override {zone_pages} out of range "
                f"(static config holds {cfg.zone_pages} pages)")
        if kind is ElementKind.FIXED and zone_pages < cfg.zone_pages:
            raise ValueError(
                "FIXED elements span the whole static zone; a "
                f"zone_pages override ({zone_pages} < {cfg.zone_pages}) "
                "cannot shrink a FIXED lane")
    if n_zones is not None and not 0 < n_zones <= cfg.n_zones:
        raise ValueError(
            f"n_zones override {n_zones} out of range "
            f"(static config holds {cfg.n_zones} zones)")
    if max_active is not None and not 0 < max_active <= cfg.max_active:
        raise ValueError(
            f"max_active override {max_active} out of range "
            f"(static config allows {cfg.max_active} active zones)")
    if alloc_policy is None:
        policy = POLICY_TRADITIONAL
    elif isinstance(alloc_policy, str):
        if alloc_policy not in _POLICY_NAMES:
            raise ValueError(
                f"alloc_policy override {alloc_policy!r} unknown "
                f"(expected one of {sorted(_POLICY_NAMES)} or the "
                f"POLICY_* ints)")
        policy = _POLICY_NAMES[alloc_policy]
    else:
        policy = int(alloc_policy)
        if policy not in (POLICY_TRADITIONAL, POLICY_SILENT):
            raise ValueError(
                f"alloc_policy override {alloc_policy!r} unknown "
                f"(expected one of {sorted(_POLICY_NAMES)} or the "
                f"POLICY_* ints)")
    if policy == POLICY_SILENT and kind is ElementKind.FIXED:
        raise ValueError(
            "alloc_policy 'silent' needs a block collection to vary; "
            "FIXED elements are the whole static zone")
    if wear_bound is not None and not 0 <= wear_bound <= _BIG:
        raise ValueError(
            f"wear_bound override {wear_bound} out of range "
            f"(must be in [0, {_BIG}])")
    i32 = np.int32
    return DynConfig(
        zone_pages=np.asarray(
            cfg.zone_pages if zone_pages is None else zone_pages, i32),
        max_active=np.asarray(
            cfg.max_active if max_active is None else max_active, i32),
        n_zones=np.asarray(
            cfg.n_zones if n_zones is None else n_zones, i32),
        wear_aware=np.asarray(
            cfg.wear_aware if wear_aware is None else wear_aware,
            np.bool_),
        n_elements=np.asarray(sv.n_elements, i32),
        per_group=np.asarray(sv.per_group, i32),
        take=np.asarray(sv.take, i32),
        zone_groups=np.asarray(sv.zone_groups, i32),
        slot_stride=np.asarray(sv.slot_stride, i32),
        pages_per_element=np.asarray(sv.pages_per_element, i32),
        alloc_policy=np.asarray(policy, i32),
        wear_bound=np.asarray(
            _BIG if wear_bound is None else wear_bound, i32),
    )


def dyn_values(cfg: EngineConfig, dyn: Optional[DynConfig] = None,
               lane: Optional[int] = None) -> dict:
    """Host-side snapshot of the *effective* value-only configuration.

    Returns the :class:`DynConfig` fields as plain Python ints/bools
    (``cfg``'s own values when ``dyn`` is ``None``); ``lane`` selects
    one row of a stacked (:func:`stack_dyn`) DynConfig.  This is the
    bridge the static checkers in :mod:`repro.check` use to read a
    dispatch's per-lane geometry without touching traced values.
    """
    if dyn is None:
        dyn = make_dyn(cfg)
    out = {}
    for name, leaf in zip(DynConfig._fields, dyn):
        v = np.asarray(leaf)
        if lane is not None and v.ndim > 0:
            v = v[lane]
        if v.ndim != 0:
            raise ValueError(
                f"dyn field {name!r} has shape {v.shape}; pass lane= "
                f"to select one row of a stacked DynConfig")
        out[name] = bool(v) if v.dtype == np.bool_ else int(v)
    return out


_DYN_DTYPES = DynConfig(*(np.bool_ if f == "wear_aware" else np.int32
                          for f in DynConfig._fields))


def stack_dyn(dyns: Sequence[DynConfig]) -> DynConfig:
    """Stack per-lane :class:`DynConfig`\\ s along a leading batch axis
    (the shape ``run_programs`` consumes for a heterogeneous batch).

    Each field is stacked with numpy on the host, in its
    :class:`DynConfig` dtype, and the stacked tuple then reaches the
    device in ONE ``jax.device_put``: stacking device scalars instead
    costs a transfer per lane and field plus a device op per field,
    seconds for a few hundred lanes against milliseconds here.  Leaves
    may be numpy arrays (:func:`make_dyn`), ``jax.Array``\\ s or Python
    scalars (``dyn._replace(...)``); the result's are ``jax.Array``\\ s.
    """
    dyns = list(dyns)
    if not dyns:
        raise ValueError("stack_dyn needs at least one DynConfig "
                         "(an empty fleet batch has no lanes to stack)")
    host = DynConfig(*(np.stack([np.asarray(d[i], dt) for d in dyns])
                       for i, dt in enumerate(_DYN_DTYPES)))
    return jax.device_put(host)


def _slot_stride(spec: ElementSpec, parallelism: int) -> int:
    if spec.kind is ElementKind.BLOCK:
        return parallelism
    if spec.kind is ElementKind.VCHUNK:
        return parallelism // spec.chunk
    if spec.kind is ElementKind.SUPERBLOCK:
        return 1
    if spec.kind is ElementKind.HCHUNK:
        return parallelism
    if spec.kind is ElementKind.FIXED:
        return 1
    raise ValueError(spec.kind)


def make_config(flash: FlashGeometry, zone_geom: ZoneGeometry,
                spec: ElementSpec, *, max_active: int = 14,
                wear_aware: Optional[bool] = None
                ) -> Tuple[EngineConfig, ElementLayout]:
    layout = build_layout(flash, spec, zone_geom)
    elems = elements_per_zone(layout, zone_geom)
    zgroups = groups_per_zone(layout, zone_geom)
    values = SpecValues(
        n_elements=layout.n_elements,
        per_group=layout.n_elements // layout.n_groups,
        take=elems // zgroups,
        zone_groups=zgroups,
        slot_stride=_slot_stride(spec, zone_geom.parallelism),
        pages_per_element=layout.pages_per_element,
    )
    cfg = EngineConfig(
        kind=spec.kind,
        chunk=spec.chunk,
        wear_aware=(spec.kind is not ElementKind.FIXED
                    if wear_aware is None else wear_aware),
        n_elements=values.n_elements,
        n_groups=layout.n_groups,
        per_group=values.per_group,
        luns_per_group=layout.luns_per_group,
        take=values.take,
        zone_groups=values.zone_groups,
        slot_stride=values.slot_stride,
        n_slots=zns.n_slots(spec, zone_geom.parallelism,
                            zone_geom.n_segments),
        parallelism=zone_geom.parallelism,
        n_segments=zone_geom.n_segments,
        pages_per_block=flash.pages_per_block,
        zone_pages=zone_geom.zone_pages(flash),
        pages_per_element=values.pages_per_element,
        blocks_per_element=layout.blocks_per_element,
        n_zones=flash.n_blocks // zone_geom.blocks_per_zone,
        max_active=max_active,
        n_channels=flash.n_channels,
        members=((spec, values),),
    )
    return cfg, layout


def make_union_config(flash: FlashGeometry, zone_geom: ZoneGeometry,
                      specs: Sequence[ElementSpec], *, max_active: int = 14,
                      wear_aware: Optional[bool] = None
                      ) -> Tuple[EngineConfig, dict]:
    """One :class:`EngineConfig` hosting *any* of ``specs`` per lane.

    Static shapes are padded to the max geometry across the spec set
    (``n_groups`` x ``per_group`` element grid, ``n_slots`` / ``take``
    / ``zone_groups`` maxima); the per-spec :class:`SpecValues` land in
    ``cfg.members`` and are selected per lane with
    ``make_dyn(cfg, spec=...)``.  A member's element ``(g, c)`` lives
    at union id ``g * per_group_max + c``, so for specs sharing one
    group width (BLOCK / VCHUNK / SUPERBLOCK all have
    ``per_group = blocks_per_lun``) member ids are a dense prefix of
    the union grid.  FIXED is rejected: its element *is* the static
    zone, which leaves no spec axis to vary.

    Returns ``(cfg, layouts)`` with one :class:`ElementLayout` per
    member (host-side wear/block bookkeeping).
    """
    specs = tuple(specs)
    if not specs:
        raise ValueError("make_union_config needs at least one spec")
    if len(set(specs)) != len(specs):
        raise ValueError(f"duplicate specs in union: "
                         f"{[s.name for s in specs]}")
    if any(s.kind is ElementKind.FIXED for s in specs):
        raise ValueError("FIXED elements span the whole static zone "
                         "and cannot join a per-lane spec union")
    built = [make_config(flash, zone_geom, s, max_active=max_active,
                         wear_aware=wear_aware) for s in specs]
    cfgs = [c for c, _ in built]
    layouts = {s: lay for s, (_, lay) in zip(specs, built)}
    n_groups = max(c.n_groups for c in cfgs)
    per_group = max(c.per_group for c in cfgs)
    cfg = dataclasses.replace(
        cfgs[0],
        # the padded element grid must stay rectangular for the
        # (n_groups, per_group) allocator reshape, so the static
        # element count is the full grid, not the largest member's
        n_elements=n_groups * per_group,
        n_groups=n_groups,
        per_group=per_group,
        luns_per_group=max(c.luns_per_group for c in cfgs),
        take=max(c.take for c in cfgs),
        zone_groups=max(c.zone_groups for c in cfgs),
        slot_stride=max(c.slot_stride for c in cfgs),
        n_slots=max(c.n_slots for c in cfgs),
        pages_per_element=max(c.pages_per_element for c in cfgs),
        blocks_per_element=max(c.blocks_per_element for c in cfgs),
        members=tuple((s, c.member_values(s))
                      for s, c in zip(specs, cfgs)),
    )
    return cfg, layouts


def init_state(cfg: EngineConfig) -> DeviceState:
    n = cfg.n_elements + 1  # + scratch slot
    i32 = jnp.int32
    return DeviceState(
        elem_wear=jnp.zeros(n, i32),
        elem_avail=jnp.full(n, AVAIL_FREE, i32),
        elem_pages=jnp.zeros(n, i32),
        elem_zone=jnp.full(n, -1, i32),
        zone_state=jnp.full(cfg.n_zones, ZONE_EMPTY, i32),
        zone_wp=jnp.zeros(cfg.n_zones, i32),
        zone_host_wp=jnp.zeros(cfg.n_zones, i32),
        zone_elems=jnp.full((cfg.n_zones, cfg.n_slots), -1, i32),
        zone_cols=jnp.zeros((cfg.n_zones, cfg.parallelism), i32),
        rr_next=jnp.zeros((), i32),
        n_active=jnp.zeros((), i32),
        host_pages=jnp.zeros((), i32),
        dummy_pages=jnp.zeros((), i32),
        block_erases=jnp.zeros((), i32),
        alloc_calls=jnp.zeros((), i32),
    )


# ----------------------------------------------------------------------- #
# pure selection helpers (bit-exact with allocator.py / device_legacy.py)
# ----------------------------------------------------------------------- #
def _rr_mask(cfg: EngineConfig, dyn: DynConfig, start: jax.Array
             ) -> jax.Array:
    """Round-robin eligibility window: ``dyn.zone_groups`` consecutive
    groups (mod the lane's *effective* group count) starting at
    ``start``.  Window positions past ``dyn.zone_groups`` scatter out
    of bounds and are dropped, so a union lane with fewer groups than
    the padded static ``cfg.zone_groups`` gets exactly its own
    window."""
    ng = dyn.n_elements // dyn.per_group      # effective group count
    pos = jnp.arange(cfg.zone_groups, dtype=jnp.int32)
    idx = jnp.where(pos < dyn.zone_groups, (start + pos) % ng,
                    cfg.n_groups)
    return jnp.zeros(cfg.n_groups, bool).at[idx].set(True)


def _in_lane_groups(dense):
    """Run the decorated per-lane ``fn(cfg, *args)`` as it is for one
    lane, and ``dense`` (same signature, same bits) in its place when a
    lane group batches it.  ``dense`` uses no sort, gather or scatter:
    batched, those run as kernels whose cost grows with every lane
    (on one TPU v5e a step of 32 lanes spent 55% of its time in the
    sorts of ``top_k`` and 20% in its gathers), while min-reductions
    and selects over a row batch into dense kernels."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(cfg, *args):
            args = jax.tree_util.tree_map(jnp.asarray, args)

            @jax.custom_batching.custom_vmap
            def one(*args):
                return fn(cfg, *args)

            @one.def_vmap
            def _(axis_size, in_batched, *args):
                axes = jax.tree_util.tree_map(lambda b: 0 if b else None,
                                              tuple(in_batched))
                out = jax.vmap(functools.partial(dense, cfg), in_axes=axes,
                               axis_size=axis_size)(*args)
                return out, jax.tree_util.tree_map(lambda _: True, out)

            return one(*args)
        return call
    return wrap


def _smallest_distinct(key: jax.Array, k: int) -> jax.Array:
    """The ``k`` smallest entries of each row of ``key`` (distinct,
    non-negative int32 within a row), ascending, by ``k``
    min-reductions: each the least entry past the one before."""
    big = jnp.iinfo(jnp.int32).max

    def nxt(last, _):
        last = jnp.min(jnp.where(key > last[..., None], key, big), axis=-1)
        return last, last

    _, out = jax.lax.scan(nxt, jnp.full(key.shape[:-1], -1, key.dtype),
                          None, length=k)
    return jnp.moveaxis(out, 0, -1)


def _take_lowest_dense(cfg: EngineConfig, dyn: DynConfig, w2, a2, eligible,
                       by_wear, take_eff):
    """:func:`_take_lowest` with no sort or gather.  Non-free entries
    get the distinct keys ``_BIG + col``, which order them as ``top_k``
    orders its ties (lower column first), so the ``take`` smallest
    distinct keys give ``top_k``'s selection; the column, and whether
    the entry is free, come back out of the key."""
    pg, k = cfg.per_group, cfg.take
    free = (a2 == AVAIL_FREE) | (a2 == AVAIL_INVALID)
    col = jnp.arange(pg, dtype=jnp.int32)[None, :]
    free = free & eligible[:, None] & (col < dyn.per_group)
    key = jnp.where(free, jnp.where(by_wear, w2 * pg + col, col),
                    _BIG + col)
    v = _smallest_distinct(key, k)
    real = v < _BIG
    cols = jnp.where(real, jnp.where(by_wear, v % pg, v), v - _BIG)
    rank = jnp.arange(k, dtype=jnp.int32)[None, :]
    got_all = jnp.any(real & (rank == take_eff - 1), axis=1)
    feasible = jnp.all(got_all | ~eligible)
    wsel = jnp.sum(jnp.where(col[:, None, :] == cols[:, :, None],
                             w2[:, None, :], 0), axis=2)
    sel_key = jnp.where(real & (rank < take_eff), wsel * pg + cols, _BIG)
    # the stable argsort of sel_key, as each entry's destination rank
    a, b = sel_key[:, :, None], sel_key[:, None, :]
    before = (b < a) | ((b == a) & (rank[None, :, :] < rank[:, :, None]))
    dest = jnp.sum(before, axis=2)
    cols = jnp.sum(jnp.where(dest[:, None, :] == rank[:, :, None],
                             cols[:, None, :], 0), axis=2)
    return cols.astype(jnp.int32), feasible


@_in_lane_groups(_take_lowest_dense)
def _take_lowest(cfg: EngineConfig, dyn: DynConfig, w2, a2, eligible,
                 by_wear, take_eff):
    """Per-eligible-group ``take`` lowest-(wear, col) available elements.

    One ``top_k`` over the unique composite key ``wear * per_group + col``
    reproduces the legacy stable argsort selection *and* its arrange
    order (within a group, selected elements ranked by wear then column)
    without full sorts -- the scan's hot path.  ``by_wear`` may be a
    traced () bool (the :class:`DynConfig` allocator axis); false is the
    wear-oblivious first-fit (selection key = column alone).
    ``take_eff`` (traced, ``<= dyn.take``) is how many of the selected
    elements the zone will actually claim (fewer under an effective-
    capacity override): feasibility only requires that many.  Columns
    past ``dyn.per_group`` are union-grid padding, never free.

    Returns (cols (n_groups, take) ordered ascending by (wear, col),
    feasible).  Valid only where ``eligible``; overflow-safe while wear
    stays below ``2**30 / per_group`` (far beyond any simulated churn).
    """
    free = (a2 == AVAIL_FREE) | (a2 == AVAIL_INVALID)
    col = jnp.arange(cfg.per_group, dtype=jnp.int32)[None, :]
    free = free & eligible[:, None] & (col < dyn.per_group)
    composite = w2 * cfg.per_group + col
    key = jnp.where(free, jnp.where(by_wear, composite, col), _BIG)
    negv, cols = jax.lax.top_k(-key, cfg.take)
    # the take_eff-th smallest key must be a real element
    kth = jnp.take(negv, take_eff - 1, axis=1)
    got_all = (-kth) < _BIG
    feasible = jnp.all(got_all | ~eligible)
    cols = cols.astype(jnp.int32)
    # whatever key selected the elements, the legacy ``_arrange`` ranks
    # the claimed ones by (wear, col) when assigning zone slots.  On the
    # wear-aware path the top_k output is already in that order, so the
    # reorder is an identity there (and lets ``by_wear`` stay traced).
    # Non-free filler (top_k rows with fewer than ``take`` free
    # elements) must keep sorting last, or an in-use element could be
    # reordered into the claimed take_eff prefix and stolen from its
    # zone.  So must the selections past take_eff: first-fit claims the
    # take_eff lowest *columns*, which a (wear, col) reorder of all
    # ``take`` would swap for less-worn higher columns under a capacity
    # override.
    rank = jnp.arange(cfg.take, dtype=jnp.int32)[None, :]
    sel_free = jnp.take_along_axis(free, cols, axis=1) & (rank < take_eff)
    sel_key = jnp.where(
        sel_free,
        jnp.take_along_axis(w2, cols, axis=1) * cfg.per_group + cols,
        _BIG)
    order = jnp.argsort(sel_key, axis=1, stable=True)
    cols = jnp.take_along_axis(cols, order, axis=1)
    return cols, feasible


def _smallest_wear_dense(cfg: EngineConfig, w2, ok):
    """:func:`_smallest_wear` with no sort: the wear of an entry is
    ``key // per_group`` of its distinct key ``wear * per_group + col``
    (``_BIG + col`` where not ``ok``, read back as inf)."""
    col = jnp.arange(cfg.per_group, dtype=jnp.int32)[None, :]
    v = _smallest_distinct(
        jnp.where(ok, w2 * cfg.per_group + col, _BIG + col), cfg.take)
    return jnp.where(v < _BIG, (v // cfg.per_group).astype(jnp.float32),
                     jnp.inf)


@_in_lane_groups(_smallest_wear_dense)
def _smallest_wear(cfg: EngineConfig, w2, ok) -> jax.Array:
    """The ``take`` smallest wears of each group row among ``ok``
    entries, ascending, as float32 (inf past the ``ok`` ones)."""
    keyed = jnp.where(ok, w2.astype(jnp.float32), jnp.inf)
    return -jax.lax.top_k(-keyed, cfg.take)[0]


def _cheapest_groups(cfg: EngineConfig, dyn: DynConfig, w2, a2, take_eff
                     ) -> jax.Array:
    ng = dyn.n_elements // dyn.per_group
    grow = jnp.arange(cfg.n_groups, dtype=jnp.int32)[:, None]
    col = jnp.arange(cfg.per_group, dtype=jnp.int32)[None, :]
    ok = (a2 == AVAIL_FREE) | (a2 == AVAIL_INVALID)
    ok = ok & (grow < ng) & (col < dyn.per_group)  # union-grid padding
    part = _smallest_wear(cfg, w2, ok)
    rank = jnp.arange(cfg.take, dtype=jnp.int32)[None, :]
    cost = jnp.where(rank < take_eff, part, 0.0).sum(axis=1)
    order = jnp.argsort(cost, stable=True)[: cfg.zone_groups]
    # cheapest dyn.zone_groups groups only (padded window tail unused)
    picked = jnp.arange(cfg.zone_groups, dtype=jnp.int32) < dyn.zone_groups
    return jnp.zeros(cfg.n_groups, bool).at[order].set(picked)


def _wear_bounded_avail(cfg: EngineConfig, dyn: DynConfig, w2, a2
                        ) -> jax.Array:
    """The silent policy's wear-leveling bound as an availability mask:
    elements worn more than ``dyn.wear_bound`` erases past the
    least-worn free element are presented busy, so neither the group
    selection nor the per-group claim can pick them.  Subtraction (not
    ``min_wear + bound``) keeps the unbounded default (``_BIG``) free of
    i32 overflow."""
    ng = dyn.n_elements // dyn.per_group
    grow = jnp.arange(cfg.n_groups, dtype=jnp.int32)[:, None]
    col = jnp.arange(cfg.per_group, dtype=jnp.int32)[None, :]
    free = (a2 == AVAIL_FREE) | (a2 == AVAIL_INVALID)
    free = free & (grow < ng) & (col < dyn.per_group)
    min_wear = jnp.min(jnp.where(free, w2, _BIG))
    in_bound = (w2 - min_wear) <= dyn.wear_bound
    return jnp.where(in_bound, a2, AVAIL_VALID)


def _where_state(pred, new: DeviceState, old: DeviceState) -> DeviceState:
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(pred, a, b), new, old)


def _lane_cond(pred, true_fn, false_fn, *operands):
    """``lax.cond(pred, true_fn, false_fn, *operands)`` for one lane,
    which a lane group (``vmap``) runs at group level.

    Batched, ``lax.cond`` runs both branches for every lane and
    selects.  Here the group runs ``false_fn`` alone when no lane's
    ``pred`` holds, and both with that per-lane select otherwise: the
    same bits, and ``true_fn`` only in the steps some lane of the group
    takes it.  The branches take ``operands`` alone (they close over no
    traced value)."""
    @jax.custom_batching.custom_vmap
    def cond(pred, *ops):
        return jax.lax.cond(pred, true_fn, false_fn, *ops)

    @cond.def_vmap
    def _(axis_size, in_batched, pred, *ops):
        axes = jax.tree_util.tree_map(lambda b: 0 if b else None,
                                      tuple(in_batched[1:]))
        vt = jax.vmap(true_fn, in_axes=axes, axis_size=axis_size)
        vf = jax.vmap(false_fn, in_axes=axes, axis_size=axis_size)
        if not in_batched[0]:
            pred = jnp.broadcast_to(pred, (axis_size,))

        out_f = vf(*ops)
        out_t = jax.lax.cond(jnp.any(pred), vt, lambda *_: out_f, *ops)
        out = jax.tree_util.tree_map(
            lambda a, b: jnp.where(
                pred.reshape(pred.shape + (1,) * (a.ndim - 1)), a, b),
            out_t, out_f)
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return cond(pred, *operands)


# ----------------------------------------------------------------------- #
# transitions
# ----------------------------------------------------------------------- #
def _alloc(cfg: EngineConfig, dyn: DynConfig, state: DeviceState,
           zone: jax.Array, hint: jax.Array
           ) -> Tuple[DeviceState, jax.Array]:
    """ALLOC a zone's elements (legacy ``_allocate_zone``).  Caller guards
    on the zone being EMPTY; this applies the selection + deferred erase.

    ``hint`` is the triggering op's ``n_pages`` (0 for a bare ALLOC with
    no size).  Traditional lanes ignore it; a silent lane commits only
    ``ceil(hint / pages_per_rank)`` element ranks (the whole grid when
    the hint is 0), one element per winning group per rank, from the
    cheapest wear-bounded groups -- :func:`_grow_silent` claims the rest
    on demand when later writes outrun the commitment."""
    n = cfg.n_elements
    limit_ok = state.n_active < dyn.max_active

    if cfg.kind is ElementKind.FIXED:
        wear = state.elem_wear[:n]
        avail = state.elem_avail[:n]
        free = (avail == AVAIL_FREE) | (avail == AVAIL_INVALID)
        key = jnp.where(
            free,
            jnp.where(dyn.wear_aware, wear,
                      jnp.arange(n, dtype=jnp.int32)),
            _BIG)
        e = jnp.argmin(key).astype(jnp.int32)
        feasible = free.any()
        band = e % cfg.n_groups
        cols_row = (band * cfg.parallelism
                    + jnp.arange(cfg.parallelism, dtype=jnp.int32))
        elems_row = jnp.full((cfg.n_slots,), e, jnp.int32)
        rr_next = state.rr_next
    else:
        pg = cfg.per_group
        w2 = state.elem_wear[:n].reshape(cfg.n_groups, pg)
        a2 = state.elem_avail[:n].reshape(cfg.n_groups, pg)
        # effective-capacity override (DynConfig): a shrunk lane claims
        # only the slots its capacity can reach, so its element set --
        # and therefore wear / deferred-erase accounting -- is exactly
        # the one a device built with the smaller geometry would pick
        # (slot layouts are uniform across groups for whole-segment
        # capacities, so the per-group claim count is a single scalar)
        n_slots_eff = dyn.zone_pages // dyn.pages_per_element
        take_eff = jnp.clip(
            n_slots_eff // jnp.maximum(dyn.slot_stride, 1),
            1, dyn.take).astype(jnp.int32)

        # the branches take their traced inputs as operands (_lane_cond)
        def traditional(dyn, w2, a2, rr, take_eff, hint):
            elig1 = _rr_mask(cfg, dyn, rr)
            cols1, f1 = _take_lowest(cfg, dyn, w2, a2, elig1,
                                     dyn.wear_aware, take_eff)

            # round-robin window exhausted: cheapest feasible groups
            # instead (the legacy fallback always uses the wear-aware
            # selection); lazily computed -- the common path pays for
            # one top_k only
            def fallback(dyn, w2, a2, take_eff, cols1, elig1):
                elig2 = _cheapest_groups(cfg, dyn, w2, a2, take_eff)
                cols2, f2 = _take_lowest(cfg, dyn, w2, a2, elig2, True,
                                         take_eff)
                return cols2, f2, elig2

            cols1, f2, elig1 = _lane_cond(
                ~f1, fallback,
                lambda dyn, w2, a2, take_eff, cols1, elig1: (
                    cols1, jnp.asarray(True), elig1),
                dyn, w2, a2, take_eff, cols1, elig1)
            # legacy advances the window even when the allocation then
            # fails
            ng = dyn.n_elements // dyn.per_group
            return (cols1, f1 | f2, elig1, (rr + dyn.zone_groups) % ng,
                    dyn.take)

        def silent(dyn, w2, a2, rr, take_eff, hint):
            # on-the-fly commitment: only the ranks the size hint needs
            # (>= 1, keeping the parallelism floor of one element per
            # winning group), from the cheapest wear-bounded groups;
            # the round-robin window is not consumed
            per_rank = dyn.pages_per_element * dyn.zone_groups
            ranks_hint = -(-hint // jnp.maximum(per_rank, 1))
            take_s = jnp.clip(jnp.where(hint > 0, ranks_hint, take_eff),
                              1, take_eff).astype(jnp.int32)
            a2b = _wear_bounded_avail(cfg, dyn, w2, a2)
            elig_s = _cheapest_groups(cfg, dyn, w2, a2b, take_s)
            cols_s, f_s = _take_lowest(cfg, dyn, w2, a2b, elig_s, True,
                                       take_s)
            return cols_s, f_s, elig_s, rr, take_s

        cols, feasible, elig, rr_next, rank_lim = _lane_cond(
            dyn.alloc_policy == POLICY_SILENT, silent, traditional,
            dyn, w2, a2, state.rr_next, take_eff, hint)
        # every eligible group contributes exactly ``take`` elements, so
        # the winning groups are the eligible window itself (ascending)
        win = jnp.nonzero(elig, size=cfg.zone_groups,
                          fill_value=0)[0].astype(jnp.int32)
        eids = (win[:, None] * pg + cols[win]).astype(jnp.int32)
        ranks = jnp.arange(cfg.take, dtype=jnp.int32)[None, :]
        cpos = jnp.arange(cfg.zone_groups, dtype=jnp.int32)[:, None]
        # window positions past the lane's zone_groups are union
        # padding: their slots divert to the scratch column and their
        # elements to the scratch element.  The rank mask is an
        # identity for traditional lanes (rank_lim = dyn.take: a slot
        # below n_slots_eff already implies rank < take because
        # zone_groups <= slot_stride for every gridded kind) and is
        # what sizes a silent lane's partial commitment.
        valid = cpos < dyn.zone_groups
        raw_slots = ranks * dyn.slot_stride + cpos
        slots = jnp.where(valid, raw_slots, cfg.n_slots).reshape(-1)
        claimed = (valid & (raw_slots < n_slots_eff)
                   & (ranks < rank_lim)).reshape(-1)
        elems_row = jnp.full(cfg.n_slots + 1, -1, jnp.int32).at[
            slots].set(jnp.where(claimed, eids.reshape(-1),
                                 -1))[: cfg.n_slots]
        # zone column c -> LUN: window position c // luns_per_group
        # owns the group band, c % luns_per_group walks its LUNs
        lpg = cfg.parallelism // dyn.zone_groups
        c = jnp.arange(cfg.parallelism, dtype=jnp.int32)
        cols_row = win[c // lpg] * lpg + c % lpg

    if cfg.kind is ElementKind.FIXED:
        flat = elems_row.reshape(-1)
        claimed_flat = jnp.ones_like(flat, dtype=bool)
    else:
        # unclaimed selections scatter into the scratch slot
        flat = jnp.where(claimed, eids.reshape(-1), n)
        claimed_flat = claimed
    ok = limit_ok & feasible
    # deferred physical erase of invalid elements (paper §5 RESET)
    inv = claimed_flat & (state.elem_avail[flat] == AVAIL_INVALID)
    erase_delta = (inv.sum().astype(jnp.int32)
                   * (dyn.pages_per_element // cfg.pages_per_block))
    new = state._replace(
        elem_wear=state.elem_wear.at[flat].add(inv.astype(jnp.int32)),
        elem_avail=state.elem_avail.at[flat].set(AVAIL_ALLOCATED),
        elem_pages=state.elem_pages.at[flat].set(0),
        elem_zone=state.elem_zone.at[flat].set(zone),
        zone_state=state.zone_state.at[zone].set(ZONE_OPEN),
        zone_wp=state.zone_wp.at[zone].set(0),
        zone_host_wp=state.zone_host_wp.at[zone].set(0),
        zone_elems=state.zone_elems.at[zone].set(elems_row),
        zone_cols=state.zone_cols.at[zone].set(cols_row),
        n_active=state.n_active + 1,
        block_erases=state.block_erases + erase_delta,
        alloc_calls=state.alloc_calls + 1,
    )
    state = _where_state(ok, new, state)
    # rr advance survives an infeasible attempt (but not a limit refusal,
    # where the legacy device raises before touching the window)
    state = state._replace(
        rr_next=jnp.where(limit_ok, rr_next, state.rr_next))
    return state, ok


def _written_per_slot(cfg: EngineConfig, dyn: DynConfig, wp: jax.Array
                      ) -> jax.Array:
    """Pages written per element slot at zone pointer ``wp``, computed
    from the lane's *dynamic* spec values: every (segment, column)
    erase-block cell scatter-adds its page count into the slot

        (segment // seg_span) * slot_stride + column // luns_per_group

    which reproduces :func:`repro.core.zns.element_pages_jnp` for every
    element kind (slot-map property-tested in
    ``tests/test_union_spec.py``) while keeping the spec a value, not a
    shape."""
    blk = zns.pages_per_block_jnp(wp, cfg.parallelism, cfg.n_segments,
                                  cfg.pages_per_block)
    lpg = cfg.parallelism // dyn.zone_groups       # LUN columns / element
    seg_span = dyn.pages_per_element // (lpg * cfg.pages_per_block)
    slot = zns.slot_map_jnp(dyn.slot_stride, lpg, seg_span,
                            cfg.parallelism, cfg.n_segments)
    return jnp.zeros(cfg.n_slots, jnp.int32).at[slot.reshape(-1)].add(
        blk.reshape(-1))


def _grow_silent(cfg: EngineConfig, dyn: DynConfig, state: DeviceState,
                 zone, wp1, pred) -> Tuple[DeviceState, jax.Array]:
    """Silent-policy on-demand commitment: when a write will advance the
    zone pointer past the element ranks claimed so far, claim the
    missing ranks (cheapest wear-bounded elements of the zone's own
    winning groups, keeping the slot grid rectangular) before the write
    lands.  A no-op (ok) for traditional lanes, FULL zones, and writes
    the commitment already covers."""
    if cfg.kind is ElementKind.FIXED:
        return state, jnp.asarray(True)
    n_slots_eff = dyn.zone_pages // dyn.pages_per_element
    take_eff = jnp.clip(
        n_slots_eff // jnp.maximum(dyn.slot_stride, 1),
        1, dyn.take).astype(jnp.int32)
    per_rank = dyn.pages_per_element * dyn.zone_groups
    need = jnp.clip(-(-wp1 // jnp.maximum(per_rank, 1)),
                    1, take_eff).astype(jnp.int32)
    # committed ranks: the claim grid is rectangular (every rank spans
    # all zone_groups window positions), so the row's live-slot count
    # divides exactly
    have = ((state.zone_elems[zone] >= 0).sum()
            // jnp.maximum(dyn.zone_groups, 1)).astype(jnp.int32)
    grow = (pred & (dyn.alloc_policy == POLICY_SILENT)
            & (need > have))

    def grow_fn(s, dyn, zone, need, have, n_slots_eff):
        n = cfg.n_elements
        pg = cfg.per_group
        w2 = s.elem_wear[:n].reshape(cfg.n_groups, pg)
        a2 = s.elem_avail[:n].reshape(cfg.n_groups, pg)
        a2b = _wear_bounded_avail(cfg, dyn, w2, a2)
        # the zone's winning groups, recovered from its column map
        # (ascending, exactly as _alloc laid them out)
        lpg = cfg.parallelism // dyn.zone_groups
        pos = jnp.arange(cfg.zone_groups, dtype=jnp.int32)
        win_g = s.zone_cols[zone][
            jnp.clip(pos * lpg, 0, cfg.parallelism - 1)] // lpg
        gidx = jnp.where(pos < dyn.zone_groups, win_g, cfg.n_groups)
        elig = jnp.zeros(cfg.n_groups, bool).at[gidx].set(True)
        k = need - have
        cols, fg = _take_lowest(cfg, dyn, w2, a2b, elig, True, k)
        win = jnp.nonzero(elig, size=cfg.zone_groups,
                          fill_value=0)[0].astype(jnp.int32)
        eids = (win[:, None] * pg + cols[win]).astype(jnp.int32)
        ranks = jnp.arange(cfg.take, dtype=jnp.int32)[None, :]
        cpos = jnp.arange(cfg.zone_groups, dtype=jnp.int32)[:, None]
        raw_slots = (have + ranks) * dyn.slot_stride + cpos
        claimed = ((cpos < dyn.zone_groups) & (ranks < k)
                   & (raw_slots < n_slots_eff))
        slots = jnp.where(claimed, raw_slots, cfg.n_slots).reshape(-1)
        claimed = claimed.reshape(-1)
        flat = jnp.where(claimed, eids.reshape(-1), n)
        row = jnp.append(s.zone_elems[zone], jnp.int32(-1))
        elems_row = row.at[slots].set(
            jnp.where(claimed, eids.reshape(-1), -1))[: cfg.n_slots]
        # deferred physical erase, exactly as at ALLOC time
        inv = claimed & (s.elem_avail[flat] == AVAIL_INVALID)
        erase_delta = (inv.sum().astype(jnp.int32)
                       * (dyn.pages_per_element // cfg.pages_per_block))
        new = s._replace(
            elem_wear=s.elem_wear.at[flat].add(inv.astype(jnp.int32)),
            elem_avail=s.elem_avail.at[flat].set(AVAIL_ALLOCATED),
            elem_pages=s.elem_pages.at[flat].set(0),
            elem_zone=s.elem_zone.at[flat].set(zone),
            zone_elems=s.zone_elems.at[zone].set(elems_row),
            block_erases=s.block_erases + erase_delta,
            alloc_calls=s.alloc_calls + 1,
        )
        return _where_state(fg, new, s), fg

    return _lane_cond(
        grow, grow_fn, lambda s, *_: (s, jnp.asarray(True)),
        state, dyn, zone, need, have, n_slots_eff)


def _write(cfg: EngineConfig, dyn: DynConfig, state: DeviceState,
           zone, n_pages, host, ok) -> Tuple[DeviceState, jax.Array]:
    """WRITE's effects on a zone that holds its elements (an EMPTY zone
    was allocated and a silent one grown before, in
    :func:`_apply_op_impl`); ``ok`` is the op's verdict."""
    wp1 = state.zone_wp[zone] + n_pages
    written = _written_per_slot(cfg, dyn, wp1).astype(jnp.int32)
    elems = state.zone_elems[zone]
    valid = elems >= 0
    idx = jnp.where(valid, elems, cfg.n_elements)
    touched = valid & (written > 0)
    seal = wp1 == dyn.zone_pages
    new = state._replace(
        elem_pages=state.elem_pages.at[idx].set(written),
        elem_avail=state.elem_avail.at[
            jnp.where(touched, elems, cfg.n_elements)].set(AVAIL_VALID),
        zone_wp=state.zone_wp.at[zone].set(wp1),
        zone_host_wp=state.zone_host_wp.at[zone].add(
            jnp.where(host, n_pages, 0)),
        zone_state=state.zone_state.at[zone].set(
            jnp.where(seal, ZONE_FULL, ZONE_OPEN)),
        n_active=state.n_active - seal.astype(jnp.int32),
        host_pages=state.host_pages + jnp.where(host, n_pages, 0),
        dummy_pages=state.dummy_pages + jnp.where(host, 0, n_pages),
    )
    return _where_state(ok, new, state), ok


def _finish(cfg: EngineConfig, dyn: DynConfig, state: DeviceState, zone
            ) -> Tuple[DeviceState, jax.Array]:
    zst0 = state.zone_state[zone]
    is_open = zst0 == ZONE_OPEN
    wp = state.zone_wp[zone]
    written = _written_per_slot(cfg, dyn, wp).astype(jnp.int32)
    elems = state.zone_elems[zone]
    valid = elems >= 0
    untouched = valid & (written == 0) & is_open
    touched = valid & (written > 0) & is_open
    cap = dyn.pages_per_element
    pad = jnp.sum(jnp.where(touched, cap - written, 0)).astype(jnp.int32)
    n = cfg.n_elements
    u_idx = jnp.where(untouched, elems, n)
    t_idx = jnp.where(touched, elems, n)
    avail = state.elem_avail.at[u_idx].set(AVAIL_FREE)
    avail = avail.at[t_idx].set(AVAIL_VALID)
    pages = state.elem_pages.at[u_idx].set(0)
    pages = pages.at[t_idx].set(cap)
    new = state._replace(
        elem_avail=avail,
        elem_pages=pages,
        elem_zone=state.elem_zone.at[u_idx].set(-1),
        zone_elems=state.zone_elems.at[zone].set(
            jnp.where(untouched, -1, elems)),
        zone_state=state.zone_state.at[zone].set(ZONE_FULL),
        dummy_pages=state.dummy_pages + pad,
        n_active=state.n_active - is_open.astype(jnp.int32),
    )
    # FULL is a no-op; EMPTY just seals (untouched/touched masks are empty)
    return _where_state(zst0 != ZONE_FULL, new, state), jnp.asarray(True)


def _reset(cfg: EngineConfig, state: DeviceState, zone
           ) -> Tuple[DeviceState, jax.Array]:
    zst0 = state.zone_state[zone]
    elems = state.zone_elems[zone]
    valid = elems >= 0
    idx = jnp.where(valid, elems, cfg.n_elements)
    cur = state.elem_avail[idx]
    nxt = jnp.where(cur == AVAIL_VALID, AVAIL_INVALID,
                    jnp.where(cur == AVAIL_ALLOCATED, AVAIL_FREE, cur))
    new = state._replace(
        elem_avail=state.elem_avail.at[idx].set(nxt),
        elem_zone=state.elem_zone.at[idx].set(-1),
        elem_pages=state.elem_pages.at[idx].set(0),
        zone_state=state.zone_state.at[zone].set(ZONE_EMPTY),
        zone_wp=state.zone_wp.at[zone].set(0),
        zone_host_wp=state.zone_host_wp.at[zone].set(0),
        zone_elems=state.zone_elems.at[zone].set(
            jnp.full(cfg.n_slots, -1, jnp.int32)),
        zone_cols=state.zone_cols.at[zone].set(
            jnp.zeros(cfg.parallelism, jnp.int32)),
        n_active=state.n_active - (zst0 == ZONE_OPEN).astype(jnp.int32),
    )
    return new, jnp.asarray(True)


# ----------------------------------------------------------------------- #
# op dispatch + program executor
# ----------------------------------------------------------------------- #
def _apply_op_impl(cfg: EngineConfig, dyn: DynConfig, state: DeviceState,
                   row: jax.Array) -> Tuple[DeviceState, OpTrace]:
    op = row[0]
    zone = jnp.clip(row[1], 0, dyn.n_zones - 1)
    n_pages = row[2]
    host = (row[3] & F_HOST) == F_HOST

    # an ALLOC, or a WRITE, on an EMPTY zone first claims its elements
    # (row[2] rides along as the silent policy's size hint); then a
    # WRITE a silent zone's committed ranks cannot hold grows the zone.
    # Both run ahead of the per-op switch, so that a lane group (see
    # _lane_cond) pays for them only in the steps some lane needs them.
    zst0 = state.zone_state[zone]
    opens = ((op == OP_ALLOC) | (op == OP_WRITE)) & (zst0 == ZONE_EMPTY)
    s1, aok = _lane_cond(
        opens, lambda s, d, z, h: _alloc(cfg, d, s, z, h),
        lambda s, *_: (s, jnp.asarray(True)), state, dyn, zone, n_pages)
    wp1 = s1.zone_wp[zone] + n_pages
    fits = (zst0 != ZONE_FULL) & aok & (wp1 <= dyn.zone_pages)
    s1, gok = _grow_silent(cfg, dyn, s1, zone, wp1,
                           (op == OP_WRITE) & fits)

    def nop(s):
        return s, jnp.asarray(True)

    state2, ok = jax.lax.switch(
        jnp.clip(op, 0, OP_READ),
        [nop,
         lambda s: (s, aok),   # ALLOC: a mapped zone is a fine no-op
         lambda s: _write(cfg, dyn, s, zone, n_pages, host, fits & gok),
         lambda s: _finish(cfg, dyn, s, zone),
         lambda s: _reset(cfg, s, zone),
         nop],  # OP_READ: reads never change device state
        s1)
    trace = OpTrace(
        op=op, zone=zone, ok=ok,
        wp_before=state.zone_wp[zone],
        wp_after=state2.zone_wp[zone],
        host_delta=state2.host_pages - state.host_pages,
        dummy_delta=state2.dummy_pages - state.dummy_pages,
        erase_delta=state2.block_erases - state.block_erases,
        elems=state2.zone_elems[zone],
        cols=state2.zone_cols[zone],
        opens=opens,
    )
    return state2, trace


@functools.partial(jax.jit, static_argnums=(0,))
def apply_op(cfg: EngineConfig, state: DeviceState, row: jax.Array,
             dyn: Optional[DynConfig] = None
             ) -> Tuple[DeviceState, OpTrace]:
    """One zone command as a pure jitted transition.  ``dyn`` (optional)
    shadows the value-only config fields -- see :class:`DynConfig`."""
    if dyn is None:
        dyn = make_dyn(cfg)
    return _apply_op_impl(cfg, dyn, state, row)


def _scan_program(cfg: EngineConfig, dyn: DynConfig, state: DeviceState,
                  program: jax.Array, obs):
    """The shared scan body of :func:`run_program` / :func:`run_programs`.

    With ``obs`` (a static ``repro.obs.recorder.ObsConfig``) the scan
    carry additionally threads a telemetry accumulator and the return
    grows a third element.  The recorder only *reads* the device state,
    so the ``DeviceState`` / ``OpTrace`` outputs are bit-identical with
    and without it (property-tested in ``tests/test_obs.py``)."""
    if obs is None:
        return jax.lax.scan(
            lambda s, r: _apply_op_impl(cfg, dyn, s, r), state, program)
    # imported lazily: repro.obs depends on repro.core, not vice versa
    from repro.obs import recorder

    n_ops = int(program.shape[0])

    def step(carry, row):
        s, tel = carry
        s2, trace = _apply_op_impl(cfg, dyn, s, row)
        tel2 = recorder.telemetry_update(obs, tel, s, s2, trace, row,
                                         max(n_ops, 1))
        return (s2, tel2), trace

    (state2, tel), trace = jax.lax.scan(
        step, (state, recorder.telemetry_init(obs)), program)
    return state2, trace, tel


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("obs",))
def run_program(cfg: EngineConfig, state: DeviceState, program: jax.Array,
                dyn: Optional[DynConfig] = None, *, obs=None
                ) -> Tuple[DeviceState, OpTrace]:
    """Execute an ``(n_ops, >=4)`` int32 program in a single ``lax.scan``.
    Only the first four row columns are interpreted; extra columns (e.g.
    the fleet layer's tenant tag) ride along untouched.  ``obs`` (a
    static ``repro.obs.recorder.ObsConfig``) opts into in-scan
    telemetry: the return becomes ``(state, trace, telemetry)``."""
    if dyn is None:
        dyn = make_dyn(cfg)
    return _scan_program(cfg, dyn, state, program, obs)


#: lane-group width rule of :func:`run_programs` on the TPU (see there)
LANE_GROUP_MIN_LANES = 64
LANE_GROUP_MAX_WIDTH = 384


def lane_group_width(n_lanes: int, platform: str) -> int:
    """Lanes one :func:`run_programs` step advances together on
    ``platform`` (a JAX platform name): 1 (lanes one after another)
    off the TPU and below ``LANE_GROUP_MIN_LANES`` lanes, else
    ``min(n_lanes, LANE_GROUP_MAX_WIDTH)``."""
    if platform != "tpu" or n_lanes < LANE_GROUP_MIN_LANES:
        return 1
    return min(n_lanes, LANE_GROUP_MAX_WIDTH)


@functools.partial(jax.jit, static_argnums=(1,))
def group_alloc_steps(opens: jax.Array, width: int) -> jax.Array:
    """``(groups,)`` i32: the steps of each lane group of ``width`` in
    which some lane ran the allocator (``opens``: a dispatch's
    ``(n_lanes, n_ops)`` ``OpTrace.opens``)."""
    opens = jnp.pad(opens, ((0, -opens.shape[0] % width), (0, 0)))
    return jnp.sum(jnp.any(opens.reshape(-1, width, opens.shape[1]),
                           axis=1), axis=1, dtype=jnp.int32)


def _lane_dyn(cfg: EngineConfig, dyn: Optional[DynConfig],
              n_lanes: int) -> DynConfig:
    """``dyn`` with ``(n_lanes,)`` leaves (``cfg``'s own when None)."""
    if dyn is not None:
        return dyn
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(jnp.asarray(x), (n_lanes,)),
        make_dyn(cfg))


def _run_lanes(cfg: EngineConfig, state: DeviceState, programs: jax.Array,
               dyn: DynConfig, obs):
    """Lanes one after another: a ``lax.map`` of the per-lane scan."""
    return jax.lax.map(
        lambda pd: _scan_program(cfg, pd[1], state, pd[0], obs),
        (programs, dyn))


def _run_lane_groups(cfg: EngineConfig, state: DeviceState,
                     programs: jax.Array, dyn: DynConfig, obs, width: int):
    """Lanes in groups of ``width``: one scan over the op rows applies
    row ``t`` of every lane of a group in the same step, on lane-major
    state; the groups run one after another.  A lane count ``width``
    does not divide is filled up with NOP lanes (under lane 0's
    ``DynConfig``), whose outputs are dropped.  Each lane gets its own
    copy of the initial state, so the scan's carry is batched from the
    start and the step is batched (and traced) once."""
    n = programs.shape[0]
    fill = -n % width
    if fill:
        programs = jnp.concatenate(
            [programs, jnp.zeros((fill,) + programs.shape[1:],
                                 programs.dtype)])
        dyn = jax.tree_util.tree_map(
            lambda x: jnp.concatenate(
                [x, jnp.broadcast_to(x[:1], (fill,) + x.shape[1:])]), dyn)
    states = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n + fill,) + x.shape), state)
    out = jax.lax.map(
        lambda pds: _scan_program(cfg, pds[1], pds[2], pds[0], obs),
        (programs, dyn, states), batch_size=width)
    if fill:
        out = jax.tree_util.tree_map(lambda x: x[:n], out)
    return out


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("obs",))
def run_programs(cfg: EngineConfig, state: DeviceState, programs: jax.Array,
                 dyn: Optional[DynConfig] = None, *, obs=None
                 ) -> Tuple[DeviceState, OpTrace]:
    """Batch :func:`run_program` over a leading program axis (shared
    initial state) -- a whole parameter sweep in one compiled dispatch.

    ``dyn`` (optional) must hold ``(n_programs,)``-shaped leaves (see
    :func:`stack_dyn`): lane ``k`` runs ``programs[k]`` under
    ``dyn[k]``, which is how a *heterogeneous* fleet (mixed effective
    zone geometries / allocator policies, padded to the largest static
    shape) executes in one dispatch.  ``obs`` opts into per-lane
    telemetry stacks (``(n_programs, n_buckets, ...)`` leaves): the
    return becomes ``(states, traces, telemetry)``.

    How the lanes run depends on the platform and the lane count
    (:func:`lane_group_width`, resolved while tracing from the default
    backend and the static lane count).  On the CPU they run one after
    another, a ``lax.map`` of the single-lane scan: batching makes every
    lane run every branch of the per-op ``switch``, 27x slower there at
    128 lanes.  On the TPU, from 64 lanes, they run in lane
    groups of up to 384 (:func:`_run_lane_groups`), where the allocator
    runs only in the steps some lane of a group allocates
    (:func:`_lane_cond`) and in forms without sorts or gathers
    (:func:`_in_lane_groups`).  The rule is one TPU v5e's: the
    grid's 384 lanes x 832 rows took 6.9 s one lane after another and
    3.9 s in one group (4.1 s in groups of 96, 4.6 s of 32), while a
    group step costs at least ~0.45 ms, so kvbench's 6 lanes x 960
    rows took 0.15 s one after another and 0.43 s in one group.  Below
    64 lanes a group saves a call little against what its larger
    program adds to tracing and loading, once per scan shape."""
    n = programs.shape[0]
    dyn = _lane_dyn(cfg, dyn, n)
    width = lane_group_width(n, jax.default_backend())
    if width == 1:
        return _run_lanes(cfg, state, programs, dyn, obs)
    return _run_lane_groups(cfg, state, programs, dyn, obs, width)


# ----------------------------------------------------------------------- #
# host-facing wrapper
# ----------------------------------------------------------------------- #
def encode_program(ops, width: int = 4) -> np.ndarray:
    """``[(opcode, zone, n_pages, flags[, ...]), ...]`` -> (n_ops, width)
    int32.  ``width > 4`` leaves room for engine-opaque columns (the
    fleet layer stores a tenant tag in column 4); short rows are
    zero-padded."""
    out = np.zeros((len(ops), width), dtype=np.int32)
    for i, row in enumerate(ops):
        out[i, : len(row)] = row
    return out


class ZoneEngine:
    """Pure functional core of one emulated ZNS device.

    Holds the static :class:`EngineConfig` + :class:`ElementLayout` and
    wraps the module-level jitted transitions; state is always passed
    explicitly (the engine itself is stateless and shareable).

    ``spec`` may be a single :class:`ElementSpec` or a *sequence* of
    them: a sequence builds the padded union config
    (:func:`make_union_config`), whose lanes each pick a member spec
    through ``self.dyn(spec=...)`` -- one batched ``run_programs``
    dispatch then mixes element specs per lane.  ``self.spec`` /
    ``self.layout`` refer to the first (primary) member.
    """

    def __init__(self, flash: FlashGeometry, zone_geom: ZoneGeometry,
                 spec, *, max_active: int = 14,
                 wear_aware: Optional[bool] = None):
        self.flash = flash
        self.zone_geom = zone_geom
        if isinstance(spec, ElementSpec):
            self.cfg, self.layout = make_config(
                flash, zone_geom, spec, max_active=max_active,
                wear_aware=wear_aware)
            self.layouts = {spec: self.layout}
        else:
            self.cfg, self.layouts = make_union_config(
                flash, zone_geom, spec, max_active=max_active,
                wear_aware=wear_aware)
            self.layout = self.layouts[tuple(spec)[0]]
            spec = tuple(spec)[0]
        self.spec = spec

    # -- state ---------------------------------------------------------- #
    def init_state(self) -> DeviceState:
        return init_state(self.cfg)

    @property
    def members(self) -> dict:
        """Member spec -> :class:`SpecValues` (one entry for a plain
        engine, one per union member otherwise)."""
        return dict(self.cfg.members)

    def dyn(self, **overrides) -> DynConfig:
        """Per-call :class:`DynConfig` (``zone_pages`` / ``max_active`` /
        ``n_zones`` / ``wear_aware`` / ``spec`` / ``alloc_policy`` /
        ``wear_bound`` keywords; others from ``cfg``)."""
        return make_dyn(self.cfg, **overrides)

    def member_element_ids(self, spec: ElementSpec) -> np.ndarray:
        """Dense element ids of ``spec`` -> their union-grid positions
        (``(g, c) -> g * cfg.per_group + c``); the identity for a plain
        single-spec engine."""
        v = self.cfg.member_values(spec)
        return union_grid_ids(v.n_elements, v.per_group,
                              self.cfg.per_group)

    def apply(self, state: DeviceState, row,
              dyn: Optional[DynConfig] = None
              ) -> Tuple[DeviceState, OpTrace]:
        return apply_op(self.cfg, state,
                        jnp.asarray(row, jnp.int32), dyn)

    def run(self, state: DeviceState, program: np.ndarray,
            dyn: Optional[DynConfig] = None, *, obs=None
            ) -> Tuple[DeviceState, OpTrace]:
        """One scan; ``obs`` (an ``ObsConfig``) adds a telemetry third
        return -- see :func:`run_program`."""
        return run_program(self.cfg, state,
                           jnp.asarray(program, jnp.int32), dyn, obs=obs)

    def run_batch(self, state: DeviceState, programs: np.ndarray,
                  dyn: Optional[DynConfig] = None, *, obs=None
                  ) -> Tuple[DeviceState, OpTrace]:
        """Batched :meth:`run`; ``dyn`` with ``(n_programs,)`` leaves
        (see :func:`stack_dyn`) makes the batch heterogeneous; ``obs``
        adds per-lane telemetry stacks."""
        return run_programs(self.cfg, state,
                            jnp.asarray(programs, jnp.int32), dyn,
                            obs=obs)

    def warmup(self) -> None:
        """Compile every op branch on a scratch state (one switch jit)."""
        s = self.init_state()
        for op in (OP_ALLOC, OP_WRITE, OP_FINISH, OP_RESET):
            s, _ = self.apply(s, (op, 0, 1, F_HOST))
        jax.block_until_ready(s.elem_wear)

    # -- metrics -------------------------------------------------------- #
    def metrics(self, state: DeviceState) -> dict:
        host = int(state.host_pages)
        dummy = int(state.dummy_pages)
        return {
            "host_pages": float(host),
            "dummy_pages": float(dummy),
            "dlwa": (host + dummy) / host if host else 1.0,
            "block_erases": float(int(state.block_erases)),
            "alloc_calls": float(int(state.alloc_calls)),
            "n_active": float(int(state.n_active)),
        }

    def elem_wear(self, state: DeviceState,
                  spec: Optional[ElementSpec] = None) -> np.ndarray:
        """Element wear in ``spec``'s dense id order (default: the
        primary spec; union-grid padding elements are excluded)."""
        ids = self.member_element_ids(spec or self.spec)
        return np.asarray(state.elem_wear, dtype=np.int64)[ids]

    def block_wear(self, state: DeviceState,
                   spec: Optional[ElementSpec] = None) -> np.ndarray:
        spec = spec or self.spec
        layout = self.layouts[spec]
        wear = np.zeros(self.flash.n_blocks, dtype=np.int64)
        wear[layout.blocks.reshape(-1)] = np.repeat(
            self.elem_wear(state, spec), layout.blocks_per_element)
        return wear

    # -- IO stream reconstruction (host-side, post-scan) ---------------- #
    def op_stream(self, op: int, wp_before: int, wp_after: int,
                  dummy_delta: int, elems_after: np.ndarray,
                  cols: np.ndarray):
        """Rebuild the per-page ``(luns, channels)`` stream of one traced
        op, exactly as the legacy device's ``trace=True`` path emits it.
        Returns ``None`` when the op moved no pages."""
        cfg = self.cfg
        cols = np.asarray(cols, dtype=np.int64)
        if op == OP_WRITE and wp_after > wp_before:
            return zns.page_stream(wp_before, wp_after - wp_before,
                                   cfg.parallelism, cfg.pages_per_block,
                                   cols, cfg.n_channels) + ("write",)
        if op == OP_FINISH and dummy_delta > 0:
            written = zns.element_pages(
                wp_before, self.spec, cfg.parallelism, cfg.n_segments,
                cfg.pages_per_block)
            padded = np.nonzero((np.asarray(elems_after) >= 0)
                                & (written > 0)
                                & (written < cfg.pages_per_element))[0]
            return zns.pad_stream(
                wp_before, cfg.zone_pages, self.spec, cfg.parallelism,
                cfg.pages_per_block, cols, padded.astype(np.int64),
                cfg.n_channels) + ("write",)
        return None
