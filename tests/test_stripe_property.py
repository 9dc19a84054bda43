"""Property tests for the shared RAID stripe math.

``parity_device_of`` / ``data_device_of`` / ``locate_page``
(:mod:`repro.array.raid`) are the single source of truth for both the
real ``ZNSArray`` and the fleet layer's program-space striper
(:func:`repro.fleet.tenants.stripe_program`).  These tests pin the
algebra for arbitrary (n_devices, chunk, page):

* address round-trip: ``locate_page`` decomposes a logical page into
  (stripe, slot, page-in-chunk, device) and the decomposition
  reconstructs the page exactly;
* parity rotation: a stripe's parity device cycles RAID-5 style through
  all members, and no data slot ever lands on it;
* striper agreement: the per-device WRITE page counts emitted by
  ``stripe_program`` match what ``locate_page`` predicts page by page.
"""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.array.raid import data_device_of, locate_page, parity_device_of
from repro.core import engine as E
from repro.fleet import TENANT_COL, stripe_program, tag_tenant

#: (n_devices, parity) with n_data >= 1; chunk; zone id; logical page
_GEOM = st.tuples(st.integers(1, 8), st.booleans()).map(
    lambda t: (max(t[0], 2) if t[1] else t[0], t[1]))


@settings(max_examples=60, deadline=None)
@given(_GEOM, st.integers(1, 64), st.integers(0, 16),
       st.integers(0, 4096))
def test_locate_page_round_trip(geom, chunk, zone, page):
    n_devices, parity = geom
    n_data = n_devices - (1 if parity else 0)
    stripe, slot, r, dev = locate_page(zone, page, chunk, n_data,
                                       n_devices, parity)
    assert 0 <= r < chunk
    assert 0 <= slot < n_data
    assert 0 <= dev < n_devices
    # the decomposition is exact: page = (stripe * n_data + slot) * c + r
    assert (stripe * n_data + slot) * chunk + r == page
    # device is a pure function of (zone, stripe, slot)
    assert dev == data_device_of(zone, stripe, slot, n_devices, parity)
    # without parity the device IS the slot
    if not parity:
        assert dev == slot


@settings(max_examples=60, deadline=None)
@given(st.integers(2, 8), st.integers(0, 16), st.integers(0, 64))
def test_parity_rotation_invariants(n_devices, zone, stripe):
    p = parity_device_of(zone, stripe, n_devices)
    assert 0 <= p < n_devices
    # RAID-5 rotation: consecutive stripes cycle every member once
    window = {parity_device_of(zone, stripe + k, n_devices)
              for k in range(n_devices)}
    assert window == set(range(n_devices))
    # no data slot of a stripe ever lands on its parity device, and the
    # n_data data slots plus parity tile the devices exactly
    devs = {data_device_of(zone, stripe, s, n_devices, True)
            for s in range(n_devices - 1)}
    assert p not in devs
    assert devs | {p} == set(range(n_devices))


@settings(max_examples=25, deadline=None)
@given(_GEOM, st.integers(1, 8), st.integers(0, 3),
       st.lists(st.integers(1, 40), min_size=1, max_size=6))
def test_stripe_program_matches_locate_page(geom, chunk, zone, writes):
    """The program-space striper sends every host page to exactly the
    member ``locate_page`` names, in logical page order."""
    n_devices, parity = geom
    n_data = n_devices - (1 if parity else 0)
    member_zone_pages = chunk * 8
    cap = n_data * member_zone_pages
    total = 0
    rows = []
    for w in writes:
        w = min(w, cap - total)
        if w <= 0:
            break
        rows.append((E.OP_WRITE, zone, w, E.F_HOST))
        total += w
    if not rows:
        return
    prog = tag_tenant(E.encode_program(rows), 0)
    striped = stripe_program(prog, n_devices=n_devices,
                             chunk_pages=chunk, parity=parity,
                             member_zone_pages=member_zone_pages,
                             parity_tenant=1)
    assert len(striped) == n_devices
    # expected per-device host-data pages, page by logical page
    want = np.zeros(n_devices, dtype=np.int64)
    for page in range(total):
        want[locate_page(zone, page, chunk, n_data, n_devices,
                         parity)[3]] += 1
    got = np.zeros(n_devices, dtype=np.int64)
    for d, p in enumerate(striped):
        data = (p[:, 0] == E.OP_WRITE) & (p[:, TENANT_COL] == 0)
        got[d] = int(p[data, 2].sum())
        # each member sees a strictly sequential append stream: chunks
        # of at most `chunk` pages
        assert (p[data, 2] <= chunk).all()
    assert np.array_equal(got, want), (geom, chunk, zone, writes)
