"""The zone manager's empty/open/active sets and the allocator's plan view
built on them, against a full scan of every zone.

* after every step of a seeded random sequence of allocations, closes,
  finishes, resets and freezes, the zone manager's sets hold exactly the
  zones a scan finds in those states;
* each placement policy, planned on those sets, picks exactly the zones
  the scanning plan view picks, step for step, errors included;
* with a full-size ZN540 (904 zones), planning a 1 MiB flush-chunk
  write reads the state of a few zones, not of every zone.
"""
import numpy as np
import pytest

from repro.core import MiB, ZNSDeviceSpec, ZoneError, ZoneState
from repro.core.spec import ACTIVE_STATES, OPEN_STATES
from repro.host import ZoneAllocator, allocator

POLICIES = ("greedy-open", "striped", "lifetime-binned")
SPEC = ZNSDeviceSpec(name="sets", zone_size_bytes=8 * MiB,
                     zone_cap_bytes=6 * MiB, num_zones=24,
                     max_open_zones=3, max_active_zones=5)


class ScanView(allocator._PlanView):
    """The plan view as it was: every count and list a scan of all
    zones."""

    @property
    def open_count(self):
        return sum(1 for z in range(self.spec.num_zones)
                   if self.state(z) in OPEN_STATES)

    @property
    def active_count(self):
        return sum(1 for z in range(self.spec.num_zones)
                   if self.state(z) in ACTIVE_STATES)

    def open_zones(self):
        skip = self.alloc.reserved | self.alloc.frozen
        out = []
        open_headroom = self.open_count < self.spec.max_open_zones
        for z in range(self.spec.num_zones):
            if z in skip or self.remaining(z) <= 0:
                continue
            st = self.state(z)
            if st in OPEN_STATES or (st == ZoneState.CLOSED
                                     and open_headroom):
                out.append(z)
        return out

    def empty_zones(self):
        skip = self.alloc.reserved | self.alloc.frozen
        return [z for z in range(self.spec.num_zones)
                if z not in skip and self.state(z) == ZoneState.EMPTY]


def _scan(zm, states):
    return {z for z, zi in enumerate(zm.zones) if zi.state in states}


def _walk(policy, seed, steps=400):
    """Drive an allocator through a seeded random sequence; the zones,
    offsets and errors of every step, and the zone states after it."""
    rng = np.random.default_rng(seed)
    alloc = ZoneAllocator(SPEC, policy=policy, reserved=(0,),
                          stripe_bytes=1 * MiB, stripe_width=4,
                          lifetime_bins=4)
    zm = alloc.zm
    log = []
    for _ in range(steps):
        r = float(rng.random())
        zone = int(rng.integers(SPEC.num_zones))
        # close, finish and reset mostly zones they apply to
        pick = sorted(_scan(zm, OPEN_STATES if r < 0.65 else ACTIVE_STATES
                            if r < 0.75 else (ZoneState.FULL,)))
        if pick and rng.random() < 0.8:
            zone = pick[int(rng.integers(len(pick)))]
        try:
            if r < 0.55:
                ext = alloc.allocate(int(rng.integers(1, 5 * MiB)),
                                     stream=int(rng.integers(3)),
                                     lifetime=int(rng.integers(4)))
                out = [(e.zone, e.offset, e.nbytes) for e in ext]
            elif r < 0.65:
                zm.close(zone)
                out = "close"
            elif r < 0.75:
                zm.finish(zone)
                out = "finish"
            elif r < 0.9:
                zm.reset(zone)
                alloc.frozen.discard(zone)
                alloc.forget_zone(zone)
                out = "reset"
            else:
                alloc.frozen.symmetric_difference_update({zone})
                out = "freeze"
        except ZoneError as e:
            out = f"error: {e}"
        for states, got in ((OPEN_STATES, zm.open_set),
                            (ACTIVE_STATES, zm.active_set),
                            ((ZoneState.EMPTY,), zm.empty_set)):
            assert got == _scan(zm, states)
        assert zm.open_count == len(_scan(zm, OPEN_STATES))
        assert zm.active_count == len(_scan(zm, ACTIVE_STATES))
        log.append((out, tuple(zi.state for zi in zm.zones),
                    tuple(zi.write_pointer for zi in zm.zones)))
    return log


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
@pytest.mark.parametrize("policy", POLICIES)
def test_plans_match_a_full_scan(policy, seed, monkeypatch):
    fast = _walk(policy, seed)
    monkeypatch.setattr(allocator, "_PlanView", ScanView)
    slow = _walk(policy, seed)
    assert fast == slow
    placed = [out for out, *_ in fast if isinstance(out, list)]
    errors = [out for out, *_ in fast if str(out).startswith("error")]
    # the walk reaches both placements and refusals
    assert len(placed) > 50 and errors


def test_full_drive_flush_chunk_plan_reads_few_zones(monkeypatch):
    spec = ZNSDeviceSpec()
    alloc = ZoneAllocator(spec, policy="greedy-open")
    for _ in range(1200):                 # more than one zone's worth
        alloc.allocate(1 * MiB)
    seen = set()
    zm = alloc.zm
    for name in ("state", "write_pointer"):
        read = getattr(zm, name)

        def counted(z, read=read):
            seen.add(z)
            return read(z)
        monkeypatch.setattr(zm, name, counted)
    alloc.allocate(1 * MiB)
    assert alloc.open_count == 1
    assert 1 <= len(seen) <= spec.max_active_zones
