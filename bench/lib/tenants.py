"""Tenant windows as the benchmark sees them, and the traffic they receive.

A :class:`TenantModel` holds one tenant's clusters (lanes) slot by slot:
the admitted classes' raw fields, the capacity and the unit chip cost of
each lane.  The same model generates the tenant's events (so departures
and edits always address admitted classes) and, replayed a second time,
tells the reference what each lane holds when a flush report answers an
event.  Its slot rule is the program's documented one: an arrival takes
the lowest free slot of its lane.

Events are plain tuples, translated to the program's event types only by
the load generator:

* ``("arrival", lane, params)``   a new class with its nine raw fields;
* ``("departure", lane, slot)``   the class in ``slot`` leaves;
* ``("edit", lane, slot, updates)``  SLA renegotiation of five fields;
* ``("capacity", lane, R)``       the lane's capacity becomes ``R``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench.lib import reference, table5

KINDS = ("arrival", "departure", "edit", "capacity")


class TenantModel:
    """One tenant's lanes, slot by slot.

    Parameters
    ----------
    raw : dict
        The nine raw fields, each (lanes, classes): the initial classes,
        in slots ``0 .. classes-1``.
    R, rho_bar : numpy.ndarray
        (lanes,) capacities and unit chip costs.
    n_max : int
        Slots per lane.
    """

    def __init__(self, raw: dict, R, rho_bar, n_max: int):
        lanes, n = raw["A"].shape
        self.n_max = n_max
        self.raw = np.zeros((len(table5.RAW_FIELDS), lanes, n_max))
        for i, f in enumerate(table5.RAW_FIELDS):
            self.raw[i, :, :n] = raw[f]
        self.mask = np.zeros((lanes, n_max), bool)
        self.mask[:, :n] = True
        self.R = np.asarray(R, float).copy()
        self.R_nominal = self.R.copy()
        self.rho_bar = np.asarray(rho_bar, float).copy()
        self.version = np.zeros(lanes, int)

    @property
    def lanes(self) -> int:
        return self.mask.shape[0]

    def copy(self) -> "TenantModel":
        other = object.__new__(TenantModel)
        other.__dict__ = {k: (v.copy() if isinstance(v, np.ndarray) else v)
                          for k, v in self.__dict__.items()}
        return other

    def lane_raw(self, lane: int) -> dict:
        """The admitted classes of ``lane``: raw fields, (n,) each."""
        cols = self.mask[lane]
        return {f: self.raw[i, lane, cols]
                for i, f in enumerate(table5.RAW_FIELDS)}

    def apply(self, ev: tuple):
        """Apply one event; returns ``(lane, slot)`` (slot None unless an
        arrival)."""
        kind, lane = ev[0], ev[1]
        slot = None
        if kind == "arrival":
            free = np.flatnonzero(~self.mask[lane])
            if free.size == 0:
                raise ValueError(f"lane {lane} is full ({self.n_max} slots)")
            slot = int(free[0])
            self.raw[:, lane, slot] = [ev[2][f] for f in table5.RAW_FIELDS]
            self.mask[lane, slot] = True
        elif kind == "departure":
            self._check(lane, ev[2])
            self.mask[lane, ev[2]] = False
            self.raw[:, lane, ev[2]] = 0.0
        elif kind == "edit":
            self._check(lane, ev[2])
            for f, v in ev[3].items():
                self.raw[table5.RAW_FIELDS.index(f), lane, ev[2]] = v
        elif kind == "capacity":
            self.R[lane] = ev[2]
        else:
            raise ValueError(f"unknown event {ev!r}")
        self.version[lane] += 1
        return lane, slot

    def _check(self, lane: int, slot: int) -> None:
        if not self.mask[lane, slot]:
            raise ValueError(f"(lane {lane}, slot {slot}) holds no class")


def initial_tenants(rng: np.random.Generator, tenants: int, lanes: int,
                    classes: int, n_max: int,
                    capacity_factor: float) -> List[TenantModel]:
    """Every tenant's initial lanes, drawn in bulk from Table 5; each lane's
    capacity is ``capacity_factor`` times the sum of its classes' ``r_up``."""
    raw = table5.draw_classes(rng, (tenants, lanes, classes))
    rho_bar = table5.draw_rho_bar(rng, (tenants, lanes))
    R = table5.f32(capacity_factor * table5.r_up(raw).sum(axis=-1))
    return [TenantModel({f: raw[f][t] for f in table5.RAW_FIELDS}, R[t],
                        rho_bar[t], n_max) for t in range(tenants)]


def make_events(rng: np.random.Generator, models: List[TenantModel],
                tenant_of: np.ndarray, mix: Dict[str, float],
                capacity_jitter: float) -> List[Tuple[int, tuple]]:
    """One event for each entry of ``tenant_of``, applied to ``models`` as
    it is made.

    The kind is drawn from ``mix``; an arrival goes to a lane with a free
    slot, a departure or an edit to an admitted class drawn uniformly, a
    capacity change sets the lane to its nominal capacity times a factor
    uniform in ``1 +- capacity_jitter``.  Where no class is admitted, a
    departure or edit becomes an arrival.
    """
    p = np.asarray([mix[k] for k in KINDS], float)
    kinds = rng.choice(len(KINDS), size=len(tenant_of), p=p / p.sum())
    fresh = table5.draw_classes(rng, (len(tenant_of),))
    u = rng.random((len(tenant_of), 2))
    out = []
    for j, (t, k) in enumerate(zip(tenant_of, kinds)):
        m = models[t]
        occupied = np.argwhere(m.mask)
        kind = KINDS[k]
        if kind in ("departure", "edit") and occupied.size == 0:
            kind = "arrival"
        if kind == "arrival":
            open_lanes = np.flatnonzero(~m.mask.all(axis=1))
            lane = int(open_lanes[int(u[j, 0] * len(open_lanes))])
            ev = ("arrival", lane,
                  {f: float(fresh[f][j]) for f in table5.RAW_FIELDS})
        elif kind in ("departure", "edit"):
            lane, slot = occupied[int(u[j, 0] * len(occupied))]
            lane, slot = int(lane), int(slot)
            ev = (("departure", lane, slot) if kind == "departure" else
                  ("edit", lane, slot,
                   {f: float(fresh[f][j]) for f in table5.EDIT_FIELDS}))
        else:
            lane = int(u[j, 0] * m.lanes)
            factor = 1.0 + capacity_jitter * (2.0 * u[j, 1] - 1.0)
            ev = ("capacity", lane,
                  float(table5.f32(m.R_nominal[lane] * factor)))
        m.apply(ev)
        out.append((int(t), ev))
    return out


class LaneReference:
    """Reference equilibria of every lane state, computed once per state."""

    def __init__(self, dtype: str = "float64"):
        self.dtype = dtype
        self._cache: Dict[tuple, dict] = {}

    def lane(self, tenant: int, model: TenantModel, lane: int) -> dict:
        key = (tenant, lane, int(model.version[lane]))
        if key not in self._cache:
            ref = reference.equilibrium(model.lane_raw(lane), model.R[lane],
                                        model.rho_bar[lane],
                                        dtype=self.dtype)
            r = np.zeros(model.n_max)
            r[model.mask[lane]] = ref["r"]
            self._cache[key] = {"r": r, "total": ref["total"]}
        return self._cache[key]
