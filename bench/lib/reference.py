"""Plain reference of the allocation game (paper Sec. 4, Algorithm 4.1).

Written from the paper, independently of the program: one cluster at a
time, only its admitted classes (no padding, no masks, no batching), in a
chosen numpy precision.  float64 is the reference; a lower precision
(bfloat16) is the control that the comparison in :mod:`bench.lib.checks`
has to reject.

Per iteration the Resource Manager solves (P5) exactly for the posted bids
by sweeping every candidate price (each bid and the interval ends
``rho_bar``, ``max rho_up``): at a price, every class bidding at least that
price is admitted, gets its guaranteed ``r_low`` and shares the spare
capacity greedily in decreasing ``p = m / K`` up to ``r_up``.  Each Class
Manager then answers in closed form (Prop. 4.1), ``psi = clip(K / r,
1/H_up, 1/H_low)``, and a manager still rejecting jobs raises its bid by
``lam * rho_up`` from ``max(bid, price)``, capped at ``rho_up``.  The game
stops when the relative allocation change ``sum |r' - r| / r`` falls below
``eps_bar``.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

#: Precisions the reference can run in.
DTYPES = {"float64": np.float64, "float32": np.float32,
          "bfloat16": ml_dtypes.bfloat16}

EPS_BAR, LAM, MAX_ITERS = 0.03, 0.05, 200


def derive(raw: dict, dt) -> dict:
    """Closed-form constants of each class (Props. 3.3 / 4.1, Eqs. 7, 8, 17,
    18) from its raw fields, computed in ``dt``."""
    A, B, E, cM, cR, H_up, H_low, m, rho_up = (
        np.asarray(raw[k], dtype=dt) for k in
        ("A", "B", "E", "cM", "cR", "H_up", "H_low", "m", "rho_up"))
    K = (np.sqrt(A / cM) + np.sqrt(B / cR)) ** 2 / -E
    return {"K": K, "r_low": K * H_low, "r_up": K * H_up, "p": m / K,
            "psi_low": dt(1) / H_up, "psi_up": dt(1) / H_low,
            "alpha": m * H_up * H_low, "beta": m * H_low, "rho_up": rho_up,
            "E": E}


def _rm_best_response(c: dict, bids, R, rho_bar, dt):
    """Exact (P5): the price and allocation that maximise the RM's revenue."""
    rho_hat = np.max(c["rho_up"]) if bids.size else rho_bar
    cand = np.concatenate([bids, np.asarray([rho_bar, rho_hat], dt)])
    order = np.argsort(-c["p"], kind="stable")
    admitted = bids[order][None, :] >= cand[:, None]          # (Nc, n)
    inc = np.where(admitted, (c["r_up"] - c["r_low"])[order][None, :],
                   dt(0))
    spare = R - np.sum(c["r_low"])
    before = np.cumsum(inc, axis=1) - inc
    fill = np.clip(spare - before, dt(0), inc)
    total_r = np.sum(c["r_low"]) + np.sum(fill, axis=1)
    p_sorted = c["p"][order]
    p_r = np.sum(c["p"] * c["r_low"]) + np.sum(fill * p_sorted[None, :],
                                               axis=1)
    revenue = (cand - rho_bar) * total_r + p_r \
        - np.sum(c["p"] * c["r_up"])
    best = int(np.argmax(revenue))
    r = c["r_low"].copy()
    r[order] = r[order] + fill[best]
    return cand[best], r


def equilibrium(raw: dict, R: float, rho_bar: float, *,
                dtype: str = "float64", eps_bar: float = EPS_BAR,
                lam: float = LAM, max_iters: int = MAX_ITERS) -> dict:
    """Algorithm 4.1 for one cluster's admitted classes.

    Parameters
    ----------
    raw : dict
        The nine raw fields, each an (n,) array of the admitted classes.
    R, rho_bar : float
        Cluster capacity and unit chip cost.
    dtype : str
        A key of :data:`DTYPES`; every input and every operation is in it.

    Returns
    -------
    dict
        ``r``, ``psi`` (n,), ``cost``, ``penalty``, ``total``, ``rho``
        (floats), ``iters`` (int) and ``feasible`` (bool:
        ``sum r_low <= R`` and every ``E < 0``).
    """
    dt = DTYPES[dtype]
    c = derive(raw, dt)
    R, rho_bar = dt(R), dt(rho_bar)
    lam = dt(lam)
    r = c["r_low"].copy()
    bids = np.full(r.shape, rho_bar, dt)
    rho, it = rho_bar, 0
    while it < max_iters:
        rho, r_new = _rm_best_response(c, bids, R, rho_bar, dt)
        psi = np.clip(c["K"] / r_new, c["psi_low"], c["psi_up"])
        rejecting = psi > c["psi_low"] * dt(1 + 1e-9)
        raised = np.minimum(np.maximum(bids, rho) + lam * c["rho_up"],
                            c["rho_up"])
        bids = np.where(rejecting, raised, bids)
        eps = np.sum(np.abs(r_new - r) / r)
        r, it = r_new, it + 1
        if not eps >= eps_bar:
            break
    psi = np.clip(c["K"] / r, c["psi_low"], c["psi_up"])
    cost = rho_bar * np.sum(r)
    penalty = np.sum(c["alpha"] * psi - c["beta"])
    return {"r": r.astype(np.float64), "psi": psi.astype(np.float64),
            "cost": float(cost), "penalty": float(penalty),
            "total": float(cost + penalty), "rho": float(rho), "iters": it,
            "feasible": bool(np.sum(c["r_low"]) <= R
                             and np.all(c["E"] < 0))}
