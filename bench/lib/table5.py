"""The paper's Table 5/6 class-parameter design (arXiv:1701.04763 Sec. 5.1),
drawn with numpy in bulk.

Every value is rounded to float32 once, here, so that the program (which
runs in float32 on the chip) and the float64 reference start from the very
same numbers.
"""
from __future__ import annotations

import numpy as np

#: Raw per-class fields a job class carries (the program's wire format).
RAW_FIELDS = ("A", "B", "E", "cM", "cR", "H_up", "H_low", "m", "rho_up")
#: SLA fields an SLA renegotiation redraws.
EDIT_FIELDS = ("E", "m", "rho_up", "H_up", "H_low")


def f32(x):
    """``x`` rounded to float32 and returned as float64 (exact)."""
    return np.asarray(x, np.float32).astype(np.float64)


def draw_profiles(rng: np.random.Generator, shape) -> dict:
    """Table 5 draws for ``shape`` classes, before the deadline is applied.

    Returns the profile terms with the tail ``C`` and deadline ``D`` kept
    apart, so one draw can be evaluated at several deadline scales.
    """
    rho_up = rng.uniform(5.0, 20.0, shape)                      # cents
    H_up = rng.integers(5, 21, shape).astype(float)
    cM = rng.integers(1, 5, shape).astype(float)
    cR = rng.integers(1, 5, shape).astype(float)
    m = rng.uniform(15000.0, 30000.0, shape)                    # cents
    nM = rng.integers(70, 1121, shape).astype(float)
    nR = 64.0
    M_max = rng.uniform(16.0, 120.0, shape)                     # s
    R_max = rng.uniform(15.0, 75.0, shape)
    Sh1_max = rng.uniform(10.0, 30.0, shape)
    Shtyp_max = rng.uniform(30.0, 150.0, shape)
    D = rng.uniform(900.0, 1500.0, shape)
    # Table 6: X^avg = 0.8 X^max, H_low = max(floor(0.8 H_up), 1)
    return {"A": nM * 0.8 * M_max, "B": nR * (0.8 * Shtyp_max + 0.8 * R_max),
            "C": M_max + R_max + Sh1_max + Shtyp_max, "D": D,
            "cM": cM, "cR": cR, "H_up": H_up,
            "H_low": np.maximum(np.floor(0.8 * H_up), 1.0),
            "m": m, "rho_up": rho_up}


def raw_fields(profiles: dict, deadline_scale: float = 1.0) -> dict:
    """The nine raw fields at one deadline scale, rounded to float32."""
    out = {k: f32(profiles[k]) for k in RAW_FIELDS if k != "E"}
    out["E"] = f32(profiles["C"] - deadline_scale * profiles["D"])
    return out


def draw_classes(rng: np.random.Generator, shape) -> dict:
    """Raw fields of ``shape`` fresh classes at the nominal deadline."""
    return raw_fields(draw_profiles(rng, shape))


def draw_rho_bar(rng: np.random.Generator, shape=()) -> np.ndarray:
    """Unit chip cost per cluster (paper Eq. 15, v = 2), float32-rounded."""
    d = rng.uniform(3.0, 5.0, shape)
    pue = rng.uniform(1.2, 2.2, shape)
    energy = rng.uniform(0.06009, 0.06690, shape)
    return f32((pue * energy + 2.0615) * 2.0 / d)


def r_up(raw: dict) -> np.ndarray:
    """Upper allocation bound ``K * H_up`` (float64), used to size capacity."""
    K = (np.sqrt(raw["A"] / raw["cM"]) + np.sqrt(raw["B"] / raw["cR"])) ** 2 \
        / -raw["E"]
    return K * raw["H_up"]
