"""The comparison that decides ``correct``.

Every checked stream's received audio is compared with the plain reference
(``reference.py``) run over exactly the samples the stream fed, as its error
energy relative to the reference's, ``sum((served - ref)^2) / sum(ref^2)``
over the samples it received. The numbers are the worst stream's
(``err_energy_max``) and the median stream's (``err_energy_median``); each
configuration's ``check`` entry gives the limit of each number it holds. A
served stream that is shorter than it should be has its missing hops
counted as failed; one that is longer or not finite fails the check.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def compare(served: List[np.ndarray], ref: List[np.ndarray]) -> Dict[str, float]:
    """Relative error energy per stream, reduced to its max and median."""
    errs, bad, samples = [], 0, 0
    for s, r in zip(served, ref):
        if s.size > r.size or not np.all(np.isfinite(s)):
            bad += 1
            continue
        if s.size == 0:
            continue
        r = r[: s.size].astype(np.float64)
        e = np.sum((s.astype(np.float64) - r) ** 2) / max(np.sum(r * r), 1e-30)
        errs.append(float(e))
        samples += s.size
    return {
        "err_energy_max": max(errs) if errs else 0.0,
        "err_energy_median": float(np.median(errs)) if errs else 0.0,
        "streams_bad": bad,
        "streams": len(errs),
        "samples": samples,
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True when no stream was malformed and every limited number is within."""
    return numbers["streams_bad"] == 0 and all(
        numbers[k] <= v for k, v in limits.items())
