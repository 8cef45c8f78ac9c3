"""Operations and bytes: of one TFTNN hop, and of each Pallas kernel call.

``macs_per_hop`` is a copy of the program's analytic count
(``models/tftnn.py:macs_per_frame``), kept here so that a change to the
program cannot move the yardstick; it counts the model's multiply-adds for
one 128-sample hop at a configuration's widths (8.81 M at TFTNN's published
widths). The ``tftnn`` family module (``bench/models/tftnn.py``) gives it to
the harness. One MAC is two operations.

``kernel_cost`` gives the operations a kernel call needs and the bytes it
must move through HBM at least (every operand read once, every result
written once), from the shapes in the call's HLO instruction. The
operations come from the kernel family's own file,
``bench/kernels/<family>.py``, whose ``cost(results, operands)`` counts
them; a file may also claim other families by a ``matches(family)`` of its
own (``linear_attention.py`` claims every ``linear_attention*`` family).
A kernel family without a file is costed by its bytes alone, which still
bounds its least time from below.
"""

from __future__ import annotations

import functools
from pathlib import Path
from typing import Callable, Dict, Optional, Tuple

import loader
from tracing import nbytes

KERNELS = Path(__file__).resolve().parent / "kernels"


def macs_per_hop(m: Dict) -> float:
    """Multiply-accumulates of one frame (one hop) through the model."""
    C, d, F = m["channels"], m["att_dim"], m["freq_bins"]
    Fp = F // m["downsample"]
    kf, kt = m["conv_kernel_f"], m["conv_kernel_t"]
    H = m["gru_hidden"]
    dense = m["dilated_block"] == "dense"
    mac = kf * kt * 2 * C * F  # enc_in
    for i, _ in enumerate(m["dilation_rates"]):
        mac += kf * kt * (C * (i + 1) * C if dense else (C // 2) * (C // 2)) * F
    mac += kf * kt * C * C * Fp  # enc_down
    mac += C * d * Fp + d * C * Fp  # att_in, att_out
    gru = lambda din, h: 3 * (din * h + h * h)  # noqa: E731
    for _ in range(m["num_transformer_blocks"]):
        mac += 3 * d * d * Fp + d * d * Fp  # QKV and output projections
        mac += (d * Fp * d + Fp * d * d) if m["softmax_free"] else (Fp * d * Fp + Fp * Fp * d)
        mac += 2 * gru(d, H) * Fp + 2 * H * d * Fp  # sub-band bi-GRU + w_out
        if m["full_band_attention"]:
            mac += 3 * d * d * Fp + d * d * Fp + Fp * 2 * d * d
        ngru = 2 if m["bidirectional_fullband_gru"] else 1
        mac += ngru * gru(d, H) * Fp + ngru * H * d * Fp
    mac += C * C * Fp * (3 if m["mask_gtu"] else 2)  # mask module
    for i, _ in enumerate(m["dilation_rates"]):
        mac += kf * kt * (C * (i + 1) * C if dense else (C // 2) * (C // 2)) * Fp
    mac += kf * kt * C * (C * m["downsample"]) * Fp  # dec_up
    mac += kf * kt * C * 2 * F  # dec_out
    return float(mac)


@functools.lru_cache(maxsize=None)
def _counter(family: str) -> Optional[Callable]:
    """``cost`` of the kernel file that names ``family``, or None."""
    own = KERNELS / f"{family}.py"
    if own.is_file():
        return loader.load(own).cost
    for path in sorted(KERNELS.glob("*.py")):
        mod = loader.load(path)
        if hasattr(mod, "matches") and mod.matches(family):
            return mod.cost
    return None


def kernel_cost(family: str, results, operands) -> Tuple[float, int]:
    """(operations, HBM bytes) of one Pallas call, from its shapes."""
    moved = nbytes(results) + nbytes(operands)
    count = _counter(family)
    return (float(count(results, operands)) if count else 0.0), moved
