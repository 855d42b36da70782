"""Published peaks per chip, keyed by JAX's ``device_kind``, and the
operation and byte counts of the kernels whose roofline share is reported.

A device kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict, Tuple

PEAKS: Dict[str, Dict[str, object]] = {
    # one TPU v5e chip; JAX reports its kind as "TPU v5 lite"
    "TPU v5 lite": {
        "flop_per_s": 197e12,          # bf16 MXU peak
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(197 TFLOP/s bf16, 16 GB HBM at 819 GB/s)",
    },
}


def peaks(device_kind: str) -> Dict[str, object]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to chipbench/bench/peaks.py with their "
                       f"source")
    return PEAKS[device_kind]


def cma_gen_update_work(n: int, lam: int) -> Tuple[float, float]:
    """(operations, bytes) of one slot of ``cma_gen_update`` at the
    unpadded problem size: dimension ``n``, population ``lam``.

    The kernel works in float32 on the chip.  It reads C, B (n² each), D,
    p_σ, p_c (n each), Y (λ·n), w (λ) and 7 scalar coefficients, and writes
    C', p_σ', p_c' and y_w.  Operations: the weighted gram Yᵀ·diag(w)·Y
    (2λn² + λn), y_w = wᵀY (2λn), the whitened step (y_w·B)/D·Bᵀ (4n² + n),
    the two paths (about 8n), p_c·p_cᵀ (n²) and C' = a·C + b·G + c·P (5n²).
    """
    ops = 2.0 * lam * n * n + 3.0 * lam * n + 10.0 * n * n + 9.0 * n
    nbytes = 4.0 * (3 * n * n + 7 * n + lam * n + lam + 7)
    return ops, nbytes


def roofline_time(ops: float, nbytes: float, device_kind: str
                  ) -> Tuple[float, str]:
    """The least time the chip could take for that work, and which bound
    sets it ("compute" or "memory")."""
    p = peaks(device_kind)
    t_ops = ops / float(p["flop_per_s"])
    t_mem = nbytes / float(p["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
