"""Operations and bytes a kernel call needs, from its shapes alone.

The nominal work is the same whatever implements it (tile sizes, padding,
split-word precision passes do not count), so a roofline share built on it
rises only when the same work takes less device time.
"""

from __future__ import annotations

F32_BYTES = 4


def gram(n: int, m: int, d: int) -> tuple[float, float]:
    """G = K_nm^T K_nm and rhs = K_nm^T y over n rows and m landmarks:
    2 n m^2 + 2 n m flop; x, y and the landmarks read once, G and rhs
    written once."""
    flop = 2.0 * n * m * m + 2.0 * n * m
    bytes_ = F32_BYTES * (n * d + n + m * d + m * m + m)
    return flop, float(bytes_)


def roofline_pct(flop: float, bytes_: float, seconds: float,
                 peak: dict) -> float | None:
    """Least time the chip could take, the larger of flop over the bf16
    peak and bytes over the HBM bandwidth, over the measured time, in %.
    At the benchmark's Gram shapes the flop term is the larger by some
    400x: the Gram is compute-bound."""
    if seconds <= 0:
        return None
    t_flop = flop / peak["bf16_flop_per_s"]
    t_mem = bytes_ / peak["hbm_bytes_per_s"]
    return 100.0 * max(t_flop, t_mem) / seconds
