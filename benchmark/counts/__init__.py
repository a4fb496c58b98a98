"""What every roofline share divides by: the least time the chip could take.

The operations and bytes themselves depend on the architecture: a module
beside this one (``counts/decoder.py``) counts them from shapes, and
``archs/<name>.py`` names it as its ``counts`` (a cell's
``cell.family.counts``).  This module only sets them against the peaks.
"""

from __future__ import annotations

from typing import Dict


def roofline_seconds(cost: Dict, peak: Dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(cost["flops"] / peak["bf16_flops_per_s"],
               cost["bytes"] / peak["hbm_bytes_per_s"])
