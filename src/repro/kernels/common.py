"""Shared kernel infrastructure.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling, MXU-aligned
block shapes) and are *validated* on CPU with ``interpret=True``, which
executes the kernel body in Python per grid step.  ``should_interpret()``
selects interpret mode automatically off-TPU so the same call sites work in
tests, benchmarks, and on real hardware.  ``tests/test_chip_compile.py``
compiles the serve-path kernels for a described v5e, and ``chip_smoke.py``
fails if a run on the chip would take the interpret path.
"""

from __future__ import annotations

import functools

import jax

#: MXU systolic array dimension — matmul block shapes must be multiples.
MXU_DIM = 128
#: VPU lane count — trailing block dims should be multiples.
LANE_DIM = 128
#: Sublane count for f32 tiles.
SUBLANE_DIM = 8


@functools.cache
def should_interpret() -> bool:
    """True when not running on a real TPU (CPU validation mode)."""
    return jax.default_backend() != "tpu"


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b
