"""What the Pallas TPU kernels share: compiler parameters and a VMEM budget.

Each kernel keeps its blocks in VMEM, double-buffered by the Pallas pipeline,
and Mosaic refuses a kernel whose blocks and scratch exceed the scoped VMEM
limit.  Most blocks hold a whole item or bit-plane axis, so what fits depends
on the catalog width.  Every wrapper prices its blocks with
:func:`vmem_bytes` and calls :func:`check_vmem` before building the kernel;
the autotuner prices its block configs the same way and leaves out those that
do not fit (``kernels/autotune.py``).
"""

from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu

# Mosaic's default scoped VMEM limit on v5e, set explicitly so the budget
# below and the compiler agree
VMEM_LIMIT_BYTES = 16 * 2**20
# what the blocks may take: the rest is left to Mosaic's own scratch
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES - 2**20

# the candidate-block axis is independent; the transaction axis accumulates
COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT_BYTES)


class VmemError(ValueError):
    """A kernel's blocks do not fit the VMEM budget at this shape."""


def tile_bytes(shape: tuple[int, int], itemsize: int) -> int:
    """VMEM bytes of one 2-D block: rows padded to the sublane tile (8 rows
    of 32-bit, 32 of 8-bit values), columns to the 128 lanes."""
    rows, cols = shape
    sub = 8 * (4 // itemsize)
    return (-(-rows // sub) * sub) * (-(-cols // 128) * 128) * itemsize


def vmem_bytes(blocks, scratch=()) -> int:
    """VMEM a kernel needs: its ``(shape, itemsize)`` input and output blocks
    twice (the pipeline's two buffers), its scratch once."""
    return (2 * sum(tile_bytes(s, n) for s, n in blocks)
            + sum(tile_bytes(s, n) for s, n in scratch))


def check_vmem(kernel: str, need: int) -> None:
    """Raise :class:`VmemError` when ``need`` bytes exceed the budget."""
    if need > VMEM_BUDGET_BYTES:
        raise VmemError(
            f"{kernel}: its blocks need {need} bytes of VMEM, over the "
            f"{VMEM_BUDGET_BYTES}-byte budget at this width; use smaller "
            f"blocks or a jnp impl")
