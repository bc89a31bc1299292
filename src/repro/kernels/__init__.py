"""Pallas TPU kernels: the traversal's local discovery (``spmsv``,
``bottomup``), the "1ds" frontier codec, and the R-MAT generator's
twin in ``graph/rmat.py``.

Each kernel holds some operands as whole blocks in fast memory (HBM
streaming is future work), so every wrapper checks its shape against
the budget below before building the ``pallas_call``: a graph too large
for one core fails at trace time with the kernel's name, never by
falling back to another path.
"""

# Budgets for whole-array operand blocks on one TPU v5e core: the
# default scoped VMEM limit is 16 MiB and SMEM holds 1 MiB; the rest is
# left for the kernel's own intermediates.
VMEM_BUDGET = 12 << 20
SMEM_BUDGET = 512 << 10


def check_fast_memory(kernel: str, *, vmem: int = 0, smem: int = 0) -> None:
    """Raise if a kernel's whole-array blocks exceed the v5e budgets."""
    if vmem > VMEM_BUDGET or smem > SMEM_BUDGET:
        raise ValueError(
            f"{kernel}: whole-array blocks need {vmem} B of VMEM and "
            f"{smem} B of SMEM, over the {VMEM_BUDGET} / {SMEM_BUDGET} B "
            f"budget of one v5e core; local_mode='kernel' needs a smaller "
            f"per-device graph at this shape")
