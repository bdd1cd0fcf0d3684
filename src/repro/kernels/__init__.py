"""repro.kernels — Pallas TPU kernels for the framework's compute hot spots.

Two kernels, each a (kernel.py, ops.py, ref.py) triple validated in
interpret mode against the pure-jnp oracle (tests/test_kernels.py):

* ``gmsa_score`` — the paper's per-slot dispatch inner loop at fleet scale:
  fused cost matvec (MXU) + drift add (VPU) + running argmin reduction, one
  VMEM pass over the (K, N, N) task-allocation tensor. Vmapped over
  Monte-Carlo runs at N < 128, one launch decides every run, the runs on
  the lane axis.
* ``ssd_scan``   — Mamba-2 chunked SSD forward (the long_500k hot spot):
  intra-chunk attention-form + cross-chunk recurrence carried in VMEM
  scratch across the sequential chunk grid.

The dry-run lowers the pure-JAX paths (XLA cost analysis cannot see inside
``pallas_call`` custom-calls); kernels are opt-in for real TPU execution and
benchmarked separately (benchmarks/kernel_bench.py). See DESIGN.md §6.

Compiled-vs-interpret policy: both kernels are TPU-tiled (``pltpu.VMEM``
scratch, Mosaic lowering), so native compilation is a TPU capability —
:func:`supports_compiled_pallas` gates it, :func:`default_interpret` is the
per-backend default every ``interpret=None`` entry point resolves through,
and the benchmarks record their timing matrix per backend against it.
"""

import jax

__all__ = ["default_interpret", "pallas_backend", "supports_compiled_pallas"]


def pallas_backend() -> str:
    """The backend kernels would lower for (``"cpu"``/``"gpu"``/``"tpu"``)."""
    return jax.default_backend()


def supports_compiled_pallas() -> bool:
    """Whether the repo's Pallas kernels can compile natively here.

    Both kernels target Mosaic (TPU memory spaces and tiling); off-TPU they
    run under the Pallas interpreter, numerically identical and test-pinned
    against the jnp oracles, but orders of magnitude slower.
    """
    return pallas_backend() == "tpu"


def default_interpret() -> bool:
    """The ``interpret=`` default: compiled on TPU, interpret elsewhere."""
    return not supports_compiled_pallas()
