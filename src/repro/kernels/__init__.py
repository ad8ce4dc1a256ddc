"""Pallas TPU kernels for the paper's compute hot spots.

fwht/          in-VMEM radix-2 butterfly Walsh-Hadamard transform (the
               preconditioning transform H of Omega = D H R)
gram/          blocked kernel-matrix stripes on the MXU with the kernel
               nonlinearity fused (the streaming pass K[:, block])
kmeans_assign/ fused distance + argmin for the Lloyd assignment step
extend_embed/  fused gram->projection serving stripe: the (n, w) kernel
               block is built and contracted against Sigma^{-1/2} U^T
               tile by tile without ever leaving VMEM (serve/extend.py)
fit_sketch/    fused gram->sketch-accumulate training stripe: each
               (m, b) kernel block is contracted into the (b, r') sketch
               rows, cross-term and Frobenius ledgers in one pass with
               the sketch accumulator VMEM-resident, visiting only the
               row tiles of the block's border; the fit's entry updates
               the sketch state in place (stream/accumulate)

Each subpackage ships <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public wrapper, interpret=True on CPU) and ref.py (pure-jnp oracle used by
the allclose test sweeps). Each ops.py registers its (op, ref,
parity-shapes) triple in registry.py at import; the kernel-parity CI job
(tests/test_kernel_registry.py, `kernels`-marked) iterates that registry,
forcing every kernel through interpret mode against its oracle on the
registered seeded shape grid.
"""
from repro.kernels.extend_embed.ops import extend_embed_pallas
from repro.kernels.fit_sketch.ops import fit_sketch_pallas
from repro.kernels.fwht.ops import fwht_pallas
from repro.kernels.gram.ops import gram_stripe_pallas
from repro.kernels.kmeans_assign.ops import assign_pallas
from repro.kernels.registry import (KernelEntry, get_kernel,
                                    kernel_entries, register_kernel,
                                    registered_kernels)
__all__ = ["extend_embed_pallas", "fit_sketch_pallas", "fwht_pallas",
           "gram_stripe_pallas", "assign_pallas",
           "KernelEntry", "get_kernel", "kernel_entries",
           "register_kernel", "registered_kernels"]
