"""Pallas TPU kernels for the compute hot spots (flash attention, Mamba-2
SSD chunked scan, fused RMSNorm) with jit'd wrappers (ops.py) and pure-jnp
oracles (ref.py).  Tests validate them on CPU with ``interpret=True``; the
models use them only when built with ``Model(..., impl="pallas")`` (the
trainer's ``--impl pallas``), which compiles them with Mosaic for the TPU."""
from . import ops, ref

__all__ = ["ops", "ref"]
