"""Pallas TPU kernels for the serving hot-spots (flash prefill attention,
paged attention over each row's live pages, with optional int8 KV
pages — and the Mamba-2 SSD scan).  Each kernel has a pure-jnp
oracle in ``ref.py`` and a jit'd wrapper in ``ops.py``; on CPU they run
in interpret mode."""
from repro.kernels.ops import flash_attention, paged_attention, ssd_scan

__all__ = ["flash_attention", "paged_attention", "ssd_scan"]
