"""Pallas TPU kernels for the perf-critical layers.

msgq/            paper §3.2: cell-queue message copy (eager 2-copy through
                 VMEM staging cells vs direct 1-copy HBM DMA)
flash_attention/ blocked online-softmax attention (GQA, causal, window)
ssd_scan/        Mamba2 SSD chunk scan with carried state
paged_attention/ block-table decode/verify attention (q-blocks of K >= 1)

Each kernel ships <name>.py (pl.pallas_call + BlockSpec), ops.py (public
wrapper), ref.py (pure-jnp oracle). The wrappers compile for the TPU by
default; CPU tests pass interpret=True, and tests/test_tpu_compile.py
compiles each one for a described v5e chip.
"""
