"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package has ``kernel.py`` (pl.pallas_call + BlockSpec VMEM
tiling; ``interpret=True`` runs it in interpret mode, as the CPU tests do),
``ref.py`` (pure-jnp oracle used by the allclose test sweeps) and
``capture.py`` (the per-thread trace-capture hook feeding the benchmark
suite — see ``docs/adding-a-kernel.md``):

- ``flash_attention`` — online-softmax attention (the LM hot-spot; never
  materializes [S, S] scores in HBM; causal tiles skipped).
- ``stream``          — STREAM Copy/Scale/Add/Triad, the DAMOV Class-1a
  bandwidth archetypes; used for the HBM-roof envelope benchmark.
- ``token_gather``    — scalar-prefetch DMA row gather, the TPU-idiomatic
  adaptation of DAMOV's irregular-access classes.
- ``paged_kv_decode`` — one decode step over a vLLM-style paged KV cache:
  scalar-prefetched page table steers the K/V page DMAs, online softmax
  in VMEM scratch.
- ``moe_dispatch``    — fused MoE token dispatch + expert FFN: sorted
  scalar-prefetch routing; the Pallas revisiting optimization keeps each
  expert's weight tile resident across its token run.
- ``ssm_scan``        — chunked selective-state-space scans (gated EMA and
  the Mamba-2-style state-expanded closed form); recurrent state lives in
  VMEM scratch, HBM sees pure chunk streams.
"""

from . import (  # noqa: F401
    flash_attention,
    moe_dispatch,
    paged_kv_decode,
    ssm_scan,
    stream,
    token_gather,
)
