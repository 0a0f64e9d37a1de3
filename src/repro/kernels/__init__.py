"""Pallas TPU kernels (+ jnp oracles) for the perf-critical compute:

  ata_tag_probe   — the paper's aggregated tag array (parallel tag compare)
  ata_probe_rank  — probe + winner pick + port arbitration, fused
  flash_attention — blocked online-softmax attention (GQA/causal/window)
  wkv6            — chunked RWKV6 recurrence with data-dependent decay

Use via ``repro.kernels.ops`` which dispatches pallas / interpret / ref:
``pallas`` is Mosaic-compiled and TPU-only (the probe kernels raise
elsewhere), ``interpret`` runs the same body on any backend.
"""
from repro.kernels import ops, ref  # noqa: F401
