"""Public jit'd entry points for the kernel package.

Each op dispatches between implementations:
  "pallas"    — the Pallas TPU kernel compiled by Mosaic (TPU only)
  "interpret" — the same kernel body interpreted (any backend)
  "ref"       — the pure-jnp oracle (always available, used for dry-run
                lowering and as the XLA fast path on non-TPU backends)

Models call these ops; the per-arch config picks the implementation so
the dry-run lowers pure-XLA while TPU deployments take the kernels.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.ata_probe_rank import ata_probe_rank as _probe_rank_kernel
from repro.kernels.ata_tag_probe import ata_tag_probe as _probe_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.wkv6 import wkv6 as _wkv6_kernel

IMPLS = ("ref", "interpret", "pallas")


def ata_probe(set_idx, qtag, tags, valid, *, impl: str = "ref", **kw):
    if impl == "ref":
        return _ref.ata_tag_probe_ref(set_idx, qtag, tags, valid)
    return _probe_kernel(set_idx, qtag, tags, valid,
                         interpret=(impl == "interpret"), **kw)


def ata_probe_rank(set_idx, qtag, core, cluster_base, deny, tags, valid,
                   dirty, *, cluster_size: int, impl: str = "ref", **kw):
    """Fused probe + winner pick + remote-port arbitration (one pass)."""
    if impl == "ref":
        return _ref.ata_probe_rank_ref(set_idx, qtag, core, cluster_base,
                                       deny, tags, valid, dirty,
                                       cluster_size=cluster_size)
    return _probe_rank_kernel(set_idx, qtag, core, cluster_base, deny,
                              tags, valid, dirty,
                              cluster_size=cluster_size,
                              interpret=(impl == "interpret"), **kw)


def attention(q, k, v, kv_len=None, *, causal=True, window=None,
              impl: str = "ref", **kw):
    if impl == "ref":
        if kv_len is not None:
            # fold valid-length into a window-style mask via ref path
            return _ref.attention_len_ref(q, k, v, kv_len, causal=causal,
                                          window=window)
        return _ref.attention_ref(q, k, v, causal=causal, window=window)
    return _flash_kernel(q, k, v, kv_len, causal=causal, window=window,
                         interpret=(impl == "interpret"), **kw)


def wkv6(r, k, v, w, u, initial_state=None, *, impl: str = "ref", **kw):
    if impl == "ref":
        return _ref.wkv6_ref(r, k, v, w, u, initial_state=initial_state)
    return _wkv6_kernel(r, k, v, w, u, initial_state,
                        interpret=(impl == "interpret"), **kw)
