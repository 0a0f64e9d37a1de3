"""The Pallas probe kernels compile for a TPU v5e at the main path's shapes.

No chip is needed: the installed TPU compiler compiles for a described
``v5e:2x2`` topology, and refuses what Mosaic cannot lower (misaligned
blocks, unsupported reductions or shape casts, too much VMEM). Shapes
are the real ones: ``ata_probe_rank`` on the paper geometry (30 caches
of 8 sets x 64 ways, clusters of 10) with one round's requests of the
paper apps, padded tail included, alone and vmapped as the sweep runs
it; ``ata_tag_probe`` on the serving engine's 8- and 16-shard
directories (64 sets x 8 ways) with a round's request blocks.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and only the worker that runs these
tests does. The persistent compilation cache is off around the
compiles — it could store them but never read them back without a chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import PAPER_GEOMETRY
from repro.core.trace.serving import ServingMix
from repro.kernels import ata_probe_rank as probe_rank_mod
from repro.kernels import ata_tag_probe as tag_probe_mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(one_chip, monkeypatch):
    """Compile ``fn`` at the given shapes for one described v5e chip.

    The kernels' TPU guard reads the process's default backend (the
    CPU here); the compile itself targets the described chip, so the
    guard is lifted for the test. The persistent cache is off.
    """
    from jax.experimental.compilation_cache import compilation_cache
    for mod in (tag_probe_mod, probe_rank_mod):
        monkeypatch.setattr(mod, "require_tpu", lambda kernel: None)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    yield compile_
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _probe_rank_shapes(R, batch=()):
    g = PAPER_GEOMETRY
    state = (batch + (g.n_cores, g.l1_sets, g.l1_ways), jnp.int32)
    req = (batch + (R,), jnp.int32)
    return [req, req, req, req, (batch + (R,), jnp.bool_),
            state, (state[0], jnp.bool_), (state[0], jnp.bool_)]


def _probe_rank(*args):
    return probe_rank_mod.ata_probe_rank(
        *args, cluster_size=PAPER_GEOMETRY.cluster_size)


@pytest.mark.parametrize("m", [2, 4, 5], ids=["cfd", "m4", "padded"])
def test_probe_rank_compiles_on_paper_geometry(compile_for_chip, m):
    # R = 30 cores x m requests per round: 60 (cfd, b+tree), 120 (the
    # other apps), 150 (two 128-request tiles, dead-lane padded)
    compile_for_chip(_probe_rank,
                     *_probe_rank_shapes(PAPER_GEOMETRY.n_cores * m))


def test_probe_rank_compiles_vmapped_like_the_sweep(compile_for_chip):
    R = PAPER_GEOMETRY.n_cores * 4
    compile_for_chip(jax.vmap(_probe_rank),
                     *_probe_rank_shapes(R, batch=(4,)))


@pytest.mark.parametrize("shards", [8, 16])
@pytest.mark.parametrize("mix", [("chat", "rag"), ("chat", "batch")],
                         ids=["chat+rag", "chat+batch"])
def test_tag_probe_compiles_on_serving_directories(compile_for_chip,
                                                   shards, mix):
    K = ServingMix(mix).make_stream(n_shards=shards, rounds=8) \
        .hashes.shape[2]
    R = shards * K            # one sub-round: every shard's block chain
    directory = ((shards, 64, 8), jnp.int32)

    def probe(set_idx, qtag, tags):
        return tag_probe_mod.ata_tag_probe(set_idx, qtag, tags, tags != 0,
                                           br=R)

    compile_for_chip(probe, ((R,), jnp.int32), ((R,), jnp.int32),
                     directory)
