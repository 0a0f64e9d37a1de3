"""Sharding-rule coverage and multi-device integration (subprocess)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.sharding.rules import make_rules, param_axes

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_axes_cover_every_full_config_param(arch):
    """Every parameter of every *full* config resolves to axis rules of
    the right rank (eval_shape: no allocation)."""
    from repro.models import transformer as T
    cfg = get_config(arch)
    params = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    axes = param_axes(params)       # raises if any param is uncovered
    flat_p = jax.tree.leaves(params)
    flat_a = jax.tree.leaves(axes, is_leaf=lambda a: isinstance(a, tuple))
    assert len(flat_p) == len(flat_a)
    for p, a in zip(flat_p, flat_a):
        assert p.ndim == len(a)


def _run_subprocess(code: str):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=900)


def test_sharded_train_step_runs_on_8_devices():
    r = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch import specs as SP
        from repro.launch.mesh import make_test_mesh
        from repro.optim.adamw import AdamWConfig
        from repro.sharding.rules import make_rules, rules_context
        from repro.train.step import init_train_state, make_train_step
        cfg = get_smoke_config("qwen3-0.6b")
        mesh = make_test_mesh(4, 2)
        rules = make_rules(cfg, mesh, batch_size=8)
        with rules_context(mesh, rules), jax.set_mesh(mesh):
            state = init_train_state(jax.random.PRNGKey(0), cfg)
            st_sh = SP.train_state_shardings(
                jax.eval_shape(lambda: state), cfg, mesh, rules)
            state = jax.device_put(state, st_sh)
            step = jax.jit(make_train_step(cfg, AdamWConfig()),
                           in_shardings=(st_sh, None),
                           out_shardings=(st_sh, None))
            tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 64),
                                        0, cfg.vocab_size)
            state, m = step(state, {"tokens": tokens, "labels": tokens})
            assert np.isfinite(float(m["loss"]))
        print("SHARDED_OK", float(m["loss"]))
    """)
    assert "SHARDED_OK" in r.stdout, r.stdout + r.stderr


def test_dp_profile_matches_tp_profile_loss():
    """Same step, two parallelism profiles -> same loss (numerics)."""
    r = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch import specs as SP
        from repro.launch.mesh import make_test_mesh
        from repro.optim.adamw import AdamWConfig
        from repro.sharding.rules import make_rules, rules_context
        from repro.train.step import init_train_state, make_train_step
        cfg = get_smoke_config("qwen3-0.6b")
        mesh = make_test_mesh(4, 2)
        losses = []
        for profile in ("tp", "dp"):
            rules = make_rules(cfg, mesh, batch_size=8, profile=profile)
            with rules_context(mesh, rules), jax.set_mesh(mesh):
                state = init_train_state(jax.random.PRNGKey(0), cfg)
                st_sh = SP.train_state_shardings(
                    jax.eval_shape(lambda: state), cfg, mesh, rules)
                state = jax.device_put(state, st_sh)
                step = jax.jit(make_train_step(cfg, AdamWConfig()),
                               in_shardings=(st_sh, None),
                               out_shardings=(st_sh, None))
                tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32),
                                            0, cfg.vocab_size)
                _, m = step(state, {"tokens": tokens, "labels": tokens})
                losses.append(float(m["loss"]))
        assert abs(losses[0] - losses[1]) < 1e-3, losses
        print("PROFILES_OK", losses)
    """)
    assert "PROFILES_OK" in r.stdout, r.stdout + r.stderr


def test_compressed_allreduce_on_8_devices():
    """int8 error-feedback all-reduce inside shard_map: mean preserved
    within quantization tolerance and error buffers carry the residual."""
    r = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import all_reduce_compressed
        from jax import shard_map
        from repro.sharding.compat import make_mesh
        mesh = make_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        e = jnp.zeros((8, 64))
        @partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                 out_specs=(P("data"), P("data")))
        def f(gs, es):
            r, ne = all_reduce_compressed(gs, es, "data")
            return r, ne
        red, nerr = f(g, e)
        exact = jnp.broadcast_to(g.mean(0, keepdims=True), g.shape)
        err = float(jnp.abs(red - exact).max())
        scale = float(jnp.abs(g).max()) / 127.0
        assert err < 2 * scale, (err, scale)
        print("COMPRESS_OK", err)
    """)
    assert "COMPRESS_OK" in r.stdout, r.stdout + r.stderr


def test_dryrun_cell_results_exist_and_fit():
    """The committed dry-run artifacts cover all 40x2 cells."""
    d = os.path.join(ROOT, "results", "dryrun")
    if not os.path.isdir(d) or not os.listdir(d):
        pytest.skip("dry-run artifacts not generated yet")
    cells = [json.load(open(os.path.join(d, f))) for f in os.listdir(d)
             if f.endswith(".json")]
    assert len(cells) == 80
    bad = [c for c in cells if c["status"] not in ("ok", "skipped")]
    assert not bad, [(c['arch'], c['shape']) for c in bad]
    skips = [c for c in cells if c["status"] == "skipped"]
    assert len(skips) == 16      # long_500k x 8 full-attention archs x 2
