"""Distributed-feature tests on placeholder devices (subprocess-isolated:
the main test process must keep seeing exactly 1 CPU device)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _run_with_devices(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


# -- gradient compression (runs single-device: math-only tests) ---------------

def test_int8_quantize_roundtrip():
    from repro.dist.compression import int8_dequantize, int8_quantize

    g = jax.random.normal(jax.random.PRNGKey(0), (256, 64)) * 0.01
    q, scale = int8_quantize(g)
    back = int8_dequantize(q, scale)
    # max quantization error is scale/2 per element (round-to-nearest)
    assert float(jnp.max(jnp.abs(back - g))) <= float(scale) * 0.51


def test_topk_error_feedback_conserves_mass():
    from repro.dist.compression import TopKEF

    grads = {"w": jax.random.normal(jax.random.PRNGKey(1), (128,))}
    err = TopKEF.init(grads)
    sparse, new_err = TopKEF.compress(grads, err, k_fraction=0.1)
    # sent + residual == original
    np.testing.assert_allclose(
        np.asarray(sparse["w"] + new_err["w"]), np.asarray(grads["w"]), rtol=1e-6
    )
    nnz = int(jnp.sum(sparse["w"] != 0))
    assert nnz == max(1, int(128 * 0.1))
    # second round: residual re-enters
    sparse2, err2 = TopKEF.compress(jax.tree.map(jnp.zeros_like, grads), new_err, 0.1)
    np.testing.assert_allclose(
        np.asarray(sparse2["w"] + err2["w"]), np.asarray(new_err["w"]), rtol=1e-6
    )


def test_int8_psum_multidevice():
    out = _run_with_devices(
        """
        import jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.dist.compression import int8_psum
        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        @partial(jax.shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
        def reduce(g):
            return int8_psum(g, "data")[None]
        g = jnp.arange(8.0)[:, None] * jnp.ones((8, 16)) * 0.01
        got = reduce(g.reshape(8, 16))
        expect = jnp.mean(g.reshape(8,16), axis=0)
        err = float(jnp.max(jnp.abs(got - expect[None])))
        assert err < 0.01 * 0.5, err  # within quantization error
        print("INT8_PSUM_OK", err)
        """
    )
    assert "INT8_PSUM_OK" in out


# -- pipeline parallelism ------------------------------------------------------

def test_gpipe_pipeline_matches_sequential():
    out = _run_with_devices(
        """
        import jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.dist.pipeline import pipeline_forward
        S = 4  # stages
        mesh = jax.make_mesh((S,), ("stage",), axis_types=(jax.sharding.AxisType.Auto,))
        key = jax.random.PRNGKey(0)
        # per-stage affine layer
        ws = jax.random.normal(key, (S, 16, 16)) * 0.3
        bs = jax.random.normal(jax.random.fold_in(key, 1), (S, 16)) * 0.1
        def stage_fn(params, x):
            w, b = params
            return jnp.tanh(x @ w[0] + b[0])
        M, mb, d = 8, 4, 16
        x = jax.random.normal(jax.random.fold_in(key, 2), (M, mb, d))
        @partial(jax.shard_map, mesh=mesh,
                 in_specs=((P("stage"), P("stage")), P(None)),
                 out_specs=P(None))
        def run(params, microbatches):
            return pipeline_forward(stage_fn, params, microbatches, S, "stage")
        got = run((ws, bs), x)
        # sequential reference
        y = x
        for s in range(S):
            y = jnp.tanh(y @ ws[s] + bs[s])
        import numpy as np
        np.testing.assert_allclose(np.asarray(got), np.asarray(y), rtol=1e-5, atol=1e-5)
        print("PIPELINE_OK")
        """,
        n_devices=4,
    )
    assert "PIPELINE_OK" in out


# -- sharding rules ------------------------------------------------------------

def test_sharding_rules_divisibility_and_coverage():
    out = _run_with_devices(
        """
        import jax
        from repro.configs import get_config, ARCHS
        from repro.dist import sharding as shd
        from repro.models import lm_init
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
        for arch in ARCHS:
            cfg = get_config(arch)
            shapes = jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), cfg))
            shardings = shd.params_shardings(mesh, shapes)
            import jax.tree_util as jtu
            n_sharded = 0
            for (path, leaf), (_, s) in zip(jtu.tree_leaves_with_path(shapes),
                                            jtu.tree_leaves_with_path(shardings)):
                spec = s.spec
                # every sharded dim must divide evenly
                for dim, axes in zip(leaf.shape, tuple(spec) + (None,) * 10):
                    if axes is None: continue
                    ax = (axes,) if isinstance(axes, str) else axes
                    size = 1
                    for a in ax: size *= mesh.shape[a]
                    assert dim % size == 0, (arch, jtu.keystr(path), leaf.shape, spec)
                    n_sharded += 1
            assert n_sharded > 0, arch
        print("SHARDING_RULES_OK")
        """,
        n_devices=8,
    )
    assert "SHARDING_RULES_OK" in out


def test_small_mesh_e2e_train_step_matches_single_device():
    """Numerical equivalence: 8-device FSDP x TP train step == 1-device."""
    out = _run_with_devices(
        """
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_smoke_config
        from repro.dist import sharding as shd
        from repro.dist.train import make_train_step, with_act_sharding
        from repro.models import lm_init
        from repro.optim import adamw
        cfg = get_smoke_config("yi-34b")
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
        key = jax.random.PRNGKey(0)
        params = lm_init(key, cfg)
        opt = adamw.init(params)
        batch = {
            "tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab),
            "labels": jax.random.randint(jax.random.fold_in(key, 1), (4, 32), 0, cfg.vocab),
        }
        opt_cfg = adamw.AdamWConfig()
        # single-device
        p1, o1, s1 = jax.jit(make_train_step(cfg, opt_cfg))(params, opt, batch)
        # meshed
        cfg2 = with_act_sharding(cfg, mesh)
        ps = shd.params_shardings(mesh, params)
        os_ = shd.opt_state_shardings(mesh, opt)
        bs = shd.batch_shardings(mesh, batch)
        with mesh:
            pp = jax.device_put(params, ps)
            oo = jax.device_put(opt, os_)
            bb = jax.device_put(batch, bs)
            p2, o2, s2 = jax.jit(make_train_step(cfg2, opt_cfg))(pp, oo, bb)
        np.testing.assert_allclose(float(s1["loss"]), float(s2["loss"]), rtol=2e-4)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                       rtol=5e-3, atol=5e-3)
        print("MESH_EQUIV_OK", float(s1["loss"]), float(s2["loss"]))
        """,
        n_devices=8,
        timeout=900,
    )
    assert "MESH_EQUIV_OK" in out


def test_launch_train_mesh_matches_one_device():
    """The ``--mesh`` driver path, with its state built on the mesh, gives
    the losses of the unsharded path."""
    out = _run_with_devices(
        """
        import numpy as np
        from repro.configs import get_smoke_config
        from repro.launch import train as lt
        cfg = get_smoke_config("mamba2-370m")
        kw = dict(steps=3, global_batch=4, seq_len=32, seed=0, log_every=100)
        sharded = lt.train(cfg, use_mesh=True, **kw)
        assert sharded["topology"]["mesh_shape"] == [1, 4], sharded["topology"]
        single = lt.train(cfg, use_mesh=False, **kw)
        np.testing.assert_allclose(sharded["losses"], single["losses"], rtol=1e-3)
        print("LAUNCH_MESH_OK", sharded["losses"], single["losses"])
        """,
        n_devices=4,
    )
    assert "LAUNCH_MESH_OK" in out


def test_launch_mesh_needs_more_than_one_device():
    from repro.configs import get_smoke_config
    from repro.core.topology import ProcessTopology
    from repro.launch.mesh import elastic_setup

    with pytest.raises(ValueError, match="more than one visible device"):
        elastic_setup(get_smoke_config("mamba2-370m"), ProcessTopology(), use_mesh=True)


# -- straggler watchdog ---------------------------------------------------------

def test_straggler_watchdog_flags_and_mitigates():
    from repro.dist.straggler import StragglerConfig, StragglerWatchdog

    events = []
    wd = StragglerWatchdog(
        StragglerConfig(window=16, threshold=1.5, evict_after=3, min_samples=4),
        on_straggler=events.append,
    )
    for i in range(10):
        assert not wd.observe(i, 0.1)
    flagged = [wd.observe(10 + i, 0.5) for i in range(3)]
    assert all(flagged)
    assert wd.mitigations == 1 and len(events) == 1
    assert events[0]["ratio"] > 1.5
    summary = wd.summary()
    assert summary["flags"] == 3
