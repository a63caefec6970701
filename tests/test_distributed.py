"""Multi-device tests (8 fake CPU devices in a subprocess so the main
test process keeps its single-device view).

Each test writes a small driver script, runs it with
XLA_FLAGS=--xla_force_host_platform_device_count=8, and checks output.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_driver(code: str, timeout: int = 420, min_devices: int = 8) -> str:
    """Run a driver script under a forced-8-CPU-device jax.  When the
    platform ignores the forcing (e.g. an already-initialized accelerator
    backend exposes a single device), the test skips with a reason rather
    than failing on mesh construction."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    preamble = (
        "import jax\n"
        f"if jax.device_count() < {min_devices}:\n"
        f"    print('SKIP: only', jax.device_count(), 'device(s) available,'\n"
        f"          ' need {min_devices}')\n"
        "    raise SystemExit(0)\n"
    )
    out = subprocess.run([sys.executable, "-c", preamble + textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=timeout)
    assert out.returncode == 0, f"driver failed:\n{out.stdout}\n{out.stderr}"
    if out.stdout.startswith("SKIP:"):
        pytest.skip(out.stdout.strip())
    return out.stdout


def test_dp_tp_train_step_matches_single_device():
    """A sharded train step must produce the same loss as single-device."""
    out = run_driver("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import configs
        from repro.models.build import build_model, make_batch
        from repro.parallel import sharding as shd
        from repro.optim import adamw

        cfg = configs.get('llama3-8b').scaled()
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        batch = make_batch(cfg, 'train', 8, 32)

        def loss_of(p, b):
            return m.loss(p, b, remat=False)[0]

        ref = float(jax.jit(loss_of)(params, batch))

        # the sharding rules are GSPMD annotations: auto axes, which
        # jax.make_mesh no longer defaults to
        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        sizes = dict(mesh.shape)
        pspecs = shd.param_specs(params, sizes)
        bspecs = shd.batch_specs(batch, ('data',), sizes)
        with mesh:
            to_sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                           is_leaf=lambda x: isinstance(x, P))
            p_sh = jax.device_put(params, to_sh(pspecs))
            b_sh = jax.device_put(batch, to_sh(bspecs))
            got = float(jax.jit(loss_of)(p_sh, b_sh))
        np.testing.assert_allclose(got, ref, rtol=2e-4)
        print('OK', ref, got)
    """)
    assert "OK" in out


def test_embed_lookup_under_explicit_sharding():
    """Under explicit mesh axes the embedding gather names its output
    sharding: the looked-up rows follow the ids, not the table."""
    out = run_driver("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.nn.core import embed_lookup

        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Explicit,) * 2)
        table = np.arange(64 * 8, dtype=np.float32).reshape(64, 8)
        ids = np.random.default_rng(0).integers(0, 64, (8, 5)).astype(np.int32)
        with jax.set_mesh(mesh):
            t_sh = jax.device_put(table, NamedSharding(mesh, P('model', None)))
            i_sh = jax.device_put(ids, NamedSharding(mesh, P('data', None)))
            got = jax.jit(embed_lookup)(t_sh, i_sh)
        assert got.sharding.spec == P('data', None, None), got.sharding
        np.testing.assert_array_equal(np.asarray(got), table[ids])
        print('OK')
    """)
    assert "OK" in out


def test_zero1_matches_adamw():
    out = run_driver("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.optim import adamw, zero1

        cfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=0, weight_decay=0.01)
        params = {'w': jnp.asarray(np.random.RandomState(0).randn(33, 7), jnp.float32),
                  'b': jnp.asarray(np.random.RandomState(1).randn(13), jnp.float32)}
        grads = {'w': jnp.asarray(np.random.RandomState(2).randn(33, 7), jnp.float32),
                 'b': jnp.asarray(np.random.RandomState(3).randn(13), jnp.float32)}

        ref_p, ref_s, _ = adamw.apply_updates(params, grads, adamw.init_state(params), cfg)

        mesh = jax.make_mesh((8,), ('data',))
        z_state = zero1.zero1_init_state(params, 8)
        upd = jax.shard_map(
            partial(zero1.zero1_update, cfg=cfg, axis='data'),
            mesh=mesh,
            in_specs=(P(), P(), {'m': P('data'), 'v': P('data'), 'step': P()}),
            out_specs=(P(), {'m': P('data'), 'v': P('data'), 'step': P()}, P()),
            check_vma=False)
        new_p, new_s, info = jax.jit(upd)(params, grads, z_state)
        for k in params:
            np.testing.assert_allclose(np.asarray(new_p[k]), np.asarray(ref_p[k]), rtol=1e-5, atol=1e-6)
        print('OK zero1')
    """)
    assert "OK zero1" in out


def test_collective_matmul_matches_baseline():
    out = run_driver("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.parallel.collective_matmul import (
            ring_allgather_matmul, ring_matmul_reduce_scatter)

        mesh = jax.make_mesh((8,), ('model',))
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(64, 32), jnp.float32)
        w = jnp.asarray(rng.randn(32, 48), jnp.float32)

        # all-gather overlap: x rows sharded, w columns sharded
        ag = jax.shard_map(partial(ring_allgather_matmul, axis='model'), mesh=mesh,
                       in_specs=(P('model', None), P(None, 'model')),
                       out_specs=P(None, 'model'), check_vma=False)
        got = jax.jit(ag)(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w), rtol=1e-4, atol=1e-4)

        # reduce-scatter overlap: x sharded on K, w rows sharded
        rs = jax.shard_map(partial(ring_matmul_reduce_scatter, axis='model'), mesh=mesh,
                       in_specs=(P(None, 'model'), P('model', None)),
                       out_specs=P(None, 'model'), check_vma=False)
        got2 = jax.jit(rs)(x, w)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(x @ w), rtol=1e-4, atol=1e-4)
        print('OK collective matmul')
    """)
    assert "OK collective matmul" in out


def test_sp_decode_attention_matches_full():
    out = run_driver("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.parallel.sp_attention import sp_decode_attention, full_decode_attention_ref

        mesh = jax.make_mesh((8,), ('data',))
        rng = np.random.RandomState(0)
        B, S, H, D = 2, 64, 4, 16
        q = jnp.asarray(rng.randn(B, H, D) * 0.5, jnp.float32)
        k = jnp.asarray(rng.randn(B, S, H, D) * 0.5, jnp.float32)
        v = jnp.asarray(rng.randn(B, S, H, D) * 0.5, jnp.float32)
        valid = jnp.asarray([S, S // 2], jnp.int32)
        scale = 1.0 / np.sqrt(D)

        def sharded(q, k, v, valid):
            s_loc = k.shape[1]
            start = jax.lax.axis_index('data') * s_loc
            vl = jnp.clip(valid - start, 0, s_loc)
            return sp_decode_attention(q, k, v, vl, scale, axis='data')

        fn = jax.shard_map(sharded, mesh=mesh,
                       in_specs=(P(), P(None, 'data'), P(None, 'data'), P()),
                       out_specs=P(), check_vma=False)
        got = jax.jit(fn)(q, k, v, valid)
        want = full_decode_attention_ref(q, k, v, valid, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
        print('OK sp attention')
    """)
    assert "OK sp attention" in out


def test_pipeline_parallel_matches_sequential():
    out = run_driver("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.parallel.pipeline import pipeline_apply, bubble_fraction

        S, M, mb, d = 8, 4, 2, 16   # 8 stages, 4 microbatches
        mesh = jax.make_mesh((8,), ('pod',))
        rng = np.random.RandomState(0)
        ws = jnp.asarray(rng.randn(S, d, d) * 0.3, jnp.float32)
        x = jnp.asarray(rng.randn(M, mb, d), jnp.float32)

        def stage(w, h):
            return jnp.tanh(h @ w)

        def run(ws_shard, micro):
            return pipeline_apply(stage, ws_shard[0], micro, axis='pod')

        fn = jax.shard_map(run, mesh=mesh, in_specs=(P('pod'), P()), out_specs=P(), check_vma=False)
        outs = jax.jit(fn)(ws, x)

        want = x
        for i in range(S):
            want = jnp.tanh(want @ ws[i])
        np.testing.assert_allclose(np.asarray(outs), np.asarray(want), rtol=1e-4, atol=1e-5)
        assert abs(bubble_fraction(8, 4) - 7/11) < 1e-9
        print('OK pipeline')
    """)
    assert "OK pipeline" in out


def test_compressed_psum_error_feedback():
    out = run_driver("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.optim.compress import compressed_psum, compression_ratio

        mesh = jax.make_mesh((8,), ('data',))
        rng = np.random.RandomState(0)
        g = jnp.asarray(rng.randn(8, 256) * 0.1, jnp.float32)

        def step(g_shard, res):
            return compressed_psum(g_shard, 'data', res)

        fn = jax.shard_map(step, mesh=mesh, in_specs=(P('data'), P('data')),
                       out_specs=(P('data'), P('data')), check_vma=False)
        res = jnp.zeros_like(g)
        out1, res = jax.jit(fn)(g, res)
        want = jnp.broadcast_to(jnp.sum(g, 0, keepdims=True), g.shape)
        err1 = float(jnp.max(jnp.abs(out1 - want)))
        # error feedback: with the residual applied, a second identical
        # round reduces the bias of the *sum over rounds*
        out2, res2 = jax.jit(fn)(g, res)
        two_round = np.asarray(out1 + out2)
        want2 = np.asarray(2 * want)
        err2 = float(np.max(np.abs(two_round - want2)))
        assert err1 < 0.05, err1
        assert err2 <= 2 * err1 + 1e-6
        assert compression_ratio((1024, 1024)) > 3.5
        print('OK compress', err1, err2)
    """)
    assert "OK compress" in out
