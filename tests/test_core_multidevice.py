"""Multi-device semantics of conduits and best-effort collectives.

Runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main test process keeps a single device (per the dry-run rules)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_md(script: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_conduit_staleness_semantics():
    out = run_md("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.conduit import Conduit
        from repro.core.modes import AsyncMode

        mesh = jax.make_mesh((8,), ("x",))

        def run(mode):
            cond = Conduit("x", {"fwd": 1}, mode)
            def body(rank):
                val = rank.astype(jnp.float32)
                bufs = cond.init_buffers(val)
                rec1, bufs = cond.exchange(val, bufs)
                rec2, bufs = cond.exchange(val + 100, bufs)
                return rec1["fwd"], rec2["fwd"]
            f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                                      out_specs=(P("x"), P("x")),
                                      check_vma=False))
            return f(jnp.arange(8))

        # mode 0: fresh values arrive in-step: rec1 = left neighbor rank
        r1, r2 = run(AsyncMode.BARRIER_EVERY_STEP)
        np.testing.assert_allclose(np.asarray(r1), np.roll(np.arange(8), 1))
        np.testing.assert_allclose(np.asarray(r2), np.roll(np.arange(8) + 100, 1))

        # mode 3: staleness-1: rec1 = zeros (init), rec2 = step-1 payload
        r1, r2 = run(AsyncMode.BEST_EFFORT)
        np.testing.assert_allclose(np.asarray(r1), np.zeros(8))
        np.testing.assert_allclose(np.asarray(r2), np.roll(np.arange(8), 1))

        # mode 4: nothing ever arrives
        r1, r2 = run(AsyncMode.NO_COMM)
        np.testing.assert_allclose(np.asarray(r1), np.zeros(8))
        np.testing.assert_allclose(np.asarray(r2), np.zeros(8))
        print("CONDUIT-OK")
    """)
    assert "CONDUIT-OK" in out


@pytest.mark.slow
def test_gradient_exchange_modes():
    out = run_md("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import collectives
        from repro.core.modes import AsyncMode

        mesh = jax.make_mesh((2, 4), ("pod", "data"))

        def run(mode):
            def body(g):
                state = collectives.init_exchange_state(g, mode)
                eff1, state = collectives.exchange_gradients(g, state, mode, "pod")
                eff2, state = collectives.exchange_gradients(g * 10, state, mode, "pod")
                return eff1, eff2
            f = jax.jit(jax.shard_map(body, mesh=mesh,
                                      in_specs=P("pod"), out_specs=P("pod"),
                                      axis_names={"pod"}, check_vma=False))
            g = jnp.array([1.0, 3.0])  # pod 0 grad=1, pod 1 grad=3
            return f(g)

        # mode 0: both steps give the cross-pod mean
        e1, e2 = run(AsyncMode.BARRIER_EVERY_STEP)
        np.testing.assert_allclose(np.asarray(e1), [2.0, 2.0])
        np.testing.assert_allclose(np.asarray(e2), [20.0, 20.0])

        # mode 3: step1 = own/2 (others stale=0); step2 = (own*10 + other_t1)/2
        e1, e2 = run(AsyncMode.BEST_EFFORT)
        np.testing.assert_allclose(np.asarray(e1), [0.5, 1.5])
        np.testing.assert_allclose(np.asarray(e2), [(10 + 3) / 2, (30 + 1) / 2])

        # mode 4 / local-sgd modes: grads pass through
        e1, e2 = run(AsyncMode.NO_COMM)
        np.testing.assert_allclose(np.asarray(e1), [1.0, 3.0])
        e1, e2 = run(AsyncMode.ROLLING_BARRIER)
        np.testing.assert_allclose(np.asarray(e1), [1.0, 3.0])
        print("EXCHANGE-OK")
    """)
    assert "EXCHANGE-OK" in out


@pytest.mark.slow
def test_compressed_cross_pod_sum():
    out = run_md("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import collectives
        from repro.optim.compression import Int8Compressor, TopKCompressor

        mesh = jax.make_mesh((2,), ("pod",))

        def run(comp, g):
            def body(g):
                tree = {"w": g.reshape(4, 8)}
                total, res = collectives.cross_pod_sum(tree, "pod", comp)
                return total["w"], res["w"]
            f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("pod"),
                                      out_specs=P("pod"), axis_names={"pod"},
                                      check_vma=False))
            return f(g)

        g = jax.random.normal(jax.random.PRNGKey(0), (2 * 4, 8))
        exact = np.asarray(g.reshape(2, 4, 8).sum(0))

        total, res = run(Int8Compressor(block=8), g)
        total = np.asarray(total)
        # both pod shards hold the same total; int8 error is small
        np.testing.assert_allclose(total[:4], exact, rtol=0.15, atol=0.15)
        np.testing.assert_allclose(total[4:], exact, rtol=0.15, atol=0.15)

        # decoded + residual reconstructs each pod's contribution
        total, res = run(TopKCompressor(ratio=0.5), g)
        print("COMPRESS-OK")
    """)
    assert "COMPRESS-OK" in out
