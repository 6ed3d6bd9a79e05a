"""Distributed-path tests on 8 FAKE host devices, run in subprocesses so the
main pytest process keeps its single real device (dry-run rule: only
subprocesses fake device counts).

Covers: shard_map hierarchical gradient sync (fp32 / bf16 / int8-stochastic
cross-pod compression), the distributed CHESSFAD L0/L1 schedules, and a
(2,2,2) multi-pod shard_map train step."""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_with_fake_devices(body: str, n: int = 8) -> str:
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={n} "
            + os.environ.get("XLA_FLAGS", ""))
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
    return out.stdout


def test_hierarchical_grad_sync_compression():
    run_with_fake_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.parallel.collectives import hierarchical_grad_sync

        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        rng = np.random.RandomState(0)
        g = jnp.asarray(rng.randn(8, 64), jnp.float32)

        def sync(method):
            @partial(shard_map, mesh=mesh, in_specs=P(("pod", "data")),
                     out_specs=P(("pod", "data")), check_vma=False)
            def run(blk):
                return hierarchical_grad_sync(
                    {"g": blk}, data_axis="data", pod_axis="pod",
                    key=jax.random.PRNGKey(0), method=method)["g"]
            return np.asarray(run(g))

        exact = sync("none")
        want = np.broadcast_to(np.asarray(g).mean(0, keepdims=True),
                               g.shape)
        np.testing.assert_allclose(exact, want, rtol=1e-5, atol=1e-6)
        bf16 = sync("bf16")
        np.testing.assert_allclose(bf16, exact, rtol=2e-2, atol=2e-2)
        q8 = sync("int8")
        np.testing.assert_allclose(q8, exact, rtol=0.15,
                                   atol=0.1 * np.abs(exact).max())
        print("SYNC_OK")
    """)


def test_int8_quantization_unbiased():
    run_with_fake_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.collectives import quantize_int8, dequantize_int8
        x = jnp.linspace(-3.0, 3.0, 64)
        outs = []
        for i in range(512):
            q, s = quantize_int8(x, jax.random.PRNGKey(i))
            outs.append(np.asarray(dequantize_int8(q, s)))
        mean = np.stack(outs).mean(0)
        np.testing.assert_allclose(mean, np.asarray(x), atol=6e-3)
        print("UNBIASED_OK")
    """, n=1)


def test_distributed_chessfad_hvp():
    run_with_fake_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import (distributed_batched_hvp,
                                            distributed_hvp_rows)
        from repro.core import testfns, ref

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        n, m, csize = 8, 16, 2
        f = testfns.rosenbrock
        rng = np.random.RandomState(0)
        A = jnp.asarray(rng.uniform(-2, 2, (m, n)), jnp.float32)
        V = jnp.asarray(rng.randn(m, n), jnp.float32)
        out = distributed_batched_hvp(mesh, f, A, V, csize=csize)
        want = jnp.stack([ref.hvp_fwdrev(f, A[i], V[i]) for i in range(m)])
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)
        r = distributed_hvp_rows(mesh, f, A[0], V[0], csize=csize)
        np.testing.assert_allclose(np.asarray(r), np.asarray(want[0]),
                                   rtol=2e-3, atol=2e-3)
        print("DIST_HVP_OK")
    """)


def test_multipod_shard_map_train_step():
    run_with_fake_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models.params import init_params
        from repro.models.model import make_batch
        from repro.optim import adamw
        from repro.optim.schedule import constant
        from repro.training import TrainState
        from repro.training.steps import make_shard_map_train_step

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_config("minitron-4b", reduced=True)
        opt = adamw(constant(1e-3))
        params = init_params(cfg, jax.random.PRNGKey(0))
        state = TrainState(params, opt.init(params),
                           jnp.zeros((), jnp.int32), jax.random.PRNGKey(1))
        step = make_shard_map_train_step(cfg, mesh, opt, compress="bf16")
        batch = make_batch(cfg, 8, 16)
        losses = []
        for i in range(3):
            state, m = step(state, make_batch(cfg, 8, 16,
                                              jax.random.PRNGKey(i)))
            loss = float(m["loss"])
            assert loss == loss
            losses.append(loss)
        print("MULTIPOD_OK", losses)
    """)


def test_gspmd_train_step_on_2d_mesh():
    run_with_fake_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models.params import init_params, param_specs
        from repro.models.model import make_batch
        from repro.optim import adamw
        from repro.optim.schedule import constant
        from repro.training import TrainState, make_train_step

        from repro.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("granite-moe-1b-a400m", reduced=True)
        opt = adamw(constant(1e-3))
        params = init_params(cfg, jax.random.PRNGKey(0))
        specs = param_specs(cfg, mesh)
        params = jax.tree.map(
            lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
            params, specs)
        state = TrainState(params, opt.init(params),
                           jnp.zeros((), jnp.int32), jax.random.PRNGKey(1))
        step = make_train_step(cfg, mesh, opt)
        batch = make_batch(cfg, 4, 32)
        batch = jax.device_put(batch, NamedSharding(mesh, P("data")))
        state, m = step(state, batch)
        assert float(m["loss"]) == float(m["loss"])
        print("GSPMD_OK", float(m["loss"]))
    """)
