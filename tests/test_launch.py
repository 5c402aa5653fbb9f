"""Launch-layer tests: sharding rules, mesh construction, and a reduced
dry-run on an 8-device debug mesh (subprocess)."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch.sharding import param_specs, with_pod_dim
from repro.models import lm
from repro.models.partitioning import MeshRules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _FakeMesh:
    """Duck-typed mesh for spec-rule tests (axis sizes only)."""
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def _rules(shape={"data": 16, "model": 16}):
    mesh = _FakeMesh(shape)
    return MeshRules.__new__(MeshRules), mesh


def test_param_specs_shard_big_dims():
    cfg = get_config("qwen3-0.6b")
    like = lm.abstract_params(cfg)
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = MeshRules.__new__(MeshRules)
    rules.mesh = mesh
    rules.roles = {"dp": ("data",), "tp": "model", "sp": "model"}
    specs = param_specs(like, rules)
    # embedding (V, d): vocab over model, d over data
    assert specs["embed"] == P("model", ("data",))
    # attention projections in the scanned stack: leading scan dim None
    stack0 = specs["stack"][0]
    assert stack0["mixer"]["wq"] == P(None, ("data",), "model")
    assert stack0["mixer"]["wo"] == P(None, "model", ("data",))
    # norms replicated
    assert specs["final_norm"] == P(None)


def test_param_specs_fall_back_on_indivisible_dims():
    cfg = get_config("xlstm-125m")  # H=4 heads, small dims
    like = lm.abstract_params(cfg)
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = MeshRules.__new__(MeshRules)
    rules.mesh = mesh
    rules.roles = {"dp": ("data",), "tp": "model", "sp": "model"}
    specs = param_specs(like, rules)
    for spec, leaf in zip(jax.tree.leaves(specs,
                                          is_leaf=lambda x: isinstance(x, P)),
                          jax.tree.leaves(like)):
        for dim, axes in zip(leaf.shape, spec):
            if axes is None:
                continue
            n = 1
            for a in (axes if isinstance(axes, tuple) else (axes,)):
                n *= mesh.shape[a]
            assert dim % n == 0, (leaf.shape, spec)


def test_with_pod_dim():
    tree = {"a": P("model"), "b": P(None, ("data",))}
    out = with_pod_dim(tree)
    assert out["a"] == P("pod", "model")
    assert out["b"] == P("pod", None, ("data",))


def test_input_specs_shapes():
    """input_specs covers every model input, spec-compliant shapes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    script = textwrap.dedent("""
        from repro.launch.dryrun import input_specs
        s = input_specs("qwen2.5-3b", "train_4k", multi_pod=True)
        assert s["tokens"].shape == (2, 128, 4096), s["tokens"].shape
        s = input_specs("llava-next-mistral-7b", "prefill_32k")
        assert s["tokens"].shape == (32, 32768)
        assert s["patch_embeds"].shape == (32, 576, 4096)
        s = input_specs("jamba-v0.1-52b", "decode_32k")
        assert s["tokens"].shape == (128, 1)
        assert "caches" in s
        print("SPECS-OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert "SPECS-OK" in r.stdout


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_placement(tmp_path, from_env):
    """An entry point leaves ``JAX_COMPILATION_CACHE_DIR`` to JAX and sets
    no other path; without it the cache lives at one fixed path in the
    checkout."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    script = textwrap.dedent("""
        import jax
        from repro.runtime.experiments import use_compile_cache
        use_compile_cache()
        print(jax.config.jax_compilation_cache_dir)
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.splitlines()[-1] == want


@pytest.mark.slow
def test_reduced_dryrun_on_debug_mesh():
    """Lower+compile a reduced config on a (2,2,2) mesh — validates the
    full dry-run path (pod-stacked train + decode) without 512 devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_config
        from repro.configs.smoke import reduce_for_smoke
        from repro.core.modes import AsyncMode
        from repro.launch import serve as serve_mod, train as train_mod
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.sharding import (param_specs, shardings_from_specs,
                                           with_pod_dim)
        from repro.models import lm, partitioning
        from repro.models.partitioning import MeshRules

        mesh = make_debug_mesh((2, 2, 2), ("pod", "data", "model"))
        rules = MeshRules(mesh, dp=("data",), tp="model")
        cfg = reduce_for_smoke(get_config("deepseek-moe-16b"))
        spec = train_mod.TrainSpec(mode=AsyncMode.BEST_EFFORT)
        with partitioning.use_rules(rules):
            state_like = train_mod.abstract_train_state(cfg, spec, 2)
            pspecs = with_pod_dim(param_specs(lm.abstract_params(cfg), rules))
            s_specs = {"params": pspecs,
                       "opt": {"m": pspecs, "v": pspecs, "step": P("pod")},
                       "others": pspecs, "step": P()}
            batch = {
                "tokens": jax.ShapeDtypeStruct((2, 4, 32), jnp.int32),
                "labels": jax.ShapeDtypeStruct((2, 4, 32), jnp.int32),
            }
            b_specs = {"tokens": P("pod", "data", None),
                       "labels": P("pod", "data", None)}
            fn = train_mod.make_train_step(cfg, spec, 2)
            lowered = jax.jit(
                fn,
                in_shardings=(shardings_from_specs(s_specs, mesh),
                              shardings_from_specs(b_specs, mesh)),
            ).lower(state_like, batch)
            compiled = lowered.compile()
            ca = compiled.cost_analysis()
            assert ca.get("flops", 0) > 0
        print("DRYRUN-SMALL-OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=540)
    assert r.returncode == 0, f"STDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    assert "DRYRUN-SMALL-OK" in r.stdout
