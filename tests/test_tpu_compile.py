"""Ahead-of-time TPU compiles of the duct kernels on the engine's main path.

``duct_window_kernel`` (every window of the dense layout) and
``duct_commit_kernel`` (every W-fused superstep) are lowered and compiled
by the TPU compiler for one chip of a described ``v5e:2x2`` host, at the
ring widths of a 2^18-process population: the torus bucket (d=4), the
16-row padded bucket of a smallworld graph and the 9-row cliques bucket,
with int32 (graphcolor) and float32 (evo) payloads.  Nothing
runs.  A pass shows what interpret-mode tests cannot: Mosaic lowers the
kernels, their blocks fit the scoped VMEM limit, the program fits the
chip's HBM, and the kernel is still a Pallas custom call.

Each test takes the engine's flat carry shapes and reshapes them into
receiver slabs the way ``WindowCore.window_dense`` does, so the compiled
program is the one the engine runs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.duct_exchange.kernel import (duct_commit_kernel,
                                                duct_window_kernel)

#: processes of the population whose widths are compiled
N = 2 ** 18
#: SimConfig.buffer_capacity default
C = 64
#: superstep width of the fused scheduler
W = 8
#: one v5e chip's HBM
HBM_BYTES = 16 * 2 ** 30

#: (receivers, rows per receiver, payload dtype, payload words L) of one
#: dense bucket at N processes; graphcolor sends int32 words and evo
#: float32 ones, one word at one simulation element per process and an
#: 8-word edge row at 64
WINDOW_CASES = [
    pytest.param(N, 4, jnp.int32, 1, id="torus-d4-graphcolor"),
    pytest.param(N, 4, jnp.float32, 1, id="torus-d4-evo"),
    pytest.param(N, 4, jnp.float32, 8, id="torus-d4-evo-L8"),
    # plan_layout puts ~32% of a smallworld population in its 16-row bucket
    pytest.param(84560, 16, jnp.int32, 1, id="smallworld-d16-graphcolor"),
    pytest.param(84560, 16, jnp.float32, 1, id="smallworld-d16-evo"),
    pytest.param(N, 9, jnp.int32, 1, id="cliques-d9-graphcolor"),
    pytest.param(N, 9, jnp.float32, 1, id="cliques-d9-evo"),
]
#: (rings, payload dtype, payload words L) of the whole population
COMMIT_CASES = [
    pytest.param(4 * N, jnp.int32, 1, id="torus-d4-graphcolor"),
    pytest.param(4 * N, jnp.float32, 8, id="torus-d4-evo-L8"),
    pytest.param(9 * N, jnp.int32, 1, id="cliques-d9-graphcolor"),
    pytest.param(9 * N, jnp.float32, 1, id="cliques-d9-evo"),
]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it cannot describe one
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without the chip
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


@pytest.mark.parametrize("nb,d,pdt,L", WINDOW_CASES)
def test_duct_window_compiles_for_v5e(one_chip, nb, d, pdt, L):
    R = nb * d

    def window(qa, qt, qp, head, size, ppos, pacc, pav, ptch, ppay,
               rnow, ract):
        def slab(x, *tail):
            return x.reshape((nb, d) + tail)

        out = duct_window_kernel(
            slab(qa, C), slab(qt, C), slab(qp, C, L), slab(head),
            slab(size), slab(ppos), slab(pacc), slab(pav), slab(ptch),
            slab(ppay, L), rnow, ract, max_pops=16)
        rings, halo = out[:7], out[7:]
        return tuple(x.reshape((R,) + x.shape[2:]) for x in rings) + halo

    _compile(window, one_chip,
             ((R, C), jnp.float32), ((R, C), jnp.int32), ((R, C, L), pdt),
             ((R,), jnp.int32), ((R,), jnp.int32), ((R,), jnp.int32),
             ((R,), jnp.bool_), ((R,), jnp.float32), ((R,), jnp.int32),
             ((R, L), pdt), ((nb,), jnp.float32), ((nb,), jnp.bool_))


@pytest.mark.parametrize("rings,pdt,L", COMMIT_CASES)
def test_duct_commit_compiles_for_v5e(one_chip, rings, pdt, L):
    _compile(duct_commit_kernel, one_chip,
             ((rings, C), jnp.float32), ((rings, C), jnp.int32),
             ((rings, C, L), pdt), ((rings,), jnp.int32),
             ((rings,), jnp.int32), ((rings,), jnp.int32),
             ((rings, W), jnp.float32), ((rings, W), jnp.int32),
             ((rings, W, L), pdt))
