"""route_commit compiles for a TPU v5e at real fleet widths.

The CPU tests run the kernel in the Pallas interpreter, which checks none
of Mosaic's layout, tiling or VMEM rules.  These tests hand both variants,
with homogeneous ``[3]`` and per-server ``[M, 3]`` rates, to the installed
TPU compiler for a described v5e chip (no chip attached): at the paper's
M=500 fleet and at M=2048, plus the vmapped form the one-program sweep
lowers to.  Nothing runs, so results are checked elsewhere (test_kernels
here, ``chip_smoke.py`` on the chip).
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import Cluster, Rates, SimConfig
from repro.core.simulator import BP_POD_DEFAULT
from repro.kernels import route_commit

PAPER_RATES = Rates(0.01, 0.005, 0.002)


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described (not attached) v5e:2x2 topology."""
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but can
    # never be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _batch(M: int) -> int:
    """The simulator's arrival-buffer width at load 0.95 of a uniform
    M-server fleet (the kernel's B in the sweep)."""
    return SimConfig().resolve_a_max(0.95 * M * PAPER_RATES.alpha)


def _operands(sharding, variant: str, M: int, hetero: bool, lead=()):
    B = _batch(M)
    C = Cluster(M=500, K=10).n_replicas + BP_POD_DEFAULT.d  # BP-Pod list
    s = lambda shape, dt: jax.ShapeDtypeStruct(lead + shape, dt,
                                               sharding=sharding)
    base = (s((M, 3), jnp.int32), s((B,), jnp.bool_),
            s((M, 3) if hetero else (3,), jnp.float32))
    if variant == "full":
        return base + (s((B, M), jnp.int32), s((M,), jnp.int32))
    return base + (s((B, C), jnp.int32), s((B, C), jnp.int32),
                   s((B, C), jnp.bool_))


def _call(variant: str):
    if variant == "full":
        return lambda q, v, i, c, p: route_commit(q, v, i, cls=c, prio=p,
                                                  interpret=False)
    return lambda q, v, i, a, c, ok: route_commit(
        q, v, i, cand_idx=a, cand_cls=c, cand_valid=ok, interpret=False)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("M", [500, 2048])
@pytest.mark.parametrize("hetero", [False, True], ids=["homo", "hetero"])
@pytest.mark.parametrize("variant", ["full", "pod"])
def test_route_commit_compiles_for_v5e(one_chip, variant, hetero, M):
    compiled = jax.jit(_call(variant)).lower(
        *_operands(one_chip, variant, M, hetero)).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("variant", ["full", "pod"])
def test_vmapped_route_commit_compiles_for_v5e(one_chip, variant):
    """The sweep vmaps the kernel over scenario x seed x load: Pallas
    lowers that to a grid over the batch axes."""
    compiled = jax.jit(jax.vmap(jax.vmap(_call(variant)))).lower(
        *_operands(one_chip, variant, 500, True, lead=(2, 4))).compile()
    _assert_mosaic(compiled)
