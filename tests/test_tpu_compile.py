"""route_commit compiles for a TPU v5e at real fleet widths.

The CPU tests run the kernel in the Pallas interpreter, which checks none
of Mosaic's layout, tiling or VMEM rules.  These tests hand both variants,
with homogeneous ``[3]`` and per-server ``[M, 3]`` rates, to the installed
TPU compiler for a described v5e chip (no chip attached): at the paper's
M=500 fleet and at M=2048, plus the vmapped form the one-program sweep
lowers to.  Nothing runs, so results are checked elsewhere (test_kernels
here, ``chip_smoke.py`` on the chip).

The last tests compile the one-device sweep at M=500 (T=8 slots, seconds
each) with the kernel in Mosaic, as on the chip, and the sweep sharded
over the four chips of the v5e:2x2 for the m500-sweep-4chip grid, and
read their phase maps
(``telemetry.spans.op_phases``).  On the TPU, XLA emits the random
permutation of full Balanced-Pandas as ``sort``s, which must land in
``candidates``; Balanced-Pandas-Pod draws its candidates from rack
intervals, so its program holds no cumulative sum (``reduce-window``)
and no [A, M] class tensor; full BP's class tensor is ``arrivals``; no
program holds an [A, C] tensor over the chunk catalog; and
the kernel must keep its name.  All described-chip compiles stay in this one file: only
one process at a time may load the TPU's library.
"""
import collections
import re
import sys

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import Cluster, Rates, SimConfig, sweep_grid
from repro.core import simulator as sim
from repro.core.simulator import BP_POD_DEFAULT
from repro.kernels import route_commit
from repro.scenarios import canonical_pad
from repro.telemetry.spans import (
    CONTAINER_OPS,
    KERNEL,
    PHASES,
    PLUMBING_OPS,
    op_phases,
    top_level,
)

PAPER_RATES = Rates(0.01, 0.005, 0.002)


@pytest.fixture(scope="module")
def topo():
    """A described (not attached) v5e:2x2 topology."""
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    """One device of the described v5e:2x2."""
    return SingleDeviceSharding(topo.devices[0])


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


def _mosaic_calls(text: str) -> list:
    """Names of the Mosaic custom calls in a compiled module's text."""
    return re.findall(r'%([\w.\-]+) = [^\n]*'
                      r'custom_call_target="tpu_custom_call"', text)


def _assert_mosaic(compiled):
    """The kernel is one Mosaic custom call, named ``route_commit.N``
    whatever wraps it (the name a device trace shows)."""
    calls = _mosaic_calls(compiled.as_text())
    assert [c.split(".")[0] for c in calls] == [KERNEL], calls


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


# ---------------------------------------------------------------------------
# the one-device sweep's phase map
# ---------------------------------------------------------------------------

PAPER = Cluster(M=500, K=10)
SWEEP_CFG = SimConfig(T=8, warmup=2, route_mode="batched")


@pytest.fixture(scope="module")
def mosaic(one_chip):
    """``one_chip`` with the simulator's kernel in Mosaic, as on the chip
    (the CPU backend would pick the Pallas interpreter).  Traces cached
    under the CPU's choice are dropped first, and the Mosaic ones after,
    so that no other test reuses them."""
    kernel_module = sys.modules["repro.kernels.route_commit"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kernel_module, "resolve_interpret", lambda _: False)
        jax.clear_caches()
        try:
            yield one_chip
        finally:
            jax.clear_caches()


def _compiled_sweep(sharding, algo: str, scenarios, loads):
    """(compiled text, arrival batch width A) of the sweep for the v5e."""
    _, scen, lam, a_max = sweep_grid(PAPER, PAPER_RATES, SWEEP_CFG, loads,
                                     scenarios=scenarios)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        (keys, lam, scen))
    return sim._sweep_jit.lower(
        *args, algo=algo, cluster=PAPER, rates=PAPER_RATES, cfg=SWEEP_CFG,
        pod=sim._pod_for(algo, None), a_max=a_max,
        tcfg=None).compile().as_text(), a_max


@pytest.fixture(scope="module", params=["balanced_pandas_pod",
                                        "balanced_pandas"])
def compiled(request, mosaic):
    """(algo, compiled text, phase map, A) of an M=500 sweep for the v5e."""
    algo = request.param
    if algo == "balanced_pandas_pod":      # the m500-bppod-sweep grid
        text, a_max = _compiled_sweep(mosaic, algo, ["uniform", "slow_rack"],
                                      [0.5, 0.9])
    else:
        text, a_max = _compiled_sweep(mosaic, algo, ["uniform"], [0.9])
    return algo, text, op_phases(text), a_max


def test_kernel_is_one_custom_call_named_route_commit(compiled):
    _, text, phases, _ = compiled
    calls = _mosaic_calls(text)
    assert [c.split(".")[0] for c in calls] == [KERNEL], calls
    assert phases[calls[0]] == "route"


def test_cumsums_and_permutation_sorts_are_candidates(compiled):
    algo, text, phases, _ = compiled
    instrs = top_level(text)
    windows = [i for i in instrs if i.opcode == "reduce-window"]
    shuffles = [i for i in instrs if i.opcode == "sort"
                and "_shuffle" in i.op_name]
    if algo == "balanced_pandas_pod":
        assert windows == []         # interval draws: no cumulative sum
    else:
        assert shuffles              # the tie priority's permutation
    for i in windows + shuffles:
        assert phases.get(i.name) == "candidates", i


def _result_shape(text: str, ins) -> str:
    """The result shape of a top-level instruction, as the text gives it."""
    m = re.search(rf"%{re.escape(ins.name)} = (.*?) {ins.opcode}\(", text)
    return m.group(1)


def test_class_tensor_is_arrivals(compiled):
    """Full BP's [A, M] class tensor is built in ``arrivals``; BP-Pod's
    program builds none."""
    algo, text, phases, a_max = compiled
    classes = [i for i in top_level(text)
               if re.search(rf"\[[\d,]*\b{a_max},{PAPER.M}\]",
                            _result_shape(text, i))
               and phases.get(i.name) == "arrivals"]
    if algo == "balanced_pandas_pod":
        assert classes == []
    else:
        assert classes


def test_catalog_draw_builds_no_arrival_by_catalog_tensor(compiled):
    """Each task's chunk is found by inverse CDF: no [A, C] tensor over
    the canonical catalog (the Gumbel argmax of a categorical draw built
    one) anywhere in the program."""
    _, text, _, a_max = compiled
    C = canonical_pad(PAPER).n_chunks
    assert not re.search(rf"\[[\d,]*\b{a_max},{C}\]", text)


def _unphased_scan_leaves(text: str, phases: dict) -> list:
    """Leaves of the slot scan's body (the entry's largest loop) that the
    phase map leaves out."""
    instrs = top_level(text)
    entry = instrs[0].computation
    sizes = collections.Counter(i.computation for i in instrs)
    body = max((c for i in instrs if i.computation == entry
                and i.opcode == "while" for c in i.callees),
               key=lambda c: sizes[c])
    leaves = [i for i in instrs if i.computation == body
              and i.opcode not in CONTAINER_OPS
              and i.opcode not in PLUMBING_OPS]
    assert leaves
    return [i.name for i in leaves if phases.get(i.name) not in PHASES]


def test_no_leaf_of_the_scan_body_is_left_without_a_phase(compiled):
    _, text, phases, _ = compiled
    assert _unphased_scan_leaves(text, phases) == []


# ---------------------------------------------------------------------------
# the sweep sharded over the four chips (the m500-sweep-4chip grid)
# ---------------------------------------------------------------------------

MESH_SCENARIOS = ["uniform", "slow_rack", "rack_outage", "zipf_hotspot"]


@pytest.fixture(scope="module")
def sharded(topo, mosaic):
    """(compiled text, phase map) of the M=500 BP-Pod sweep over the four
    scenarios, one per chip of the described v5e:2x2, its arguments placed
    as ``simulate_sweep`` places them."""
    mesh = Mesh(np.asarray(topo.devices), ("scen",))
    _, scen, lam, a_max = sweep_grid(PAPER, PAPER_RATES, SWEEP_CFG,
                                     [0.5, 0.9], scenarios=MESH_SCENARIOS)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)

    def on(spec):
        return lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec))
    args = ((on(P())(keys),)
            + jax.tree_util.tree_map(on(P("scen")), (lam, scen)))
    algo = "balanced_pandas_pod"
    text = sim._sweep_sharded.lower(
        *args, mesh=mesh, algo=algo, cluster=PAPER, rates=PAPER_RATES,
        cfg=SWEEP_CFG, pod=sim._pod_for(algo, None), a_max=a_max,
        tcfg=None).compile().as_text()
    return text, op_phases(text)


def test_sharded_sweep_runs_the_kernel_in_mosaic_without_collectives(
        sharded):
    """Each chip runs its own scenario: one Mosaic ``route_commit.N`` in
    the ``route`` phase, and nothing crosses chips."""
    text, phases = sharded
    calls = _mosaic_calls(text)
    assert [c.split(".")[0] for c in calls] == [KERNEL], calls
    assert phases[calls[0]] == "route"
    crossing = [i.name for i in top_level(text)
                if i.opcode.startswith(("all-", "collective-",
                                        "reduce-scatter"))]
    assert crossing == []


def test_no_leaf_of_the_sharded_scan_body_is_left_without_a_phase(sharded):
    assert _unphased_scan_leaves(*sharded) == []
