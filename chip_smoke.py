#!/usr/bin/env python3
"""Drive the scheduler's main path once on a TPU, at the paper's fleet.

Usage:
  python3 chip_smoke.py [--seed N]       # one chip, phases a-d below
  python3 chip_smoke.py --chips 4        # the four-chip sweep only

The fleet is the paper's §V setting (``benchmarks/common.py`` PAPER
preset): M=500 servers in K=10 racks, 3 replicas per chunk,
``Rates(0.01, 0.005, 0.002)``.  Everything routes through
``route_mode="batched"``, so the ``route_commit`` Pallas kernel runs
compiled by Mosaic.  One process drives the chip; it starts no children.

Phases (one chip):
  a  the device is a TPU (else exit 1 with no result line).
  b  ``route_commit`` full and pod, homogeneous and [M, 3] rates with a
     dead server, against ``kernels.ref.route_commit_ref`` on the same
     operands: Q, sel and sel_cls exactly equal.
  c  ``simulate_sweep`` over {uniform, slow_rack} x loads {0.5, 0.9} x 2
     seeds for BP, BP-Pod and JSQ-MW-Pod: one compile per policy, no
     clipped arrivals, finite delays, and on ``uniform`` batched within
     25% of ``route_mode="sequential"`` per load.  The ``slow_rack`` pair
     is printed, not gated.
  d  a production-day trace synthesized from ``--seed`` replayed by
     ``ReplayEngine`` at M=500: one compile, mean delay within 5% of
     ``simulate_grid`` on the trace's lowered scenario.

``--chips 4`` runs the phase-c sweep with the scenario axis shard_mapped
over four chips and the same grid on one chip, and requires bit-identical
cells.  Wall times after compile go to lines before the last; the last
line is the JSON result, printed only when every phase passed.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

M, K = 500, 10                     # paper §V fleet
T_SWEEP, WARMUP_SWEEP = 20_000, 5_000   # cut from the preset's 40k / 10k
SWEEP_SCENARIOS = ("uniform", "slow_rack")
SWEEP_LOADS = (0.5, 0.9)
SWEEP_SEEDS = 2
POLICIES = ("balanced_pandas", "balanced_pandas_pod", "jsq_maxweight_pod")
FOUR_CHIP_SCENARIOS = ("uniform", "slow_rack", "rack_outage",
                       "zipf_hotspot")
T_REPLAY, WARMUP_REPLAY = 30_000, 6_000
REPLAY_LOAD = 0.45
REPLAY_SEEDS, SIM_SEEDS = 4, 8


def log(msg: str) -> None:
    """One progress line (never the last line of the output)."""
    print(msg, flush=True)


def timed(fn):
    """(result, seconds) of ``fn()``, blocking on every array it returns."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# -- phase b: the kernel against its oracle ---------------------------------

def kernel_operands(cluster, rates, seed: int, B: int):
    """Realistic route_commit operands: queues, one slot's arrivals with
    their locality classes and pod candidate lists, and homogeneous and
    per-server inverse rates (a slow rack at 0.3x and one dead server)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.cluster import locality_class, safe_inv_rates, \
        sample_locals
    from repro.core.policies import pod_candidates
    from repro.core.simulator import BP_POD_DEFAULT

    rng = np.random.default_rng(seed)
    k_loc, k_cand = jax.random.split(jax.random.PRNGKey(seed))
    locals_ = sample_locals(k_loc, cluster, B)
    cls = locality_class(cluster, locals_)
    ci, cc, cv = pod_candidates(k_cand, cluster, locals_, cls,
                                BP_POD_DEFAULT)
    Q = jnp.asarray(rng.integers(0, 40, (cluster.M, 3)), jnp.int32)
    valid = jnp.asarray(rng.random(B) < 0.85)
    prio = jnp.asarray(rng.permutation(cluster.M), jnp.int32)
    speed = np.ones((cluster.M, 1), np.float32)
    speed[:cluster.rack_size] = 0.3
    rate_m = np.asarray(rates.as_array())[None, :] * speed
    rate_m[cluster.M // 2] = 0.0                      # a dead server
    homo = safe_inv_rates(rates.as_array())
    hetero = safe_inv_rates(jnp.asarray(rate_m, jnp.float32))
    return dict(Q=Q, valid=valid, cls=cls, prio=prio, ci=ci, cc=cc,
                cv=cv, homo=homo, hetero=hetero)


def phase_kernel(cluster, rates, seed: int, B: int) -> list:
    """route_commit vs route_commit_ref; returns failure strings."""
    import jax
    import numpy as np
    from repro.kernels import ref, route_commit

    ops = kernel_operands(cluster, rates, seed, B)
    fails = []
    for rname in ("homo", "hetero"):
        inv = ops[rname]
        for variant in ("full", "pod"):
            kw = (dict(cls=ops["cls"], prio=ops["prio"]) if variant == "full"
                  else dict(cand_idx=ops["ci"], cand_cls=ops["cc"],
                            cand_valid=ops["cv"]))
            run = lambda: route_commit(ops["Q"], ops["valid"], inv, **kw)
            timed(run)                                      # compile
            got, dt = timed(run)
            want = jax.block_until_ready(
                jax.jit(lambda: ref.route_commit_ref(
                    ops["Q"], ops["valid"], inv, **kw))())
            got = [np.asarray(x) for x in got]
            want = [np.asarray(x) for x in want]
            exact = all(np.array_equal(a, b) for a, b in
                        zip(got[:1] + got[2:4], want[:1] + want[2:4]))
            w_err = float(np.max(np.abs(got[1] - want[1])
                                 / np.maximum(np.abs(want[1]), 1e-30)))
            v_fin = np.isfinite(want[4])
            v_ok = (np.array_equal(v_fin, np.isfinite(got[4]))
                    and np.allclose(got[4][v_fin], want[4][v_fin],
                                    rtol=1e-6))
            log(f"[b] route_commit {variant:4s} {rname:6s} M={cluster.M} "
                f"B={B} exact_Q_sel_cls={exact} W_max_rel_err={w_err:.3g} "
                f"W_bit_equal={np.array_equal(got[1], want[1])} "
                f"val_ok={v_ok} wall_after_compile_s={dt:.6f}")
            if not exact or w_err > 1e-6 or not v_ok:
                fails.append(f"route_commit {variant} {rname}")
    return fails


# -- phase c: the one-program sweep -----------------------------------------

def run_sweep(algo, cluster, rates, mode, scenarios, devices):
    """(names, SimResult, traces added, seconds) of one simulate_sweep in
    route ``mode`` on ``devices``."""
    from repro.core import SimConfig
    from repro.core.simulator import simulate_sweep, trace_count
    cfg = SimConfig(T=T_SWEEP, warmup=WARMUP_SWEEP, route_mode=mode)
    tc0 = trace_count()
    (names, res, _), dt = timed(lambda: simulate_sweep(
        algo, cluster, rates, SWEEP_LOADS, SWEEP_SEEDS, cfg=cfg,
        scenarios=scenarios, devices=devices))
    return names, res, trace_count() - tc0, dt


def phase_sweep(cluster, rates, device) -> list:
    """Batched sweep per policy, checked against the sequential path."""
    import numpy as np

    fails = []
    run = lambda algo, mode: run_sweep(algo, cluster, rates, mode,
                                       SWEEP_SCENARIOS, [device])
    for algo in POLICIES:
        names, rb, n_tr, dt_first = run(algo, "batched")
        _, rb2, n_tr2, dt_warm = run(algo, "batched")
        _, rs, n_trs, dt_seq = run(algo, "sequential")
        delay_b = np.asarray(rb.mean_completion_slots)     # [S, K, L]
        delay_s = np.asarray(rs.mean_completion_slots)
        cells = delay_b.size
        log(f"[c] {algo}: batched first_call_s={dt_first:.3f} "
            f"wall_after_compile_s={dt_warm:.3f} "
            f"slots_per_s={cells * T_SWEEP / dt_warm:.1f} "
            f"traces={n_tr}+{n_tr2} "
            f"| sequential first_call_s={dt_seq:.3f} traces={n_trs}")
        if n_tr != 1 or n_tr2 != 0 or n_trs != 1:
            fails.append(f"{algo}: traces {n_tr}/{n_tr2}/{n_trs}, "
                         "want 1/0/1")
        for tag, r in (("batched", rb), ("sequential", rs)):
            clip = float(np.max(np.asarray(r.clip_fraction)))
            if clip != 0.0:
                fails.append(f"{algo} {tag}: clip fraction {clip}")
            if not np.all(np.isfinite(np.asarray(r.mean_completion_slots))):
                fails.append(f"{algo} {tag}: non-finite delay")
        if not np.array_equal(np.asarray(rb2.mean_completion_slots),
                              delay_b):
            fails.append(f"{algo}: warm rerun differs")
        for si, name in enumerate(names):
            for li, load in enumerate(SWEEP_LOADS):
                b = float(np.mean(delay_b[si, :, li]))
                s = float(np.mean(delay_s[si, :, li]))
                rel = abs(b - s) / s
                gated = name == "uniform"
                log(f"[c] {algo} {name} load={load}: batched={b:.4f} "
                    f"sequential={s:.4f} rel={rel:.4f} "
                    f"{'(gate 0.25)' if gated else '(printed, not gated)'}")
                if gated and not rel < 0.25:
                    fails.append(f"{algo} {name} load={load}: rel {rel:.4f}")
    return fails


# -- phase d: trace replay --------------------------------------------------

def phase_replay(cluster, rates, seed: int) -> list:
    """ReplayEngine on a synthesized production day vs the simulator."""
    import numpy as np
    from repro.core import SimConfig
    from repro.core.simulator import simulate_grid
    from repro.trace import ReplayEngine, production_day, \
        replay_trace_count, reset_replay_trace_count, scenario_from_trace

    T = T_REPLAY
    cfg = SimConfig(T=T, warmup=WARMUP_REPLAY)
    # size the day to the target load on this fleet's capacity edge
    probe = ReplayEngine(production_day(n_tasks=T, seed=seed), cluster,
                         rates, cfg=cfg, chunks_per_server=12)
    n_tasks = int(round(REPLAY_LOAD * T * probe.lam_cap))
    day = production_day(n_tasks=n_tasks, seed=seed)
    eng = ReplayEngine(day, cluster, rates, cfg=cfg, chunks_per_server=12)
    reset_replay_trace_count()
    first, dt_first = timed(lambda: eng.run(seed=0))
    runs = [eng.run(seed=s) for s in range(1, REPLAY_SEEDS)]
    fails = []
    if replay_trace_count() != 1:
        fails.append(f"replay compiled {replay_trace_count()} times")
    warm = min(r.wall_s for r in runs)
    replay = float(np.mean([float(r.result.mean_completion_norm)
                            for r in [first] + runs]))
    scn = scenario_from_trace(day, chunks_per_server=12, seed=0)
    grid, dt_sim = timed(lambda: simulate_grid(
        "balanced_pandas_pod", cluster, rates, [eng.load],
        n_seeds=SIM_SEEDS, cfg=cfg, scenario=scn))
    sim = float(np.mean(np.asarray(grid.mean_completion_norm)[:, 0]))
    rel = abs(replay - sim) / sim
    log(f"[d] replay M={cluster.M} tasks={day.n_tasks} load={eng.load:.4f} "
        f"first_call_s={dt_first:.3f} wall_after_compile_s={warm:.3f} "
        f"tasks_per_s={day.n_tasks / warm:.1f} replay_delay={replay:.4f} "
        f"sim_delay={sim:.4f} rel={rel:.4f} (gate 0.05) "
        f"sim_first_call_s={dt_sim:.3f}")
    if not rel < 0.05:
        fails.append(f"replay vs simulate: rel {rel:.4f}")
    return fails


def phase_four_chips(cluster, rates, devices) -> list:
    """The sweep shard_mapped over four chips vs one chip, bit for bit."""
    import jax
    import numpy as np

    fails = []
    run = lambda algo, devs: run_sweep(algo, cluster, rates, "batched",
                                       FOUR_CHIP_SCENARIOS, devs)
    for algo in POLICIES:
        _, r4, n4, dt4_first = run(algo, devices)
        _, _, n4w, dt4 = run(algo, devices)
        _, r1, n1, dt1_first = run(algo, devices[:1])
        _, _, n1w, dt1 = run(algo, devices[:1])
        same = all(np.array_equal(np.asarray(a), np.asarray(b),
                                  equal_nan=True)
                   for a, b in zip(jax.tree_util.tree_leaves(r4),
                                   jax.tree_util.tree_leaves(r1)))
        log(f"[4] {algo}: 4 chips first_call_s={dt4_first:.3f} "
            f"wall_after_compile_s={dt4:.3f} | 1 chip "
            f"first_call_s={dt1_first:.3f} wall_after_compile_s={dt1:.3f} "
            f"| bit_identical={same} traces={n4}+{n4w}/{n1}+{n1w}")
        if not same:
            fails.append(f"{algo}: 4-chip cells differ from 1-chip cells")
        if (n4, n4w, n1, n1w) != (1, 0, 1, 0):
            fails.append(f"{algo}: traces {n4}+{n4w}/{n1}+{n1w}, "
                         "want 1+0/1+0")
    return fails


def main(argv=None) -> int:
    """Run the phases; print the result line only if all of them pass."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for kernel operands and the synthetic trace")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sweep comparison")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1
    devs = devs[:args.chips]
    kind = devs[0].device_kind
    log(f"[a] platform={platform} device_kind={kind} count={len(devs)} "
        f"jax={jax.__version__}")

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.core import Cluster, Rates, SimConfig
    from repro.core.simulator import sweep_grid

    log(f"[a] compile cache: {enable_compile_cache()}")
    cluster, rates = Cluster(M=M, K=K), Rates(0.01, 0.005, 0.002)
    fails = []
    if args.chips == 4:
        fails += phase_four_chips(cluster, rates, devs)
    else:
        # the kernel's batch width is the sweep's arrival buffer: sized
        # for the peak slot intensity at load 0.95
        B = sweep_grid(cluster, rates,
                       SimConfig(T=T_SWEEP, warmup=WARMUP_SWEEP), (0.95,),
                       scenarios=SWEEP_SCENARIOS)[3]
        for name, phase in (
                ("b", lambda: phase_kernel(cluster, rates, args.seed, B)),
                ("c", lambda: phase_sweep(cluster, rates, devs[0])),
                ("d", lambda: phase_replay(cluster, rates, args.seed))):
            t0 = time.perf_counter()
            try:
                got = phase()
            except Exception as e:          # a phase that raises fails
                got = [f"raised {type(e).__name__}: {e}"]
            log(f"[{name}] phase wall incl. compile "
                f"{time.perf_counter() - t0:.3f} s, "
                f"{'FAIL' if got else 'ok'}")
            fails += [f"phase {name}: {f}" for f in got]
    if "repro.launch.dryrun" in sys.modules:
        fails.append("repro.launch.dryrun was imported (it rewrites "
                     "XLA_FLAGS)")
    for f in fails:
        print(f"chip_smoke FAIL: {f}", file=sys.stderr)
    if fails:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
