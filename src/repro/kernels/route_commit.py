"""Pallas TPU megakernel: fused score -> route -> queue-commit for a batch.

The batched scheduler hot path used to be three HBM round-trips per slot:
recompute W from Q, run ``pod_route``/``weighted_argmin`` over ONE workload
snapshot, then scatter the commits back into Q on the host.  Snapshot
routing has a correctness bug at bursty arrival rates — every arrival in
the batch sees the same argmin, so a burst herds onto one server in a way
the paper's sequential model (and GB-PANDAS) never does.

This kernel fuses all three stages into one launch and resolves conflicts
*inside* the batch: a W-delta accumulator lives in VMEM, and arrival b+1
scores against workloads that already include arrival b's commit
(``dW[sel] += inv_rates[sel, cls]`` per accepted arrival).  Semantics are
the paper's per-arrival sequential routing, at batched launch cost.

Two variants share the wrapper (``route_commit``):

  full  (``cls``: [B, M])           — Balanced-Pandas O(M) argmin per
        arrival over every server's weighted workload.
  pod   (``cand_idx``/``cand_cls``/``cand_valid``: [B, C]) — power-of-d
        argmin over an explicit candidate list (paper §IV-C); also serves
        JSQ-style shortest-queue routing with unit rates (queue length ==
        workload when every inverse rate is 1).

Tie-break contract (the in-kernel class-priority lane)
------------------------------------------------------
Exact score ties resolve by locality class first (LOCAL < RACK < REMOTE),
then — full variant — by an optional per-server integer priority ``prio``
(lower wins; pass a random permutation for the unbiased random ties the
sequential path and the event-accurate refsim use — W takes lattice
values, ties are ROUTINE, and always-lowest-index ties hotspot low-index
servers measurably), then by lowest server index.  The pod variant breaks
class ties by lowest candidate slot; slots are randomly sampled, so slot
order is already unbiased across slots.  The ranking is staged on
integers — ``rank = (cls * Mp + prio) * Mp + index`` under the tie mask —
so it is EXACT at any workload magnitude.  This replaces the old
host-side ``W + _BP_TIE_EPS`` uniform lift, which f32 addition silently
absorbed once W >> 1e-6 * ulp scale (W >~ 16), i.e. the documented class
tie-break did not fire at exactly the high loads where it matters.  If no
candidate has a finite score (all dead / invalid), the same ranking still
yields a deterministic pick (lowest class, then priority/index, valid
slots preferred) and the W commit is 0 (dead entries carry finite rate 0
in the encoding).

TPU mapping: one launch, whole operands VMEM-resident.  Servers sit on
lanes: Q and the inverse rates enter as [8, Mp] (classes on sublanes, M
padded to 128 lanes), W leaves as a [1, Mp] row, and the per-arrival
outputs as [Bp, 1] columns (B and C padded to multiples of 8), so the
kernel needs no reshape at all.  Batch-parallel work: the workload
recompute ``W0 = Q0*r0 + Q1*r1 + Q2*r2``, the pod variant's candidate
gathers (one [Bp, Mp] one-hot per candidate slot, lane-reduced with a
select, so the gather is exact), and the final Q commit (a masked
integer column sum per class).  The sequential ``while_loop`` over
arrivals does only the argmin and the rank-1 W-delta update: the full
variant reads arrival b's class row from VMEM (O(M) per step); the pod
variant keeps the delta mirrored at every candidate ([Bp, Cp]).

Limits (compiled for a described v5e, ``tests/test_tpu_compile.py``):
the tie-rank lane needs ``4 * Mp**2 < 2**30``, i.e. M <= 16256.  Below
that, VMEM bounds the full variant: it holds ``cls`` [Bp, Mp] int32 and
a few [Bp, Mp] temporaries of the vectorized tail inside the default
scoped VMEM (16 MiB on v5e).  Both variants compile at M=5000, B=96
(about 2 s each) and at M=8192, B=96; the full variant at M=16000, B=96
runs out of VMEM, the pod variant compiles.  There is no host-side
batch tiling: a caller above the limit gets the compiler's error.

``interpret=None`` (default) resolves through ``resolve_interpret``:
Mosaic on a TPU backend, the Pallas interpreter on the CPU backend.
Oracle: ref.route_commit_ref (exact sequential-commit semantics).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .invrates import FLAG_BASE, WIDTH, encode, resolve_interpret

LANE = 128
_BIG = 2**30  # tie-rank sentinel (fits int32; plain int so kernels close over no arrays)


def _class_select(sel_key, per_class):
    """per_class[c] broadcast-selected by ``sel_key`` in {0,1,2}."""
    return jnp.where(sel_key == 0, per_class[0],
                     jnp.where(sel_key == 1, per_class[1], per_class[2]))


def _rows(ref, base):
    """The three class rows ``ref[base + k]`` of a [8, Mp] operand."""
    return [ref[base + k:base + k + 1, :] for k in range(3)]


def _workload(q_ref, invm_ref):
    """W0 [1, Mp] = sum_k Q[k] * inv[k], summed in class order (the
    oracle's order, so W is bit-equal to ref.route_commit_ref)."""
    q = [r.astype(jnp.float32) for r in _rows(q_ref, 0)]
    r = _rows(invm_ref, 0)
    return (q[0] * r[0] + q[1] * r[1]) + q[2] * r[2]


def _pick_row(row, x, fill):
    """Row ``b`` of a [Bp, N] block as [1, N] (``row``: [Bp, 1] mask)."""
    return jnp.sum(jnp.where(row, x, fill), axis=0, keepdims=True)


def _write_outputs(q_ref, mask, sel_v, cls_v, val_v, w,
                   qout_ref, wout_ref, sel_ref, selcls_ref, val_ref,
                   *, m_pad: int, b_pad: int):
    """Commit the accepted arrivals into Q and store every output.

    dQ[k, m] counts accepted arrivals routed to server m in class k: a
    masked integer column sum per class (exact, no MXU)."""
    iota_bm = jax.lax.broadcasted_iota(jnp.int32, (b_pad, m_pad), 1)
    hit = (iota_bm == sel_v) & (mask > 0)                     # [Bp, Mp]
    qout_ref[...] = q_ref[...]
    for k in range(3):
        dq = jnp.sum(jnp.where(hit & (cls_v == k), 1, 0), axis=0,
                     keepdims=True)
        qout_ref[k:k + 1, :] = q_ref[k:k + 1, :] + dq
    wout_ref[...] = w
    sel_ref[...] = sel_v
    selcls_ref[...] = cls_v
    val_ref[...] = val_v


def _loop_init(m_pad, b_pad):
    return (jnp.int32(0),
            jnp.zeros((1, m_pad), jnp.float32),
            jnp.zeros((b_pad, 1), jnp.int32),
            jnp.zeros((b_pad, 1), jnp.int32),
            jnp.zeros((b_pad, 1), jnp.float32))


def _kernel_full(q_ref, cls_ref, mask_ref, invm_ref, prio_ref,
                 qout_ref, wout_ref, sel_ref, selcls_ref, val_ref,
                 *, m_pad: int, b_pad: int):
    # layout: servers on lanes.  q/invm: [8, Mp] (rows 0..2 = classes,
    # invm rows 4..6 = dead flags); cls: [Bp, Mp] (pad rows/lanes: 3);
    # mask: [Bp, 1] commit gate; prio: [1, Mp] tie priority < Mp.
    mask = mask_ref[...]
    prio = prio_ref[...]
    w0 = _workload(q_ref, invm_ref)                          # [1, Mp]
    rates = _rows(invm_ref, 0)
    flags = _rows(invm_ref, FLAG_BASE)

    rows = jax.lax.broadcasted_iota(jnp.int32, (b_pad, 1), 0)
    iota_m = jax.lax.broadcasted_iota(jnp.int32, (1, m_pad), 1)

    # arrivals past the last committing (valid) one never change dW — the
    # sequential loop only needs to run that far; everything after is one
    # vectorized tail pass against the final dW.  Typical batches are
    # Poisson draws far below the a_max padding, so this cuts the
    # sequential trip count from Bp to ~E[arrivals].
    n_proc = jnp.max(jnp.where(mask > 0, rows + 1, 0))

    def cond(carry):
        return carry[0] < n_proc

    def body(carry):
        b, dw, sel_v, cls_v, val_v = carry
        # arrival b's class row, read straight from VMEM: O(M) per step
        cls_b = cls_ref[pl.ds(b, 1), :]                      # [1, Mp]
        fac_b = _class_select(cls_b, rates)
        ok_b = (cls_b < 3) & (_class_select(cls_b, flags) == 0.0)
        # arrival b scores against W0 + the commits of arrivals 0..b-1
        scores = jnp.where(ok_b, (w0 + dw) * fac_b, jnp.inf)
        best = jnp.min(scores)
        # tie-break lane: class, then priority, then index — exact integer
        # ranking, no epsilon (fits int32: wrapper asserts 4*Mp^2 < _BIG)
        rank = jnp.where(scores == best,
                         (cls_b * m_pad + prio) * m_pad + iota_m, _BIG)
        rb = jnp.min(rank)
        sel = rb % m_pad
        scls = rb // (m_pad * m_pad)
        onb = rows == b
        accept = jnp.sum(jnp.where(onb, mask, 0)) > 0
        # W-delta accumulator: the committed task adds 1/rate at (sel, cls)
        # (0 for a dead server — finite encoding carries 0 there)
        dw = dw + jnp.where((iota_m == sel) & accept, fac_b, 0.0)
        return (b + 1, dw, jnp.where(onb, sel, sel_v),
                jnp.where(onb, scls, cls_v), jnp.where(onb, best, val_v))

    _, dw, sel_v, cls_v, val_v = jax.lax.while_loop(
        cond, body, _loop_init(m_pad, b_pad))

    # vectorized tail: arrivals b >= n_proc (all invalid) score against
    # the final dW — identical semantics to running the loop to Bp
    cls = cls_ref[...]
    elig = (cls < 3) & (_class_select(cls, flags) == 0.0)
    scores_t = jnp.where(elig, (w0 + dw) * _class_select(cls, rates),
                         jnp.inf)                                # [Bp, Mp]
    best_t = jnp.min(scores_t, axis=1, keepdims=True)            # [Bp, 1]
    rank_t = jnp.where(scores_t == best_t,
                       (cls * m_pad + prio) * m_pad + iota_m, _BIG)
    rb_t = jnp.min(rank_t, axis=1, keepdims=True)
    done = rows < n_proc
    sel_v = jnp.where(done, sel_v, rb_t % m_pad)
    cls_v = jnp.where(done, cls_v, rb_t // (m_pad * m_pad))
    val_v = jnp.where(done, val_v, best_t)

    _write_outputs(q_ref, mask, sel_v, cls_v, val_v, w0 + dw,
                   qout_ref, wout_ref, sel_ref, selcls_ref, val_ref,
                   m_pad=m_pad, b_pad=b_pad)


def _kernel_pod(q_ref, idx_ref, cls_ref, cval_ref, mask_ref, invm_ref,
                qout_ref, wout_ref, sel_ref, selcls_ref, val_ref,
                *, m_pad: int, c_pad: int, b_pad: int, homogeneous: bool):
    cand = idx_ref[...]                          # [Bp, Cp]
    ccls = cls_ref[...]                          # [Bp, Cp] (pad: 3)
    cval = cval_ref[...]                         # [Bp, Cp] int 0/1
    mask = mask_ref[...]                         # [Bp, 1]
    w0 = _workload(q_ref, invm_ref)              # [1, Mp]

    rows = jax.lax.broadcasted_iota(jnp.int32, (b_pad, 1), 0)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (1, c_pad), 1)
    iota_m = jax.lax.broadcasted_iota(jnp.int32, (1, m_pad), 1)
    iota_bc = jax.lax.broadcasted_iota(jnp.int32, (b_pad, c_pad), 1)
    iota_bm = jax.lax.broadcasted_iota(jnp.int32, (b_pad, m_pad), 1)

    # candidate gathers, one slot column at a time: a [Bp, Mp] one-hot
    # against candidate column c, lane-reduced to [Bp, 1] and placed at
    # lane c.  Select + sum is an exact gather (every other term is 0).
    if homogeneous:
        # every server has the same rates: the per-candidate rate is a
        # pure function of the class (scalars from lane 0)
        lane0 = lambda base: [x[:, 0:1] for x in _rows(invm_ref, base)]
        factor = _class_select(ccls, lane0(0))
        dead = _class_select(ccls, lane0(FLAG_BASE))
        srcs = [w0]
    else:
        srcs = [w0] + _rows(invm_ref, 0) + _rows(invm_ref, FLAG_BASE)

    def gather(c, acc):
        at_c = iota_bc == c
        oh = iota_bm == jnp.sum(jnp.where(at_c, cand, 0), axis=1,
                                keepdims=True)                  # [Bp, Mp]
        return tuple(
            jnp.where(at_c, jnp.sum(jnp.where(oh, src, 0.0), axis=1,
                                    keepdims=True), a)
            for src, a in zip(srcs, acc))

    zero_bc = jnp.zeros((b_pad, c_pad), jnp.float32)
    g = jax.lax.fori_loop(0, c_pad, gather, (zero_bc,) * len(srcs))
    w0c = g[0]                                   # W0[cand]  [Bp, Cp]
    if not homogeneous:
        factor = _class_select(ccls, list(g[1:4]))
        dead = _class_select(ccls, list(g[4:7]))
    elig = ((cval > 0) & (ccls < 3) & (dead == 0.0)).astype(jnp.int32)

    # sequential work stops after the last valid arrival (see _kernel_full)
    n_proc = jnp.max(jnp.where(mask > 0, rows + 1, 0))

    def cond(carry):
        return carry[0][0] < n_proc

    def body(carry):
        # dwc [Bp, Cp] mirrors dw at every candidate: dwc[b, c] is
        # dw[cand[b, c]] built from the same additions in the same order,
        # so W0[cand] + dwc is bit-equal to (W0 + dW)[cand]
        (b, dw, sel_v, cls_v, val_v), dwc = carry
        row = rows == b
        ccls_b = _pick_row(row, ccls, 0)
        cand_b = _pick_row(row, cand, 0)
        cval_b = _pick_row(row, cval, 0)
        fac_b = _pick_row(row, factor, 0.0)
        ok_b = _pick_row(row, elig, 0) > 0
        wc_b = _pick_row(row, w0c, 0.0) + _pick_row(row, dwc, 0.0)
        scores = jnp.where(ok_b, wc_b * fac_b, jnp.inf)
        best = jnp.min(scores)
        # class lane, then slot order; invalid slots only as a last resort
        rank = jnp.where(scores == best,
                         ccls_b * c_pad + iota_c + (1 - cval_b) * 4 * c_pad,
                         _BIG)
        slot = jnp.min(rank) % c_pad
        slot_oh = iota_c == slot
        sel = jnp.sum(jnp.where(slot_oh, cand_b, 0))
        scls = jnp.sum(jnp.where(slot_oh, ccls_b, 0))
        amt = jnp.sum(jnp.where(slot_oh, fac_b, 0.0))
        accept = jnp.sum(jnp.where(row, mask, 0)) > 0
        dw = dw + jnp.where((iota_m == sel) & accept, amt, 0.0)
        dwc = dwc + jnp.where((cand == sel) & accept, amt, 0.0)
        return ((b + 1, dw, jnp.where(row, sel, sel_v),
                 jnp.where(row, scls, cls_v), jnp.where(row, best, val_v)),
                dwc)

    (_, dw, sel_v, cls_v, val_v), dwc = jax.lax.while_loop(
        cond, body, (_loop_init(m_pad, b_pad), zero_bc))

    # vectorized tail for arrivals past the last valid one: all score
    # against the final dW
    scores_t = jnp.where(elig > 0, (w0c + dwc) * factor, jnp.inf)
    best_t = jnp.min(scores_t, axis=1, keepdims=True)            # [Bp, 1]
    rank_t = jnp.where(scores_t == best_t,
                       ccls * c_pad + iota_bc + (1 - cval) * 4 * c_pad,
                       _BIG)
    slot_t = jnp.min(rank_t, axis=1, keepdims=True) % c_pad      # [Bp, 1]
    slot_oh_t = iota_bc == slot_t
    sel_t = jnp.sum(jnp.where(slot_oh_t, cand, 0), axis=1, keepdims=True)
    scls_t = jnp.sum(jnp.where(slot_oh_t, ccls, 0), axis=1, keepdims=True)
    done = rows < n_proc
    sel_v = jnp.where(done, sel_v, sel_t)
    cls_v = jnp.where(done, cls_v, scls_t)
    val_v = jnp.where(done, val_v, best_t)

    _write_outputs(q_ref, mask, sel_v, cls_v, val_v, w0 + dw,
                   qout_ref, wout_ref, sel_ref, selcls_ref, val_ref,
                   m_pad=m_pad, b_pad=b_pad)


def _server_rows(x, Mp):
    """[M, k] per-server operand -> [8, Mp] with servers on lanes."""
    M, k = x.shape
    return jnp.pad(x.T, ((0, WIDTH - k), (0, Mp - M)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def route_commit(Q: jnp.ndarray, valid: jnp.ndarray, inv_rates: jnp.ndarray,
                 *, cls: Optional[jnp.ndarray] = None,
                 prio: Optional[jnp.ndarray] = None,
                 cand_idx: Optional[jnp.ndarray] = None,
                 cand_cls: Optional[jnp.ndarray] = None,
                 cand_valid: Optional[jnp.ndarray] = None,
                 interpret: Optional[bool] = None):
    """Fused sequential-commit routing of one arrival batch.

    Q: [M, 3] int32 sub-queue lengths; valid: [B] bool arrival/commit mask;
    inv_rates: [3] homogeneous or [M, 3] per-server (+inf = dead, masked
    to +inf scores after the multiply, never NaN).  Exactly one of:

      cls       [B, M] int32  — full variant (argmin over all M)
      cand_idx/cand_cls/cand_valid [B, C] — pod variant (candidate list)

    prio (full variant only): [M] int32 per-server tie priority in
    [0, M), lower wins after the class tie-break — pass a random
    permutation for unbiased ties (the sequential path / refsim
    semantics); None falls back to index order.

    Returns (Q_new [M, 3] int32, W_new [M] f32, sel [B] int32,
    sel_cls [B] int32, val [B] f32): the post-commit queues, the
    post-commit workloads as routing saw them (W0 + the sequential
    deltas), each arrival's chosen server + locality class, and its score
    at decision time.  Arrival b's score already reflects commits
    0..b-1 — see ref.route_commit_ref for the exact oracle.
    """
    M, three = Q.shape
    assert three == 3
    interp = resolve_interpret(interpret)
    # Mosaic needs 128-lane tiles; the interpreter (CPU/CI) has no lane
    # constraint, and at small M the 128-lane pad is ~3x wasted vector work
    # per slot.  Padding never changes results (pad lanes are ineligible
    # and the integer tie radix scales with Mp without reordering ranks).
    lane = LANE if not interp else 8
    Mp = max(8, -(-M // lane) * lane)
    assert 4 * Mp * Mp < _BIG, f"M={M}: tie-rank lane overflows int32"
    q_p = _server_rows(Q.astype(jnp.int32), Mp)                # [8, Mp]
    invm = _server_rows(encode(inv_rates, M), Mp)              # [8, Mp]
    B = (cls if cls is not None else cand_idx).shape[0]
    Bp = max(8, -(-B // 8) * 8)
    mask_p = jnp.pad(valid.astype(jnp.int32), (0, Bp - B))[:, None]

    if cls is not None:
        assert cand_idx is None, "pass cls OR cand_idx, not both"
        cls_p = jnp.pad(cls.astype(jnp.int32), ((0, Bp - B), (0, Mp - M)),
                        constant_values=3)
        prio_p = jnp.arange(Mp, dtype=jnp.int32)   # pad lanes keep < Mp
        if prio is not None:
            prio_p = prio_p.at[:M].set(prio.astype(jnp.int32))
        kern = functools.partial(_kernel_full, m_pad=Mp, b_pad=Bp)
        operands = (q_p, cls_p, mask_p, invm, prio_p[None, :])
    else:
        assert cand_idx is not None and cand_cls is not None \
            and cand_valid is not None
        assert prio is None, "prio is a full-variant operand (slot order " \
            "is already random in a sampled candidate list)"
        C = cand_idx.shape[1]
        Cp = max(8, -(-C // 8) * 8)
        pad2 = lambda x, fill: jnp.pad(x.astype(jnp.int32),
                                       ((0, Bp - B), (0, Cp - C)),
                                       constant_values=fill)
        kern = functools.partial(_kernel_pod, m_pad=Mp, c_pad=Cp, b_pad=Bp,
                                 homogeneous=inv_rates.ndim == 1)
        operands = (q_p, pad2(cand_idx, 0), pad2(cand_cls, 3),
                    pad2(cand_valid, 0), mask_p, invm)

    q_new, w, sel, scls, val = pl.pallas_call(
        kern,
        out_shape=[
            jax.ShapeDtypeStruct((WIDTH, Mp), jnp.int32),
            jax.ShapeDtypeStruct((1, Mp), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        ],
        interpret=interp,
        # the device instruction is then ``route_commit.N`` whatever
        # jit, vmap or scope wraps the call (profiles find it by name)
        name="route_commit",
    )(*operands)
    return (q_new[:3, :M].T, w[0, :M], sel[:B, 0], scls[:B, 0], val[:B, 0])
