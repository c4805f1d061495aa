"""Batched fused placement kernel: many templates, one Pallas call.

The single-template fused kernel (engine/fused.py) bakes every per-problem
scalar into the program as a literal — perfect for repeated solves of one
template, useless for a 100-template sweep (every template would trigger a
fresh Mosaic compile).  This variant moves the per-template numerics into an
SMEM scalar table and runs a grid over the template axis: one compiled
executable serves the whole group, each grid program runs K fused greedy
steps for one template with that template's planes resident in VMEM while
Pallas pipelines the next template's slab in from HBM.

Group-uniform structure (resource vocabulary, padded constraint/group
counts, plugin set, sampling mode) lives in the jit key; everything numeric
(request vectors, skews, weights, group increments, self-match flags) is
runtime data.  parallel/sweep._pad_group already provides exactly this
uniformity for its vmapped XLA path — the batched kernel rides the same
padded problems and must stay bit-identical to `vmap(_step)` over them
(differential-tested in tests/test_fused_batched.py; runtime cross-check in
_batched_solve mirrors the single-template kernel's).

Reference hot path being replaced (one scheduling cycle per pod, repeated
per template): vendor/k8s.io/kubernetes/pkg/scheduler/schedule_one.go:610-694.
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from .. import obs
from ..models.snapshot import IDX_CPU, IDX_PODS
from ..ops.node_resources_fit import _floor_div
from . import fused
from . import simulator as sim
from .fused import LANES, _BIG, _Packing, _pack_consts, _pack_meta

# Template-axis cap per pallas call: bounds the stacked const slab in HBM
# (B * P * S * 128 * 4B).  _batched_solve splits bigger groups into
# MAX_BATCH-sized segments before reaching this module.
MAX_BATCH = 256

# Mosaic requires SMEM block sublane counts divisible by 8 (or equal to the
# array dimension).  The per-template scalar rows therefore move through
# 8-row tiles: arrays are padded to a multiple of _SMEM_TILE on the template
# axis and each grid program reads/writes row `program_id % _SMEM_TILE` of
# block `program_id // _SMEM_TILE`.
_SMEM_TILE = 8


def _pad_rows(arr, xp=np):
    """Pad [B, W] to [ceil(B/8)*8, W] with zeros."""
    b = arr.shape[0]
    pad = -b % _SMEM_TILE
    if not pad:
        return arr
    return xp.concatenate(
        [arr, xp.zeros((pad, arr.shape[1]), dtype=arr.dtype)])


class ScalarTable(NamedTuple):
    """Layout of the per-template SMEM scalar row."""

    fields: Tuple[Tuple[str, int], ...]    # (name, length) in order

    @property
    def offsets(self) -> Dict[str, int]:
        out, off = {}, 0
        for name, ln in self.fields:
            out[name] = off
            off += ln
        return out

    @property
    def width(self) -> int:
        return sum(ln for _, ln in self.fields)


def _scalar_table(pk: _Packing) -> ScalarTable:
    """Per-template numerics the single-template kernel bakes as literals.
    Lengths are group-uniform (same cfg, padded counts)."""
    m = pk.meta
    f = len(m.cfg.fit_idx)
    bal = len(m.cfg.bal_idx)
    return ScalarTable(fields=(
        ("req_vec", m.r), ("req_nonzero", 2),
        ("fit_w", f), ("fit_req", f), ("bal_req", bal),
        ("sh_skew", m.ch), ("sh_mindom", m.ch), ("sh_domnum", m.ch),
        ("sh_self", m.ch),
        ("ss_skew", m.cs), ("ss_self", m.cs), ("ss_host", m.cs),
        ("ghas_aff", m.g), ("ghas_anti", m.g),
        ("aff_ginc", m.g), ("anti_ginc", m.g), ("pref_gw", m.g),
    ))


def _structural_meta(meta: "fused.KernelMeta") -> "fused.KernelMeta":
    """Zero the numeric tuples (lengths preserved) so the compiled-call
    cache keys on group STRUCTURE — the batched kernel reads numerics from
    the SMEM table, so two groups with the same shape share the
    executable."""
    z = lambda t: tuple(0.0 for _ in t)
    zb = lambda t: tuple(False for _ in t)
    zi = lambda t: tuple(0 for _ in t)
    return meta._replace(
        req_vec=z(meta.req_vec), req_nonzero=z(meta.req_nonzero),
        shared_req_vec=z(meta.shared_req_vec),
        fit_w=z(meta.fit_w), fit_req=z(meta.fit_req),
        bal_req=z(meta.bal_req),
        sh_skew=z(meta.sh_skew), sh_mindom=z(meta.sh_mindom),
        sh_domnum=z(meta.sh_domnum), sh_self=zb(meta.sh_self),
        ss_skew=z(meta.ss_skew), ss_self=zb(meta.ss_self),
        ss_host=zb(meta.ss_host), ss_dnh=zi(meta.ss_dnh),
        ghas_aff=zb(meta.ghas_aff), ghas_anti=zb(meta.ghas_anti),
        aff_ginc=z(meta.aff_ginc), anti_ginc=z(meta.anti_ginc),
        pref_gw=z(meta.pref_gw))


def _scalar_row(tab: ScalarTable, meta: "fused.KernelMeta") -> np.ndarray:
    row = np.zeros(tab.width, dtype=np.float32)
    off = tab.offsets
    for name, ln in tab.fields:
        vals = getattr(meta, name)
        row[off[name]: off[name] + ln] = [float(v) for v in vals[:ln]]
    return row


class BatchedKey(NamedTuple):
    """jit/verification cache key: the group-uniform structure plus every
    template's numeric meta (distinct numerics still share the compiled
    executable — only `shape` feeds the jit key — but verification is
    memoized per exact group)."""

    shape: tuple                       # (const_names, carry_names, s, n, cfg…)
    metas: Tuple["fused.KernelMeta", ...]


def batched_eligible(cfg: sim.StaticConfig, pbs: List) -> bool:
    """Can this padded group ride the batched kernel?  Per-template checks
    are the single-kernel ones under the GROUP cfg; the layout-uniformity
    invariant (_pad_group's contract) is asserted in make_batched_runner."""
    if len(pbs) < 2:
        return False
    # VMEM is checked once on the shared packing in make_batched_runner
    # (pipelined budget), not per template
    return all(fused.eligible(cfg, pb, check_vmem=False) for pb in pbs)


def _build_batched_kernel(pk: _Packing, tab: ScalarTable, k_steps: int,
                          max_dnh: int):
    """Kernel body for one grid program = one template's K fused steps.
    Mirrors fused._build_kernel step-for-step with per-template literals
    replaced by SMEM scalar-table reads (ts(name, i))."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    meta, cfg = pk.meta, pk.meta.cfg
    ci, yi = pk.const_idx, pk.carry_idx
    s, n = meta.s, meta.n
    n_carry = len(yi)
    off = tab.offsets

    def kernel(const_ref, yin_ref, sin_ref, tsc_ref,
               yout_ref, sout_ref, chosen_ref):
        iota = (jax.lax.broadcasted_iota(jnp.int32, (s, LANES), 0) * LANES
                + jax.lax.broadcasted_iota(jnp.int32, (s, LANES), 1))
        real = iota < n
        # scalar rows ride in 8-row SMEM tiles (see _SMEM_TILE)
        row = jax.lax.rem(pl.program_id(0), jnp.int32(_SMEM_TILE))

        C = {name: const_ref[0, i] for name, i in ci.items()}

        def ts(name, i=0):
            return tsc_ref[row, off[name] + i]

        def step(k, state):
            Y, placed_count, stopped, next_start, aff_total = state

            # ---- feasibility ------------------------------------------
            feasible = C["static_mask"] > 0.5
            if cfg.fit_filter_on:
                fit_ok = ~(Y[yi[f"requested{IDX_PODS}"]] + 1.0
                           > C[f"alloc{IDX_PODS}"])
                for j in range(meta.r):
                    if j == IDX_PODS:
                        continue
                    rv = ts("req_vec", j)
                    fit_ok &= ~((rv > C[f"alloc{j}"]
                                 - Y[yi[f"requested{j}"]]) & (rv > 0))
                feasible &= fit_ok
            if cfg.volume_filter_on:
                feasible &= C["volume_mask"] > 0.5

            if cfg.spread_hard_n > 0:
                violated = jnp.zeros((s, LANES), dtype=bool)
                for c in range(meta.ch):
                    cnt = Y[yi[f"sh_cnt{c}"]]
                    countable = C[f"sh_countable{c}"] > 0.5
                    min_match = jnp.min(jnp.where(countable, cnt, _BIG))
                    min_match = jnp.where(
                        ts("sh_domnum", c) < ts("sh_mindom", c),
                        0.0, min_match)
                    has_key = C[f"sh_dom{c}"] >= 0
                    skew = cnt + ts("sh_self", c) - min_match
                    violated |= (skew > ts("sh_skew", c)) & has_key
                feasible &= ~((C["sh_missing"] > 0.5) | violated)

            if cfg.ipa_filter_on:
                if cfg.ipa_num_aff > 0:
                    pods_exist = jnp.ones((s, LANES), dtype=bool)
                    all_keys = jnp.ones((s, LANES), dtype=bool)
                    for gi in range(meta.g):
                        has_aff = ts("ghas_aff", gi) > 0.5
                        has_key = C[f"ipa_dom{gi}"] >= 0
                        tot = C[f"ipa_aff_scnt{gi}"] + Y[yi[f"aff_cnt{gi}"]]
                        pods_exist &= jnp.where(has_aff,
                                                has_key & (tot > 0), True)
                        all_keys &= jnp.where(has_aff, has_key, True)
                    if cfg.ipa_escape_allowed and cfg.ipa_static_empty:
                        escape = all_keys & (aff_total == 0)
                        aff_ok = pods_exist | escape
                    else:
                        aff_ok = pods_exist
                else:
                    aff_ok = jnp.ones((s, LANES), dtype=bool)
                if cfg.ipa_num_anti > 0:
                    anti_fail = jnp.zeros((s, LANES), dtype=bool)
                    eanti_dyn = jnp.zeros((s, LANES), dtype=bool)
                    for gi in range(meta.g):
                        has_anti = ts("ghas_anti", gi) > 0.5
                        has_key = C[f"ipa_dom{gi}"] >= 0
                        dyn = Y[yi[f"anti_cnt{gi}"]]
                        anti_fail |= jnp.where(
                            has_anti,
                            has_key & (C[f"ipa_anti_scnt{gi}"] + dyn > 0),
                            False)
                        eanti_dyn |= jnp.where(has_anti,
                                               has_key & (dyn > 0), False)
                else:
                    anti_fail = jnp.zeros((s, LANES), dtype=bool)
                    eanti_dyn = jnp.zeros((s, LANES), dtype=bool)
                eanti_fail = (C["ipa_eanti_static"] > 0.5) | eanti_dyn
                feasible &= aff_ok & ~anti_fail & ~eanti_fail

            any_feasible = jnp.any(feasible)

            # ---- sampling (numFeasibleNodesToFind emulation) ----------
            scorable = feasible
            new_next_start = next_start
            if cfg.sample_k > 0:
                start = next_start.astype(jnp.int32)
                rank = jnp.where(real, (iota - start) % n, n)
                kk = min(cfg.sample_k, n)

                def bs_body(_, lo_hi):
                    lo, hi = lo_hi
                    mid = (lo + hi) // 2
                    # counts 0/1 over n nodes: int32 is ample, say so
                    cnt = jnp.sum((feasible & (rank <= mid))
                                  .astype(jnp.int32), dtype=jnp.int32)
                    return jnp.where(cnt >= kk, lo, mid + 1), \
                        jnp.where(cnt >= kk, mid, hi)

                iters = max(1, int(np.ceil(np.log2(max(n, 2)))) + 1)
                lo, hi = jax.lax.fori_loop(
                    0, iters, bs_body,
                    (jnp.asarray(0, jnp.int32), jnp.asarray(n - 1, jnp.int32)))
                threshold = hi
                scorable = feasible & (rank <= threshold)
                processed = threshold + 1
                new_next_start = ((start + processed) % n).astype(jnp.float32)

            # ---- scores ----------------------------------------------
            total = jnp.zeros((s, LANES), dtype=jnp.float32)
            w = sim._weight(cfg, "NodeResourcesFit")
            if w:
                acc = jnp.zeros((s, LANES), dtype=jnp.float32)
                wsum_n = jnp.zeros((s, LANES), dtype=jnp.float32)
                rtc = cfg.fit_strategy_type == "RequestedToCapacityRatio"
                for k2, j in enumerate(cfg.fit_idx):
                    alloc = C[f"alloc{j}"]
                    if cfg.fit_nz[k2]:
                        req = Y[yi["nonzero0" if j == IDX_CPU else "nonzero1"]]
                    else:
                        req = Y[yi[f"requested{j}"]]
                    req = req + ts("fit_req", k2)
                    if cfg.fit_strategy_type == "MostAllocated":
                        per = jnp.where(alloc > 0,
                                        _floor_div(jnp.minimum(req, alloc)
                                                   * 100.0, alloc), 0.0)
                    elif rtc:
                        from ..ops.node_resources_fit import piecewise_shape
                        util = jnp.where(alloc > 0,
                                         _floor_div(req * 100.0, alloc), 0.0)
                        per = jnp.trunc(piecewise_shape(
                            util, cfg.fit_shape[0], cfg.fit_shape[1]))
                        per = jnp.where(alloc > 0, per, 0.0)
                    else:
                        per = jnp.where(req > alloc, 0.0,
                                        _floor_div((alloc - req) * 100.0,
                                                   alloc))
                        per = jnp.where(alloc > 0, per, 0.0)
                    acc = acc + per * ts("fit_w", k2)
                    # RTC drops score-0 resources from the weight sum and
                    # math.Rounds (requested_to_capacity_ratio.go:48-56)
                    counted = (alloc > 0) & (per > 0) if rtc else alloc > 0
                    wsum_n = wsum_n + jnp.where(counted,
                                                ts("fit_w", k2), 0.0)
                if rtc:
                    score = jnp.where(
                        wsum_n > 0,
                        jnp.floor(acc / jnp.maximum(wsum_n, 1e-30) + 0.5),
                        0.0)
                else:
                    score = jnp.where(wsum_n > 0, _floor_div(acc, wsum_n), 0.0)
                total = total + w * jnp.where(scorable, score, 0.0)

            w = sim._weight(cfg, "NodeResourcesBalancedAllocation")
            if w:
                fracs = []
                valids = []
                for k2, j in enumerate(cfg.bal_idx):
                    alloc = C[f"alloc{j}"]
                    req = Y[yi[f"requested{j}"]] + ts("bal_req", k2)
                    valids.append(alloc > 0)
                    fracs.append(jnp.where(
                        valids[-1],
                        jnp.minimum(req / jnp.maximum(alloc, 1e-30), 1.0),
                        0.0))
                count = sum(v.astype(jnp.float32) for v in valids)
                mean = sum(fracs) / jnp.maximum(count, 1.0)
                var = sum(jnp.where(v, (fr - mean) ** 2, 0.0)
                          for v, fr in zip(valids, fracs)) \
                    / jnp.maximum(count, 1.0)
                std = jnp.where(count >= 2, jnp.sqrt(var), 0.0)
                score = jnp.trunc((1.0 - std) * 100.0)
                total = total + w * jnp.where(scorable, score, 0.0)

            def default_normalize(raw, reverse):
                max_s = jnp.max(jnp.where(scorable, raw, 0.0))
                scaled = jnp.where(
                    max_s > 0,
                    jnp.floor(100.0 * raw / jnp.where(max_s > 0, max_s, 1.0)),
                    raw)
                if reverse:
                    scaled = jnp.where(max_s > 0, 100.0 - scaled, 100.0)
                return jnp.where(scorable, scaled, 0.0)

            w = sim._weight(cfg, "TaintToleration")
            if w:
                total = total + w * default_normalize(C["taint_raw"], True)
            w = sim._weight(cfg, "NodeAffinity")
            if w and cfg.na_active:
                total = total + w * default_normalize(C["na_raw"], False)
            w = sim._weight(cfg, "ImageLocality")
            if w:
                total = total + w * jnp.where(scorable, C["il_score"], 0.0)

            w = sim._weight(cfg, "PodTopologySpread")
            if w and cfg.spread_soft_n > 0:
                ssc = scorable & ~(C["ss_ignored"] > 0.5)
                raw = jnp.zeros((s, LANES), dtype=jnp.float32)
                host_size = jnp.sum(ssc.astype(jnp.float32))
                for c in range(meta.cs):
                    dom = C[f"ss_dom{c}"]
                    has_key = dom >= 0
                    host_c = ts("ss_host", c) > 0.5
                    cnt_host = C[f"ss_existing{c}"] \
                        + ts("ss_self", c) * Y[yi["placed"]]
                    cnt_nh = Y[yi[f"ss_cnt{c}"]]
                    size_nh = jnp.zeros((), dtype=jnp.float32)
                    for d in range(max_dnh):
                        size_nh = size_nh + jnp.any(
                            ssc & (dom == d)).astype(jnp.float32)
                    cnt = jnp.where(host_c, cnt_host, cnt_nh)
                    size = jnp.where(host_c, host_size, size_nh)
                    tp = jnp.log(size + 2.0)
                    raw = raw + jnp.where(
                        has_key, cnt * tp + (ts("ss_skew", c) - 1.0), 0.0)
                raw = jnp.round(raw)
                any_sc = jnp.any(ssc)
                max_s = jnp.max(jnp.where(ssc, raw, -jnp.inf))
                min_s = jnp.min(jnp.where(ssc, raw, jnp.inf))
                max_s = jnp.where(any_sc, max_s, 0.0)
                min_s = jnp.where(any_sc, min_s, 0.0)
                out = jnp.where(
                    max_s == 0, 100.0,
                    jnp.floor(100.0 * (max_s + min_s - raw)
                              / jnp.maximum(max_s, 1e-30)))
                total = total + w * jnp.where(ssc, out, 0.0)

            w = sim._weight(cfg, "InterPodAffinity")
            if w and cfg.ipa_score_active:
                raw = C["ipa_static_pref"] if meta.has_static_pref \
                    else jnp.zeros((s, LANES), dtype=jnp.float32)
                if cfg.ipa_num_pref > 0:
                    for gi in range(meta.g):
                        raw = raw + jnp.where(C[f"ipa_dom{gi}"] >= 0,
                                              Y[yi[f"pref_cnt{gi}"]], 0.0)
                max_s = jnp.max(jnp.where(scorable, raw, -jnp.inf))
                min_s = jnp.min(jnp.where(scorable, raw, jnp.inf))
                diff = max_s - min_s
                norm = jnp.where(
                    diff > 0,
                    jnp.floor(100.0 * (raw - min_s)
                              / jnp.where(diff > 0, diff, 1.0)), 0.0)
                total = total + w * jnp.where(scorable, norm, 0.0)

            # ---- host selection (argmax, lowest index wins) ----------
            keyed = jnp.where(scorable, total, -1.0)
            gmax = jnp.max(keyed)
            cand = jnp.where((keyed == gmax) & real, iota, n)
            chosen = jnp.min(cand).astype(jnp.int32)
            chosen = jnp.where(chosen >= n, 0, chosen)

            place = any_feasible & ~(stopped > 0.5)
            gate = place.astype(jnp.float32)
            onehot = ((iota == chosen) & real).astype(jnp.float32) * gate

            # ---- commit ----------------------------------------------
            Y2 = list(Y)
            for j in range(meta.r):
                Y2[yi[f"requested{j}"]] = Y[yi[f"requested{j}"]] \
                    + onehot * ts("req_vec", j)
            Y2[yi["nonzero0"]] = Y[yi["nonzero0"]] \
                + onehot * ts("req_nonzero", 0)
            Y2[yi["nonzero1"]] = Y[yi["nonzero1"]] \
                + onehot * ts("req_nonzero", 1)
            Y2[yi["placed"]] = Y[yi["placed"]] + onehot

            if cfg.spread_hard_n > 0:
                for c in range(meta.ch):
                    dom = C[f"sh_dom{c}"]
                    dom_ch = jnp.sum(onehot * dom)
                    countable_ch = jnp.sum(onehot * C[f"sh_countable{c}"])
                    inc = countable_ch * gate * ts("sh_self", c)
                    hit = (dom == dom_ch) & (dom >= 0)
                    Y2[yi[f"sh_cnt{c}"]] = Y[yi[f"sh_cnt{c}"]] \
                        + hit.astype(jnp.float32) * inc
            if cfg.spread_soft_n > 0:
                for c in range(meta.cs):
                    dom = C[f"ss_dom{c}"]
                    dom_ch = jnp.sum(onehot * dom)
                    countable_ch = jnp.sum(onehot * C[f"ss_countable{c}"])
                    inc = countable_ch * gate * ts("ss_self", c)
                    hit = (dom == dom_ch) & (dom >= 0)
                    Y2[yi[f"ss_cnt{c}"]] = Y[yi[f"ss_cnt{c}"]] \
                        + hit.astype(jnp.float32) * inc

            new_aff_total = aff_total
            if cfg.ipa_num_aff > 0 or cfg.ipa_num_anti > 0 \
                    or cfg.ipa_num_pref > 0:
                for gi in range(meta.g):
                    dom = C[f"ipa_dom{gi}"]
                    dom_ch = jnp.sum(onehot * dom) + jnp.where(
                        jnp.sum(onehot) > 0, 0.0, -1.0)
                    valid = (dom_ch >= 0).astype(jnp.float32)
                    hit = ((dom == dom_ch) & (dom >= 0)).astype(jnp.float32)
                    if cfg.ipa_num_aff > 0:
                        inc = ts("aff_ginc", gi) * valid * gate
                        Y2[yi[f"aff_cnt{gi}"]] = Y[yi[f"aff_cnt{gi}"]] \
                            + hit * inc
                        new_aff_total = new_aff_total + inc
                    if cfg.ipa_num_anti > 0:
                        inc = ts("anti_ginc", gi) * valid * gate
                        Y2[yi[f"anti_cnt{gi}"]] = Y[yi[f"anti_cnt{gi}"]] \
                            + hit * inc
                    if cfg.ipa_num_pref > 0:
                        inc = ts("pref_gw", gi) * valid * gate
                        Y2[yi[f"pref_cnt{gi}"]] = Y[yi[f"pref_cnt{gi}"]] \
                            + hit * inc

            chosen_ref[0, pl.ds(k, 1), :] = jnp.where(
                place, chosen, -1).astype(jnp.int32).reshape(1, 1)

            new_stopped = jnp.maximum(stopped,
                                      (~any_feasible).astype(jnp.float32))
            keep = stopped > 0.5
            next_start_out = jnp.where(keep, next_start, new_next_start)
            return (tuple(Y2),
                    placed_count + gate,
                    new_stopped,
                    next_start_out,
                    new_aff_total)

        Y0 = tuple(yin_ref[0, i] for i in range(n_carry))
        state = (Y0, sin_ref[row, 0], sin_ref[row, 1], sin_ref[row, 2],
                 sin_ref[row, 3])
        Yf, pc, st, ns, at = jax.lax.fori_loop(0, k_steps, step, state)
        for i in range(n_carry):
            yout_ref[0, i] = Yf[i]
        sout_ref[row, 0] = pc
        sout_ref[row, 1] = st
        sout_ref[row, 2] = ns
        sout_ref[row, 3] = at

    return kernel


def _batched_spec_table(pk: _Packing, tab: ScalarTable, b: int, k_steps: int):
    """Operand spec table for _compiled_batched_call (block shape, array
    shape, memory space, grid index map) — the single source for both the
    Mosaic lint and the real pallas_call construction.  Mosaic refuses
    `(1, 4)` SMEM blocks on a `[B, 4]` array; the lint rejects that shape
    off-hardware."""
    from .mosaic_lint import SpecEntry
    meta = pk.meta
    n_const = len(pk.const_idx)
    n_carry = len(pk.carry_idx)
    s = meta.s
    tile = _SMEM_TILE
    b_pad = b + (-b % tile)
    slab = lambda i: (i, 0, 0, 0)
    srow = lambda i: (i // tile, 0)
    ins = [
        (SpecEntry("const_stack", (1, n_const, s, LANES),
                   (b, n_const, s, LANES), "vmem"), slab),
        (SpecEntry("carry_in", (1, n_carry, s, LANES),
                   (b, n_carry, s, LANES), "vmem"), slab),
        (SpecEntry("scalars_in", (tile, 4), (b_pad, 4), "smem"), srow),
        (SpecEntry("scalar_table", (tile, tab.width),
                   (b_pad, tab.width), "smem"), srow),
    ]
    outs = [
        (SpecEntry("carry_out", (1, n_carry, s, LANES),
                   (b, n_carry, s, LANES), "vmem"), slab),
        (SpecEntry("scalars_out", (tile, 4), (b_pad, 4), "smem"), srow),
        (SpecEntry("chosen", (1, k_steps, 1),
                   (b, k_steps, 1), "vmem"), lambda i: (i, 0, 0)),
    ]
    return ins, outs


@functools.lru_cache(maxsize=32)
def _compiled_batched_call(pk: _Packing, tab: ScalarTable, b: int,
                           k_steps: int, max_dnh: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .mosaic_lint import assert_clean

    kernel = _build_batched_kernel(pk, tab, k_steps, max_dnh)
    ins, outs = _batched_spec_table(pk, tab, b, k_steps)
    assert_clean([e for e, _m in ins + outs],
                 f"batched fused kernel b={b} n={pk.meta.n} k={k_steps}")

    spaces = {"vmem": pltpu.VMEM, "smem": pltpu.SMEM}

    def spec(e, index_map):
        return pl.BlockSpec(e.block_shape, index_map,
                            memory_space=spaces[e.memory_space])

    out_shape = [
        jax.ShapeDtypeStruct(outs[0][0].array_shape, jnp.float32),
        jax.ShapeDtypeStruct(outs[1][0].array_shape, jnp.float32),
        jax.ShapeDtypeStruct(outs[2][0].array_shape, jnp.int32),
    ]
    call = pl.pallas_call(
        kernel,
        grid=(b,),
        out_shape=out_shape,
        in_specs=[spec(e, m) for e, m in ins],
        out_specs=[spec(e, m) for e, m in outs],
        interpret=interpret,
    )
    return jax.jit(call)


def _plane_b(mat, s: int, xp=np):
    """[B, N] -> [B, s, 128] zero-padded plane; numpy or jax.numpy."""
    mat = xp.asarray(mat, dtype=xp.float32)
    pad = s * LANES - mat.shape[1]
    if pad:
        mat = xp.concatenate(
            [mat, xp.zeros((mat.shape[0], pad), dtype=xp.float32)], axis=1)
    return mat.reshape(mat.shape[0], s, LANES)


def _pack_carry_batched(pk: _Packing, carry, xp=np):
    """Stacked Carry (leading template axis on every leaf) → planes
    [B, P, S, 128] + scalars [B, 4].  Vectorized over the batch — no
    per-template round-trips; with xp=jax.numpy the whole pack runs on
    device (see _device_batched_carry_packer)."""
    meta = pk.meta
    s, n = meta.s, meta.n
    yi = pk.carry_idx
    planes = [None] * len(yi)

    def put(name, mat):                      # mat: [B, N]
        planes[yi[name]] = _plane_b(mat, s, xp=xp)

    req = xp.asarray(carry.requested)        # [B, N, R]
    for j in range(meta.r):
        put(f"requested{j}", req[:, :, j])
    nz = xp.asarray(carry.nonzero)
    put("nonzero0", nz[:, :, 0])
    put("nonzero1", nz[:, :, 1])
    put("placed", xp.asarray(carry.placed))
    if "sh_cnt0" in yi:
        cnt = xp.asarray(carry.sh_cnt)       # [B, Ch, N]
        for c in range(meta.ch):
            put(f"sh_cnt{c}", cnt[:, c])
    if "ss_cnt0" in yi:
        cnt = xp.asarray(carry.ss_cnt)
        for c in range(meta.cs):
            put(f"ss_cnt{c}", cnt[:, c])
    for stem, arr in (("aff_cnt", carry.aff_cnt), ("anti_cnt", carry.anti_cnt),
                      ("pref_cnt", carry.pref_cnt)):
        if f"{stem}0" in yi:
            a = xp.asarray(arr)              # [B, G, N]
            for gi in range(meta.g):
                put(f"{stem}{gi}", a[:, gi])
    scalars = xp.stack([
        xp.asarray(carry.placed_count, dtype=xp.float32),
        xp.asarray(carry.stopped, dtype=xp.float32),
        xp.asarray(carry.next_start, dtype=xp.float32),
        xp.asarray(carry.aff_total, dtype=xp.float32),
    ], axis=1)
    return xp.stack(planes, axis=1), scalars


@functools.lru_cache(maxsize=32)
def _device_batched_carry_packer(pk: _Packing):
    """On-device batched carry pack (scalars padded to the SMEM tile) — a
    host-side pack would pay one host round trip per carry leaf."""
    import jax
    import jax.numpy as jnp

    def f(carry):
        planes, scalars = _pack_carry_batched(pk, carry, xp=jnp)
        return planes, _pad_rows(scalars, xp=jnp)
    return jax.jit(f)


@functools.lru_cache(maxsize=32)
def _device_batched_const_packer(pk: _Packing, b: int):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda cl: jnp.stack(
        [_pack_consts(pk, c, xp=jnp) for c in cl]))


def _unpack_carry_batched(pk: _Packing, planes, scalars, template):
    """Kernel output → stacked Carry matching the vmapped XLA layout."""
    import jax.numpy as jnp
    meta = pk.meta
    n = meta.n
    yi = pk.carry_idx
    for a in (planes, scalars):              # one round trip, not two
        if hasattr(a, "copy_to_host_async"):
            a.copy_to_host_async()
    pl_np = np.asarray(planes)
    b = pl_np.shape[0]
    flat = pl_np.reshape(b, pl_np.shape[1], -1)[:, :, :n]    # [B, P, N]

    def rows(stem, count):                   # → [B, count, N]
        return np.stack([flat[:, yi[f"{stem}{i}"]] for i in range(count)],
                        axis=1)

    sc = np.asarray(scalars)[:b]             # [B, 4] (tile padding dropped)
    dt = template.requested.dtype
    requested = np.stack([flat[:, yi[f"requested{j}"]]
                          for j in range(meta.r)], axis=2)   # [B, N, R]
    nonzero = np.stack([flat[:, yi["nonzero0"]],
                        flat[:, yi["nonzero1"]]], axis=2)
    return template._replace(
        requested=jnp.asarray(requested, dtype=dt),
        nonzero=jnp.asarray(nonzero, dtype=dt),
        placed=jnp.asarray(flat[:, yi["placed"]].astype(np.int32)),
        sh_cnt=jnp.asarray(rows("sh_cnt", meta.ch), dtype=dt)
        if "sh_cnt0" in yi else template.sh_cnt,
        ss_cnt=jnp.asarray(rows("ss_cnt", meta.cs), dtype=dt)
        if "ss_cnt0" in yi else template.ss_cnt,
        aff_cnt=jnp.asarray(rows("aff_cnt", meta.g), dtype=dt)
        if "aff_cnt0" in yi else template.aff_cnt,
        anti_cnt=jnp.asarray(rows("anti_cnt", meta.g), dtype=dt)
        if "anti_cnt0" in yi else template.anti_cnt,
        pref_cnt=jnp.asarray(rows("pref_cnt", meta.g), dtype=dt)
        if "pref_cnt0" in yi else template.pref_cnt,
        placed_count=jnp.asarray(np.round(sc[:, 0]).astype(np.int32)),
        stopped=jnp.asarray(sc[:, 1] > 0.5),
        next_start=jnp.asarray(np.round(sc[:, 2]).astype(np.int32)),
        aff_total=jnp.asarray(sc[:, 3], dtype=dt),
    )


class BatchedFusedRunner:
    """Drives the batched kernel over a padded template group."""

    def __init__(self, cfg: sim.StaticConfig, pbs: List, consts_list,
                 max_dnh: int, interpret: Optional[bool] = None,
                 pks: Optional[List[_Packing]] = None):
        import jax
        if pks is None:
            pks = [_pack_meta(cfg, pb, None) for pb in pbs]
        # _pad_group's contract: one layout for the whole group
        names0 = (pks[0].const_names, pks[0].carry_names)
        if any((pk.const_names, pk.carry_names) != names0 for pk in pks):
            raise ValueError("non-uniform plane layout in batched group")
        # structural packing: numerics zeroed so the compiled-call cache
        # (and the jit cache behind it) is shared across groups of one shape
        self.pk = pks[0]._replace(meta=_structural_meta(pks[0].meta))
        self.tab = _scalar_table(self.pk)
        self.b = len(pbs)
        self.max_dnh = max(1, max_dnh)
        self.key = BatchedKey(
            shape=(self.pk.const_names, self.pk.carry_names,
                   self.pk.meta.s, self.pk.meta.n, self.pk.meta.cfg,
                   self.max_dnh),
            metas=tuple(pk.meta for pk in pks))
        self.scalar_rows = _pad_rows(np.stack(
            [_scalar_row(self.tab, pk.meta) for pk in pks]))
        self._consts_list = consts_list
        self.const_stack = None
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        self.interpret = interpret

    def pack(self, carry):
        return _device_batched_carry_packer(self.pk)(carry)

    def unpack(self, state, template):
        return _unpack_carry_batched(self.pk, state[0], state[1], template)

    def stopped_flags(self, state) -> np.ndarray:
        """bool[B] per-template stopped flags from the packed scalar plane —
        no plane unpack (the full unpack is a [B, P, S*128] device->host
        round trip; limit-reached sweeps never need it)."""
        return np.asarray(state[1])[:self.b, 1] > 0.5

    def run_packed(self, state, k_steps: int):
        """One fused chunk for the whole group.  Returns (new_state,
        chosen[k_steps, B], all_stopped)."""
        import jax.numpy as jnp
        with obs.span("cc.issue", steps=k_steps, lanes=self.b):
            if self.const_stack is None:
                self.const_stack = _device_batched_const_packer(
                    self.pk, self.b)(tuple(self._consts_list))
                self.scalar_rows_dev = jnp.asarray(self.scalar_rows)
            call = _compiled_batched_call(self.pk, self.tab, self.b, k_steps,
                                          self.max_dnh, self.interpret)
            yout, sout, chosen = call(self.const_stack, state[0], state[1],
                                      self.scalar_rows_dev)
        with obs.span("cc.wait"):
            for a in (sout, chosen):             # one round trip, not two
                if hasattr(a, "copy_to_host_async"):
                    a.copy_to_host_async()
            sc = np.asarray(sout)[:self.b]
            chosen = np.asarray(chosen)[:, :, 0].T          # [k_steps, B]
        fused.STATS["batched_chunks"] = fused.STATS.get("batched_chunks", 0) + 1
        return (yout, sout), chosen, bool((sc[:, 1] > 0.5).all())

    def run_chunk(self, carry, k_steps: int):
        state, chosen, _ = self.run_packed(self.pack(carry), k_steps)
        return self.unpack(state, carry), chosen


_failed_keys: set = set()
_verified_keys: set = set()


def make_batched_runner(cfg: sim.StaticConfig, pbs: List, consts_list,
                        max_dnh: int, verify_against=None
                        ) -> Optional[BatchedFusedRunner]:
    """Build a batched runner when the padded group is kernel-eligible.

    verify_against: (consts_stacked, carry_stacked, steps, xla_run_chunk) —
    cross-checks the kernel's placements against the vmapped XLA step for a
    short prefix, mirroring fused.make_runner's guarantee."""
    if len(pbs) > MAX_BATCH:                 # _batched_solve segments first
        return None
    if not batched_eligible(cfg, pbs):
        return None
    # one _pack_meta pass serves the VMEM check AND the runner (the grid
    # pipeline double-buffers slabs — stricter than fused.eligible's budget)
    pks = [_pack_meta(cfg, pb, None) for pb in pbs]
    if not fused.vmem_ok(pks[0], pipelined=True):
        return None
    from ..runtime.errors import RuntimeFault
    runner = BatchedFusedRunner(cfg, pbs, consts_list, max_dnh, pks=pks)
    key = (runner.key, runner.interpret)
    if key in _failed_keys:
        return None
    if verify_against is not None and key not in _verified_keys:
        with obs.span("cc.verify"):
            v_consts, v_carry, steps, xla_run_chunk = verify_against
            try:
                _f_carry, f_chosen = runner.run_chunk(v_carry, steps)
            except RuntimeFault:
                raise
            except Exception as e:
                _mark_failed(runner, f"{type(e).__name__}: {e}", e)
                return None
            _x_carry, x_chosen = xla_run_chunk(cfg, v_consts, v_carry, steps)
            if not np.array_equal(f_chosen, np.asarray(x_chosen)):
                _mark_failed(runner, "cross-check divergence vs vmapped XLA")
                return None
        _verified_keys.add(key)
    return runner


def _mark_failed(runner: BatchedFusedRunner, why: str,
                 cause: Optional[BaseException] = None) -> None:
    """fused.mark_failed for the batched kernel: raises a KernelFault on
    the chip; in interpret mode disables the group shape."""
    if not runner.interpret:
        from ..runtime.errors import KernelFault
        raise KernelFault(f"batched fused kernel B={runner.b} "
                          f"n={runner.pk.meta.n}: {why}",
                          site="engine.fused_batched") from cause
    import sys
    _failed_keys.add((runner.key, runner.interpret))
    sys.stderr.write(f"cluster_capacity_tpu: batched fused kernel disabled "
                     f"for B={runner.b} n={runner.pk.meta.n} ({why}); "
                     f"using vmapped XLA scan\n")
