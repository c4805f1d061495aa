"""Analytic fast path: the whole greedy simulation as ONE batched solve.

The reference's throughput ceiling is its per-pod event loop — every placement
does a full filter+score pass (schedule_one.go:66-364).  The scan engine
already collapses the event machinery, but still steps sequentially.  This
module removes the sequential loop entirely for the (very common) plugin
configurations where the total score of a node depends only on THAT node's own
placement count:

    total_n(k) = fit(k) + balanced(k) + static_n        (no cross-node
    normalization active: taints uniform, no preferred node affinity, no
    spread/IPA terms)

Then the greedy trace is fully determined by the score matrix
S[n, k] = total score of node n when it hosts its (k+1)-th clone:

- Per-node score sequences are checked (numerically, on device) to be
  non-increasing in k.  When they are, the greedy argmax sequence is exactly
  the descending merge of the N sorted sequences — i.e. sort ALL (n, k) pairs
  by (score desc, node asc); the t-th placement is the t-th pair.  Ties break
  toward the lower node index, matching the deterministic selectHost
  replacement; within a node, equal scores keep k ascending (stable sort), so
  per-node order is respected.
- Capacity = number of pairs with k < cap_n (the fit bound), clipped by
  max_limit.

One sort over ~N*Kmax pairs replaces ~1M scan steps: a 10k-node x 1M-pod
estimate becomes a few device kernels (score matrix + sort + prefix counts).
Falls back to the scan engine whenever eligibility or monotonicity fails —
results are bit-identical either way (validated by tests/test_fast_path.py).
"""

from __future__ import annotations

import functools

from typing import Optional

import numpy as np

from .. import obs
from . import encode as enc
from . import simulator as sim
from ..models.snapshot import IDX_CPU, IDX_PODS


def _uniform_on_eligible(pb: enc.EncodedProblem, raw: np.ndarray
                         ) -> Optional[float]:
    """The single raw value `raw` takes over statically-eligible nodes, or
    None when it varies.  DefaultNormalizeScore runs over the per-step
    FEASIBLE set; feasibility only ever shrinks within the static mask, so
    uniformity there makes the normalized contribution a per-step constant
    (uniform r>0 -> every node floor(100r/r)=100; all-zero -> max==0
    branch), which the analytic solve can fold in."""
    mask = np.asarray(pb.static_mask) & np.asarray(pb.volume_mask)
    vals = np.asarray(raw)[mask]
    if vals.size == 0:
        return 0.0
    first = float(vals[0])
    return first if bool((vals == first).all()) else None


def _structural_eligible(pb: enc.EncodedProblem) -> bool:
    """Filter/score structure the analytic solve can express at all (no
    carried cross-node state); says nothing about normalization constancy."""
    profile = pb.profile
    if not profile.deterministic:
        # the randomized selectHost tie-break emulation lives in the scan only
        return False
    if pb.pod_level_reason is not None:
        return False
    if pb.spread_hard.num_constraints or pb.spread_soft.num_constraints:
        return False
    if pb.ipa.active:
        return False
    if pb.clone_has_host_ports or pb.volume_self_conflict or pb.rwop_self_conflict:
        return False
    if pb.dra_shared_colocate:
        return False
    if sim._num_feasible_nodes_to_find(profile, pb.num_alive) > 0:
        return False
    return True


def eligible(pb: enc.EncodedProblem) -> bool:
    """Static eligibility: every active score must be a pure per-node function
    of that node's own placement count, and every filter static-or-fit."""
    if not _structural_eligible(pb):
        return False
    profile = pb.profile
    # TaintToleration / NodeAffinity normalize over the per-step feasible
    # set — cross-node in general, but a CONSTANT when the raw scores are
    # uniform over the statically-eligible nodes (e.g. dedicated
    # pools where every node carries the same PreferNoSchedule taint, or a
    # preferred term matching every node, now ride the fast path).
    if profile.score_weight("TaintToleration") \
            and _uniform_on_eligible(pb, pb.taint_raw) is None:
        return False
    if profile.score_weight("NodeAffinity") and pb.node_affinity_active \
            and _uniform_on_eligible(pb, pb.node_affinity_raw) is None:
        return False
    return True


def eligible_limited(pb: enc.EncodedProblem) -> bool:
    """Eligibility for the BOUNDED batched analytic solve: taint/NA raw
    uniformity is NOT required — a non-uniform static raw still normalizes
    to a constant per-node vector while some max-raw node stays feasible,
    which holds for the whole run when that node's capacity covers the
    budget.  _fast_batch_chunk verifies that per template and falls back
    when it can't."""
    return _structural_eligible(pb)


def _static_normalized(raw: np.ndarray, caps: np.ndarray, budget: int,
                       reverse: bool, dt) -> Optional[np.ndarray]:
    """DefaultNormalizeScore of a STATIC raw vector, exact for a bounded run:
    the per-step feasible set only ever shrinks (a node leaves when full), so
    the feasible max is constant while a max-raw node remains feasible — and
    a node with cap >= budget can never fill within the run.  Returns the
    normalized dt vector, or None when no max-raw node has cap >= budget.
    Arithmetic mirrors sim._default_normalize op-for-op in dt."""
    feas = caps > 0
    raw_dt = raw.astype(dt)
    hundred = np.asarray(100.0, dtype=dt)
    m = np.max(np.where(feas, raw_dt, np.asarray(0.0, dtype=dt))) \
        if raw_dt.size else np.asarray(0.0, dtype=dt)
    if m > 0:
        holders = feas & (raw_dt == m)
        if not bool((caps[holders] >= budget).any()):
            return None
        scaled = np.floor(hundred * raw_dt / m)
        if reverse:
            scaled = hundred - scaled
    else:
        scaled = np.full_like(raw_dt, 100.0) if reverse else raw_dt
    return scaled


def _per_node_caps(pb: enc.EncodedProblem) -> np.ndarray:
    """Max clones each node can take under the fit filter (and pod slots)."""
    snap = pb.snapshot
    if pb.allocatable is getattr(snap, "allocatable", None) \
            and pb.init_requested is getattr(snap, "requested", None):
        # snapshot-owned arrays (no virtual columns): the free matrix is
        # template-independent — compute once per snapshot
        free = snap.memo(("free_matrix",),
                         lambda: pb.allocatable - pb.init_requested)
    else:
        free = pb.allocatable - pb.init_requested
    caps = np.maximum(pb.allocatable[:, IDX_PODS]
                      - pb.init_requested[:, IDX_PODS], 0.0)
    if pb.profile.filter_enabled("NodeResourcesFit"):
        for j in range(pb.req_vec.shape[0]):
            if j != IDX_PODS and pb.req_vec[j] > 0:
                caps = np.minimum(caps, np.floor(
                    np.maximum(free[:, j], 0.0) / pb.req_vec[j]))
    else:
        caps = np.minimum(caps, 0.0)  # without fit there is no safe bound
    caps = np.where(pb.static_mask & pb.volume_mask, caps, 0.0)
    return caps.astype(np.int64)


# k-axis floor for the single-problem kernel: caps are clipped to
# max(budget, _K_FLOOR) before the power-of-two rounding, so varying
# max_limit between calls normally lands in the SAME quantized K bucket and
# the jitted kernel is traced exactly once per static config (the retrace
# pin in tests/test_fast_path.py).  Correctness is budget-independent: rows
# are monotone non-increasing and the sort is stable, so a (n, k) pair can
# only be selected after its k lower-k predecessors — the first `budget`
# picks are identical for ANY clip value >= budget.
_K_FLOOR = 1024

# Trace-time log of the single-problem kernel: the factory key is appended
# from INSIDE the traced body, so it grows only when jax actually retraces —
# the observable the retrace-pin test asserts on.
_trace_events: list = []


def trace_count() -> int:
    """How many times the single-problem analytic kernel has been traced in
    this process (test hook: must not grow across explain/bounds/max_limit
    kwarg changes on the same static config)."""
    return len(_trace_events)


@functools.lru_cache(maxsize=64)
def _fast_solve_device(strategy: str, fit_shape, K: int, n: int,
                       w_fit: float, w_bal: float, add_t: bool, add_na: bool,
                       w_il: float, dt_name: str):
    """One jitted kernel for the single-problem analytic solve: fused score
    construction, monotonicity check and masked flat scores, with the
    per-plugin fit/balanced component matrices returned unconditionally so
    explain on/off shares the SAME trace.  Selection deliberately stays on
    the host — numpy's stable argsort is ~10x faster than XLA:CPU's stable
    sort on the [N*K] key vector, and the kernel returning `flat` instead
    of placements keeps the sort out of the traced region entirely.

    Everything value-like (taint/NA folded constants, the image-locality
    vector, per-node caps) enters as a runtime argument; only genuine
    structure (strategy, weights, shapes, dtype) is baked into the trace —
    so kwarg churn on solve_fast cannot re-enter the tracer."""
    import jax
    import jax.numpy as jnp

    dt = jnp.float64 if dt_name == "float64" else jnp.float32
    key = (strategy, fit_shape, K, n, w_fit, w_bal, add_t, add_na,
           w_il, dt_name)

    @jax.jit
    def run(alloc_f, base_f, inc_f, freq, fit_w,
            alloc_b, base_b, inc_b, breq, t_c, na_c, il, caps):
        _trace_events.append(key)       # trace-time only: the retrace pin
        k_axis = jnp.arange(K, dtype=dt)
        total = jnp.zeros((n, K), dtype=dt)
        comp_fit = comp_bal = jnp.zeros((0, 0), dtype=dt)

        if w_fit:
            # [N, K, R] lazily broadcast; the score reductions run over the
            # trailing axis, so XLA fuses the construction without
            # materializing the operands.  Arithmetic (dtype, op order)
            # mirrors the scan step exactly — placements stay bit-identical.
            req = base_f.astype(dt)[:, None, :] \
                + inc_f.astype(dt)[None, None, :] * k_axis[None, :, None] \
                + freq.astype(dt)[None, None, :]
            a3 = alloc_f.astype(dt)[:, None, :]
            if strategy == "MostAllocated":
                from ..ops.node_resources_fit import most_allocated_score
                s = most_allocated_score(a3, req, fit_w.astype(dt))
            elif strategy == "RequestedToCapacityRatio":
                from ..ops.node_resources_fit import (
                    requested_to_capacity_ratio_score)
                s = requested_to_capacity_ratio_score(
                    a3, req, fit_w.astype(dt), fit_shape[0], fit_shape[1])
            else:
                from ..ops.node_resources_fit import least_allocated_score
                s = least_allocated_score(a3, req, fit_w.astype(dt))
            comp_fit = w_fit * s
            total = total + w_fit * s

        if w_bal:
            from ..ops.node_resources_fit import balanced_allocation_score
            req = base_b.astype(dt)[:, None, :] \
                + inc_b.astype(dt)[None, None, :] * k_axis[None, :, None] \
                + breq.astype(dt)[None, None, :]
            a3 = alloc_b.astype(dt)[:, None, :]
            s = balanced_allocation_score(
                jnp.broadcast_to(a3, req.shape), req)
            comp_bal = w_bal * s
            total = total + w_bal * s

        if add_t:
            total = total + t_c.astype(dt)
        if add_na:
            total = total + na_c.astype(dt)
        if w_il:
            total = total + il.astype(dt)[:, None] * w_il

        valid = k_axis[None, :] < caps.astype(dt)[:, None]
        # Monotonicity check (exactly the property the merge argument needs).
        mono = jnp.all(jnp.where(valid[:, 1:],
                                 total[:, 1:] <= total[:, :-1], True))
        neg_inf = jnp.asarray(-jnp.inf, dt)
        flat = jnp.where(valid, total, neg_inf).reshape(-1)
        return mono, flat, comp_fit, comp_bal

    return run


def _fast_state(pb: enc.EncodedProblem) -> dict:
    """Host-side prep for the analytic solve, memoized on the problem
    instance: static config, per-node caps, the numpy kernel operands
    (nonzero-substituted fit bases, folded taint/NA constants, resolved
    plugin weights) — none of it depends on max_limit/explain, so repeated
    solves of the same problem skip straight to the kernel call."""
    st = pb.__dict__.get("_fast_state_memo")
    if st is not None:
        return st
    sim._ensure_x64(pb.profile)
    cfg = sim.cached_static_config(pb)
    profile = pb.profile
    dt = np.float64 if profile.compute_dtype == "float64" else np.float32
    _z1 = np.zeros((1,), dtype=np.float64)
    _z2 = np.zeros((1, 1), dtype=np.float64)

    w_fit = float(profile.score_weight("NodeResourcesFit") or 0.0)
    alloc_f = base_f = _z2
    inc_f = freq = fit_w = _z1
    if w_fit:
        cols = list(cfg.fit_idx)
        alloc_f = pb.allocatable[:, cols].astype(np.float64)
        base_f = pb.init_requested[:, cols].astype(np.float64)
        inc_f = pb.req_vec[cols].astype(np.float64)
        freq = np.asarray(pb.fit_req, dtype=np.float64)
        # cpu/mem columns use NonZeroRequested (resource_allocation.go:85-91)
        for k, j in enumerate(cols):
            if cfg.fit_nz[k]:
                nzc = 0 if j == IDX_CPU else 1
                base_f[:, k] = pb.init_nonzero[:, nzc]
                inc_f[k] = pb.req_nonzero[nzc]
        fit_w = np.asarray(pb.fit_res_weights, dtype=np.float64)

    w_bal = float(profile.score_weight("NodeResourcesBalancedAllocation")
                  or 0.0)
    alloc_b = base_b = _z2
    inc_b = breq = _z1
    if w_bal:
        bcols = list(cfg.bal_idx)
        alloc_b = pb.allocatable[:, bcols].astype(np.float64)
        base_b = pb.init_requested[:, bcols].astype(np.float64)
        inc_b = pb.req_vec[bcols].astype(np.float64)
        breq = np.asarray(pb.balanced_req, dtype=np.float64)

    # TaintToleration / NodeAffinity fold to per-step constants on the fast
    # path (eligible() proved raw uniformity): reverse-normalized uniform
    # raw r>0 -> 100-floor(100r/r)=0, r==0 -> the max==0 branch scores 100;
    # forward-normalized r>0 -> 100, r==0 -> untouched 0s.
    w_t = float(profile.score_weight("TaintToleration") or 0.0)
    comp_t = None
    if w_t:
        r = _uniform_on_eligible(pb, pb.taint_raw)
        comp_t = (100.0 if not r else 0.0) * w_t
    w_na = float(profile.score_weight("NodeAffinity") or 0.0)
    add_na = bool(w_na and pb.node_affinity_active)
    comp_na = None
    if add_na:
        r = _uniform_on_eligible(pb, pb.node_affinity_raw)
        comp_na = (100.0 if r else 0.0) * w_na

    w_il = float(profile.score_weight("ImageLocality") or 0.0)
    il = _z1
    comp_il = None
    if w_il:
        il = np.asarray(pb.image_locality_score, dtype=np.float64)
        comp_il = il.astype(dt) * np.asarray(w_il, dtype=dt)

    caps_full = _per_node_caps(pb)
    st = {
        "cfg": cfg, "dt": dt, "dt_name": profile.compute_dtype or "float32",
        "caps_full": caps_full, "total_cap": int(caps_full.sum()),
        "w_fit": w_fit, "w_bal": w_bal, "w_il": w_il,
        "add_t": bool(w_t), "add_na": add_na,
        "alloc_f": alloc_f, "base_f": base_f, "inc_f": inc_f,
        "freq": freq, "fit_w": fit_w,
        "alloc_b": alloc_b, "base_b": base_b, "inc_b": inc_b, "breq": breq,
        "t_c": np.asarray(comp_t or 0.0, dtype=dt),
        "na_c": np.asarray(comp_na or 0.0, dtype=dt),
        "il": il,
        "comp_t": comp_t, "comp_na": comp_na, "comp_il": comp_il,
    }
    pb.__dict__["_fast_state_memo"] = st
    return st


def solve_fast(pb: enc.EncodedProblem, max_limit: int = 0,
               explain: bool = False) -> Optional[sim.SolveResult]:
    """Returns a SolveResult identical to sim.solve(), or None when the
    configuration is outside the fast path (caller falls back to the scan).

    The score matrix + monotonicity check run as ONE cached jitted kernel
    (`_fast_solve_device`, keyed on the static config); the stable sort
    runs on the host over the kernel's flat score vector, where numpy's
    stable argsort beats XLA:CPU's sort kernel ~10x.  Host prep and the
    build_consts/static_config products are memoized per problem, so only
    the kernel call and the sort are paid per solve.

    With `explain`, the per-plugin components of the score matrix (returned
    by the same kernel — no retrace) are gathered on the host at the chosen
    (node, k) pairs to produce the why-here attribution, and the
    reconstructed terminal carry feeds the why-not reason codes — both
    bit-matching what the scan engine's explain path computes step by step
    (tests/test_explain.py parity)."""
    import jax.numpy as jnp

    if not eligible(pb):
        return None

    n = pb.snapshot.num_nodes
    if n == 0:
        return None
    with obs.span("cc.setup"):
        st = _fast_state(pb)
        total_cap = st["total_cap"]
        if total_cap == 0:
            # nothing places: reuse the scan path for exact diagnosis
            return None
        # Mirror the scan's budget exactly, including its unlimited-run cap
        # (simulator.py solve(): min(hint+1, _DEFAULT_UNLIMITED_CAP)).
        budget = total_cap if not max_limit else min(max_limit, total_cap)
        budget = min(budget, sim._DEFAULT_UNLIMITED_CAP)
        # A node can never take more clones than the whole budget → clip
        # before sizing the score matrix (bounds memory for small-limit
        # queries); the _K_FLOOR + power-of-two rounding keep the clip off
        # the jit cache key.
        caps = np.minimum(st["caps_full"], max(budget, _K_FLOOR))
        k_max = int(caps.max())
        K = 1 << max(0, k_max - 1).bit_length()
    dt = st["dt"]

    with obs.span("cc.issue"):
        run = _fast_solve_device(
            st["cfg"].fit_strategy_type, st["cfg"].fit_shape, K, n,
            st["w_fit"], st["w_bal"], st["add_t"], st["add_na"], st["w_il"],
            st["dt_name"])
        mono, flat, comp_fit, comp_bal = run(
            st["alloc_f"], st["base_f"], st["inc_f"], st["freq"],
            st["fit_w"], st["alloc_b"], st["base_b"], st["inc_b"],
            st["breq"], st["t_c"], st["na_c"], st["il"],
            caps.astype(np.int32))
    with obs.span("cc.wait"):
        if not bool(mono):
            return None
        flat_np = np.asarray(flat)

    # Sort all valid pairs by (score desc, node asc, k asc).  The flat index
    # is node-major, so a STABLE sort on -score alone yields exactly that
    # order — the same (max score, lowest node index) rule the scan's argmax
    # applies step by step.  Invalid slots were masked to -inf (-> +inf
    # after negation: last), and any two stable sorts over identical keys
    # produce the identical permutation, so the selection matches the old
    # on-device argsort bit-for-bit.
    with obs.span("cc.fast.sort"):
        order = np.argsort(-flat_np, kind="stable")
        chosen_nodes = order[:budget] // K
        placements = chosen_nodes.astype(np.int64).tolist()
    placed = len(placements)

    # Reconstruct the final carry once: the exhausted branch diagnoses from
    # it and the explain path computes terminal why-not codes from it.
    carry = None
    counts = None
    consts = None
    if explain or placed >= total_cap:
        consts = sim.cached_consts(pb)
        counts = np.bincount(placements, minlength=n) if placements else \
            np.zeros(n, dtype=np.int64)
        final_requested = pb.init_requested + np.outer(counts, pb.req_vec)
        final_nonzero = pb.init_nonzero + np.outer(counts, pb.req_nonzero)
        carry = sim._init_carry(pb, consts, pb.profile.seed)
        carry = carry._replace(
            requested=jnp.asarray(final_requested, dtype=dt),
            nonzero=jnp.asarray(final_nonzero, dtype=dt),
            placed=jnp.asarray(counts, dtype=jnp.int32),
            placed_count=jnp.asarray(placed, dtype=jnp.int32),
            stopped=jnp.asarray(True))

    expl_obj = None
    if explain:
        comp = {}
        if st["w_fit"]:
            comp["NodeResourcesFit"] = np.asarray(comp_fit)
        if st["w_bal"]:
            comp["NodeResourcesBalancedAllocation"] = np.asarray(comp_bal)
        if st["comp_t"] is not None:
            comp["TaintToleration"] = st["comp_t"]
        if st["comp_na"] is not None:
            comp["NodeAffinity"] = st["comp_na"]
        if st["comp_il"] is not None:
            comp["ImageLocality"] = st["comp_il"]
        expl_obj = _explain_fast(pb, st["cfg"], consts, carry, comp, order,
                                 chosen_nodes, caps, counts, placements, dt)

    if max_limit and placed >= max_limit:
        return sim.SolveResult(
            placements=placements, placed_count=placed,
            fail_type=sim.FAIL_LIMIT_REACHED,
            fail_message=f"Maximum number of pods simulated: {max_limit}",
            node_names=pb.snapshot.node_names, explain=expl_obj)
    if placed < total_cap:
        # the _DEFAULT_UNLIMITED_CAP clamp stopped us (scan parity message)
        return sim.SolveResult(
            placements=placements, placed_count=placed,
            fail_type=sim.FAIL_LIMIT_REACHED,
            fail_message=(f"Simulation step budget exhausted after "
                          f"{placed} placements; set max_limit to "
                          f"bound unlimited profiles"),
            node_names=pb.snapshot.node_names, explain=expl_obj)

    # Exhausted capacity → diagnose from the reconstructed final state.
    reason_counts = sim.diagnose(pb, st["cfg"], consts, carry)
    msg = sim.format_fit_error(n, reason_counts)
    return sim.SolveResult(
        placements=placements, placed_count=placed,
        fail_type=sim.FAIL_UNSCHEDULABLE, fail_message=msg,
        fail_counts=reason_counts, node_names=pb.snapshot.node_names,
        explain=expl_obj)


def _explain_fast(pb, cfg, consts, carry, comp, order, chosen_nodes, caps,
                  counts, placements, dt):
    """Assemble the fast path's Explanation: why-here gathered ON THE HOST
    from the kernel-returned score components (pure gathers — values
    identical to the old on-device path), why-not from the reconstructed
    terminal carry, elimination steps from the per-node fill times (a node
    leaves the feasible set at the step after its cap fills — there is no
    other elimination channel in a fast-path-eligible config)."""
    import jax.numpy as jnp
    from ..explain import artifacts as _art
    from ..explain import attribution as _attr

    n = pb.snapshot.num_nodes
    budget = chosen_nodes.shape[0]
    flat_sel = order[:budget]
    why_cols = []
    for name in _art.PLUGINS:
        v = comp.get(name)
        if v is None:
            why_cols.append(np.zeros((budget,), dtype=dt))
        elif getattr(v, "ndim", 0) == 2:
            why_cols.append(np.asarray(v).reshape(-1)[flat_sel])
        elif getattr(v, "ndim", 0) == 1:
            why_cols.append(np.asarray(v)[chosen_nodes])
        else:       # folded per-step constant (taint / node-affinity)
            why_cols.append(np.full((budget,), v, dtype=dt))
    why_here = np.stack(why_cols, axis=1).astype(np.float64)

    codes, insuff, toomany = _attr.final_codes_runner()(
        cfg, consts, jnp.asarray(pb.static_code, dtype=jnp.int32), carry)
    codes = np.asarray(codes)

    # Elimination record: caps==0 nodes were never feasible (step 0); a
    # filled node is first seen infeasible at the step AFTER its last fill.
    elim_step = np.full(n, -1, dtype=np.int32)
    elim_code = np.zeros(n, dtype=np.int32)
    eliminated = codes != enc.CODE_OK
    elim_code[eliminated] = codes[eliminated]
    elim_step[eliminated & (caps == 0)] = 0
    filled = eliminated & (caps > 0) & (counts >= caps)
    if filled.any():
        cnt = np.zeros(n, dtype=np.int64)
        for t, node in enumerate(placements):
            cnt[node] += 1
            if filled[node] and cnt[node] == caps[node]:
                elim_step[node] = t + 1

    return _art.build_explanation(
        pb, why_here=why_here, final_codes=codes,
        elim_step=elim_step, elim_code=elim_code,
        insufficient=np.asarray(insuff), too_many=np.asarray(toomany),
        rung="fast_path")


def solve_auto(pb: enc.EncodedProblem, max_limit: int = 0,
               chunk_size: int = 1024, explain: bool = False,
               bounds: bool = True) -> sim.SolveResult:
    """Fast path when exact, scan engine otherwise — identical results."""
    result = solve_fast(pb, max_limit=max_limit, explain=explain)
    if result is not None:
        return result
    return sim.solve(pb, max_limit=max_limit, chunk_size=chunk_size,
                     explain=explain, bounds=bounds)


# --------------------------------------------------------------------------
# Batched analytic solve: B small-limit templates in one argsort
# --------------------------------------------------------------------------
# A what-if sweep with a small per-template limit (BASELINE config 5's
# limit-3 probes) spends its time stepping the scan engine B times for a
# question the analytic path answers with a [B, N, K] score tensor and ONE
# stable argsort over [B, N*K].  Score arithmetic mirrors solve_fast
# component-for-component in the same dtype and addition order, so the
# placements are bit-identical (tests/test_sweep.py differential).

_ELEM_BUDGET = 1 << 27          # max B*N*K elements materialized per chunk


def solve_fast_batched(pbs, max_limit: int):
    """Solve B eligible templates (uniform StaticConfig group) at a small
    max_limit.  Returns a list aligned with pbs; None entries mean "fall
    back to solve_auto" (zero capacity -> needs scan diagnosis, or a
    monotonicity failure)."""
    out = [None] * len(pbs)
    if not max_limit or max_limit <= 0 or not pbs:
        return out
    n = pbs[0].snapshot.num_nodes
    if n == 0:
        return out
    sim._ensure_x64(pbs[0].profile)
    cfg = sim.static_config(pbs[0])

    caps_list, budgets, act = [], [], []
    for b, pb in enumerate(pbs):
        caps = _per_node_caps(pb)
        tc = int(caps.sum())
        if tc < max_limit:
            # zero capacity, or capacity exhausts before the limit: either
            # way the template needs the scan's exact diagnosis — running
            # it through the kernel would only discard the result
            continue
        budget = min(max_limit, tc, sim._DEFAULT_UNLIMITED_CAP)
        caps_list.append(np.minimum(caps, budget))
        budgets.append(budget)
        act.append(b)
    if not act:
        return out

    k_hint = int(max(c.max() for c in caps_list))
    chunk = max(1, _ELEM_BUDGET // max(1, n * k_hint))
    for s in range(0, len(act), chunk):
        res = _fast_batch_chunk(
            [pbs[i] for i in act[s:s + chunk]], caps_list[s:s + chunk],
            budgets[s:s + chunk], cfg, max_limit)
        for i, r in zip(act[s:s + chunk], res):
            out[i] = r
    return out


def _unique_rows(rows, n: int, dt):
    """Dedup per-template [N] vectors by identity/constant value: returns
    (unique [U, N] dt, idx i32[B]).  Entries are either ('const', v) or a
    numpy vector (snapshot-memoized objects dedup by id)."""
    uniq: list = []
    keymap: dict = {}
    idx = np.zeros(len(rows), dtype=np.int32)
    for bi, r in enumerate(rows):
        key = r if isinstance(r, tuple) else id(r)
        u = keymap.get(key)
        if u is None:
            u = len(uniq)
            keymap[key] = u
            uniq.append(np.full(n, r[1], dtype=dt) if isinstance(r, tuple)
                        else np.asarray(r, dtype=dt))
        idx[bi] = u
    return np.stack(uniq), idx


import functools


# Bounded: under --watch mode every snapshot delta can shift K (the max
# per-node capacity), and an unbounded cache would accumulate one compiled
# executable per distinct K for the life of the process.  Callers quantize
# K to the next power of two so nearby capacities share an entry.
@functools.lru_cache(maxsize=64)
def _fast_batch_device(strategy: str, fit_shape, K: int, m: int, n: int,
                       w_fit: float, w_bal: float, w_t: float, w_na: float,
                       w_il: float, dt_name: str):
    """One jitted kernel for the whole batched analytic solve: fused score
    construction (shared [N, R] inputs + per-template [B, R] vectors — no
    [B, N, ...] host stacks), monotonicity check, and top-m selection."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    dt = jnp.float64 if dt_name == "float64" else jnp.float32

    @jax.jit
    def run(alloc_f, base_f, inc_f, freq, fit_w,
            alloc_b, base_b, inc_b, breq,
            t_u, t_ix, na_u, na_ix, il_u, il_ix, caps):
        B = caps.shape[0]
        k_axis = jnp.arange(K, dtype=dt)
        total = jnp.zeros((B, n, K), dtype=dt)

        if w_fit:
            # [B, N, K, R] lazily broadcast — the score reductions run over
            # the trailing axis, so XLA fuses the whole construction without
            # materializing the 4-D operands (no reshape in the chain).
            req = base_f.astype(dt)[None, :, None, :] \
                + inc_f.astype(dt)[:, None, None, :] \
                * k_axis[None, None, :, None] \
                + freq.astype(dt)[:, None, None, :]
            a4 = alloc_f.astype(dt)[None, :, None, :]
            if strategy == "MostAllocated":
                from ..ops.node_resources_fit import most_allocated_score
                s = most_allocated_score(a4, req, fit_w.astype(dt))
            elif strategy == "RequestedToCapacityRatio":
                from ..ops.node_resources_fit import (
                    requested_to_capacity_ratio_score)
                s = requested_to_capacity_ratio_score(
                    a4, req, fit_w.astype(dt), fit_shape[0], fit_shape[1])
            else:
                from ..ops.node_resources_fit import least_allocated_score
                s = least_allocated_score(a4, req, fit_w.astype(dt))
            total = total + w_fit * s

        if w_bal:
            from ..ops.node_resources_fit import balanced_allocation_score
            req = base_b.astype(dt)[None, :, None, :] \
                + inc_b.astype(dt)[:, None, None, :] \
                * k_axis[None, None, :, None] \
                + breq.astype(dt)[:, None, None, :]
            a4 = alloc_b.astype(dt)[None, :, None, :]
            s = balanced_allocation_score(jnp.broadcast_to(a4, req.shape), req)
            total = total + w_bal * s

        if w_t:
            total = total + (w_t * t_u)[t_ix][:, :, None]
        if w_na:
            total = total + (w_na * na_u)[na_ix][:, :, None]
        if w_il:
            total = total + il_u[il_ix][:, :, None] * w_il

        capsf = caps.astype(dt)
        valid = k_axis[None, None, :] < capsf[:, :, None]
        mono = jnp.all(jnp.where(valid[:, :, 1:],
                                 total[:, :, 1:] <= total[:, :, :-1], True),
                       axis=(1, 2))
        neg_inf = jnp.asarray(-jnp.inf, dt)
        flat = jnp.where(valid, total, neg_inf).reshape(B, n * K)
        # Only the first max_limit placements are consumed, and ties must
        # break toward the LOWER flat index — the (score desc, node asc,
        # k asc) order solve_fast's stable argsort encodes (the flat axis is
        # node-major).  For small m, m masked-argmax passes (single-pass
        # reductions; argmax takes the first maximum) beat XLA CPU's TopK
        # (a per-row sort); larger m uses TopK (also lower-index-first).
        if m <= 32:
            def body(fl, _):
                idx = jnp.argmax(fl, axis=1)              # [B]
                fl = fl.at[jnp.arange(fl.shape[0]), idx].set(neg_inf)
                return fl, idx
            _fl, idxs = lax.scan(body, flat, None, length=m)
            order_m = idxs.T                              # [B, m]
        else:
            _vals, order_m = lax.top_k(flat, m)
        node_ids = jnp.repeat(jnp.arange(n, dtype=jnp.int32), K)
        chosen = node_ids[order_m]                        # [B, m]
        return mono, chosen

    return run


def _fast_batch_chunk(sub, caps_list, budgets, cfg, max_limit: int):
    B = len(sub)
    n = sub[0].snapshot.num_nodes
    K = int(max(c.max() for c in caps_list))
    profile = sub[0].profile
    dt = np.float64 if profile.compute_dtype == "float64" else np.float32
    drop = [False] * B                   # per-template fallback to solve_auto
    _z1 = np.zeros((1,), dtype=np.float64)
    _z2 = np.zeros((1, 1), dtype=np.float64)
    _zi = np.zeros(B, dtype=np.int32)

    # ---- fit inputs: base/alloc are snapshot-shared, inc/freq per template
    w_fit = float(profile.score_weight("NodeResourcesFit") or 0.0)
    alloc_f = base_f = _z2
    inc_f = freq = _z2
    fit_w = _z1
    if w_fit:
        cols = list(cfg.fit_idx)
        if not _shared_columns(sub, cols):
            return [None] * B             # virtual-column divergence: rare
        pb0 = sub[0]
        alloc_f = pb0.allocatable[:, cols].astype(np.float64)
        base_f = pb0.init_requested[:, cols].astype(np.float64)
        inc_f = np.stack([pb.req_vec[cols] for pb in sub]).astype(np.float64)
        freq = np.stack([pb.fit_req for pb in sub]).astype(np.float64)
        for k, j in enumerate(cols):
            if cfg.fit_nz[k]:
                nzc = 0 if j == IDX_CPU else 1
                base_f[:, k] = pb0.init_nonzero[:, nzc]
                for bi, pb in enumerate(sub):
                    inc_f[bi, k] = pb.req_nonzero[nzc]
        fit_w = np.asarray(pb0.fit_res_weights, dtype=np.float64)

    w_bal = float(profile.score_weight("NodeResourcesBalancedAllocation")
                  or 0.0)
    alloc_b = base_b = inc_b = breq = _z2
    if w_bal:
        bcols = list(cfg.bal_idx)
        if not _shared_columns(sub, bcols):
            return [None] * B
        pb0 = sub[0]
        alloc_b = pb0.allocatable[:, bcols].astype(np.float64)
        base_b = pb0.init_requested[:, bcols].astype(np.float64)
        inc_b = np.stack([pb.req_vec[bcols] for pb in sub]).astype(np.float64)
        breq = np.stack([pb.balanced_req for pb in sub]).astype(np.float64)

    # ---- static per-node score rows, deduped by identity/constant --------
    norm_cache: dict = {}

    def _row_entries(raw_of, reverse: bool, active_of):
        entries = []
        for bi, pb in enumerate(sub):
            if not active_of(pb):
                entries.append(("const", 0.0))
                continue
            raw = raw_of(pb)
            r = _uniform_on_eligible(pb, raw)
            if r is not None:
                on = (not r) if reverse else bool(r)
                entries.append(("const", 100.0 if on else 0.0))
                continue
            sn = _static_normalized(raw, caps_list[bi], budgets[bi],
                                    reverse=reverse, dt=dt)
            if sn is None:
                drop[bi] = True
                entries.append(("const", 0.0))
            else:
                key = (id(raw), reverse)
                cached = norm_cache.get(key)
                if cached is not None and np.array_equal(cached, sn):
                    sn = cached            # stable id across templates
                else:
                    norm_cache[key] = sn
                entries.append(sn)
        return entries

    w_t = float(profile.score_weight("TaintToleration") or 0.0)
    t_u, t_ix = (_z2, _zi)
    if w_t:
        t_u, t_ix = _unique_rows(
            _row_entries(lambda pb: pb.taint_raw, True, lambda pb: True),
            n, dt)
    w_na = float(profile.score_weight("NodeAffinity") or 0.0)
    na_u, na_ix = (_z2, _zi)
    if w_na:
        na_u, na_ix = _unique_rows(
            _row_entries(lambda pb: pb.node_affinity_raw, False,
                         lambda pb: pb.node_affinity_active), n, dt)
    w_il = float(profile.score_weight("ImageLocality") or 0.0)
    il_u, il_ix = (_z2, _zi)
    if w_il:
        il_u, il_ix = _unique_rows([pb.image_locality_score for pb in sub],
                                   n, dt)

    caps = np.stack(caps_list).astype(np.int32)
    m = min(max_limit, n * K)
    # Quantize the k-axis extent to the next power of two: `valid = k < caps`
    # masks the padded slots to -inf and the node-major flat order is
    # unchanged, so selection is bit-identical while snapshots with nearby
    # max capacities share one compiled kernel (m stays derived from the
    # true K so the scan-vs-top_k branch choice is unaffected).
    K = 1 << max(0, K - 1).bit_length()
    run = _fast_batch_device(
        cfg.fit_strategy_type, cfg.fit_shape, K, m, n,
        w_fit, w_bal, w_t, w_na, w_il, profile.compute_dtype or "float32")
    mono, chosen = run(alloc_f, base_f, inc_f, freq, fit_w,
                       alloc_b, base_b, inc_b, breq,
                       t_u, t_ix, na_u, na_ix, il_u, il_ix, caps)

    mono_np = np.asarray(mono)
    chosen_np = np.asarray(chosen)
    results = []
    for bi, pb in enumerate(sub):
        if drop[bi] or not bool(mono_np[bi]) or budgets[bi] < max_limit:
            # normalization constancy unprovable, monotonicity failed, or
            # capacity exhausts before the limit (needs the exact diagnose)
            # -> per-template fallback
            results.append(None)
            continue
        placements = chosen_np[bi, :budgets[bi]].astype(np.int64).tolist()
        results.append(sim.SolveResult(
            placements=placements, placed_count=len(placements),
            fail_type=sim.FAIL_LIMIT_REACHED,
            fail_message=f"Maximum number of pods simulated: {max_limit}",
            node_names=pb.snapshot.node_names))
    return results


def _shared_columns(sub, cols) -> bool:
    """True when every template's allocatable/init_requested (restricted to
    the selected strategy columns) and init_nonzero agree — the condition
    for passing them to the device once, unbatched.  Virtual resource
    columns OUTSIDE `cols` may differ freely."""
    pb0 = sub[0]
    for pb in sub[1:]:
        for fld in ("allocatable", "init_requested"):
            a, b = getattr(pb, fld), getattr(pb0, fld)
            if a is not b and not np.array_equal(a[:, cols], b[:, cols]):
                return False
        a, b = pb.init_nonzero, pb0.init_nonzero
        if a is not b and not np.array_equal(a, b):
            return False
    return True
