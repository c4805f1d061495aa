"""Batched capacity bracketing: fractional upper + auction lower bounds.

The engine reproduces the reference's one-clone-at-a-time greedy loop, so a
capacity question costs a full scan even when a relaxation could prove the
answer.  This module computes, per encoded problem:

- *Upper bound*: the LP-style fractional relaxation of the fit encodings —
  per-node headroom ÷ per-clone demand, min over resource dimensions and pod
  slots — tightened by the per-node integer floor (any schedule places at
  most floor(headroom/demand) clones on a node) and by every hard topology-
  spread constraint folded as a row cap over its domain capacities.
- *Lower bound*: a constructive first-fit pass — with a single template the
  per-node floors ARE a feasible schedule; for template mixes a K-round
  vectorized auction (`auction_device`): nodes bid headroom, templates claim
  greedily round-robin against the shared free matrix, every claim feasible
  by construction.

Soundness under f32: the host bracket shares fast_path._per_node_caps's
f64 floor formula bit-for-bit, so for fit-only problems it does not
approximate the engine — it IS the engine's arithmetic.  The device kernel
computes the same floors in f32, where a rounding flip across an integer
boundary is possible, so `bracket_group` parity-checks every device shot
against the host recomputation and discards (degrades to host) on any
mismatch: a bracket is only ever used when it bit-matches the f64 oracle
(tests/test_bounds.py differential-fuzzes ``lower <= simulated <= upper``).

Exactness: for fit-only shapes (`exact_capacity` — no dynamic gate beyond
NodeResourcesFit, deterministic, full sampling; exactly the family the
resilience analyzer batches via `_mask_exact`) greedy capacity equals the
sum of per-node fit caps regardless of scoring order, so the bracket is
tight and the terminal FitError histogram is a pure function of the caps
(`exhausted_fit_counts`) — which is what lets resilience/analyzer.py skip
whole device solves and still emit row-identical results.

Dispatch discipline: `bracket_device` / `auction_device` are dispatch-set
members (tools/irgate GD001) — call them only through runtime/guard.run
under faults.SITE_BOUNDS, the way `bracket_group` / `bracket_mix` do; both
carry an oracle-side host recomputation (`bracket_host`, `_auction_host`)
used for parity checking and as the fault-degraded fallback.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..engine import encode as enc
from ..engine import simulator as sim
from ..models.snapshot import IDX_PODS

# No finite bound exists (fit filter off: nothing limits placements).
# Mirrors the scan engine's unlimited budget cap so a bracket never promises
# more than the engine could count — and so pruning can refuse shapes whose
# unbounded run would end with the budget-exhausted message instead of a
# FitError.
UNBOUNDED = sim._DEFAULT_UNLIMITED_CAP

_BIG = np.float32(3.0e38)


@dataclass(frozen=True)
class CapacityBracket:
    """lower <= true greedy capacity <= upper.  `frac` keeps the raw LP
    relaxation value (pre-floor) for reporting; `exact` records that the
    problem met the `exact_capacity` gates, under which `tight` brackets
    equal the scan's placed count bit-for-bit."""

    lower: int
    upper: int
    exact: bool
    frac: float = 0.0
    method: str = "frac+ffd"

    @property
    def tight(self) -> bool:
        return self.exact and self.lower == self.upper


def _free_matrix(pb: enc.EncodedProblem) -> np.ndarray:
    snap = pb.snapshot
    if pb.allocatable is getattr(snap, "allocatable", None) \
            and pb.init_requested is getattr(snap, "requested", None):
        # snapshot-owned arrays: share fast_path._per_node_caps's memo
        return snap.memo(("free_matrix",),
                         lambda: pb.allocatable - pb.init_requested)
    return pb.allocatable - pb.init_requested


def _host_planes(pb: enc.EncodedProblem) -> Tuple[np.ndarray, np.ndarray]:
    """(frac, gate): per-node fractional fit headroom (f64, pre-floor) and
    the static&volume gate.  Pre-floor twin of fast_path._per_node_caps."""
    free = _free_matrix(pb)
    frac = np.maximum(pb.allocatable[:, IDX_PODS]
                      - pb.init_requested[:, IDX_PODS], 0.0).astype(np.float64)
    for j in range(pb.req_vec.shape[0]):
        if j != IDX_PODS and pb.req_vec[j] > 0:
            frac = np.minimum(frac, np.maximum(free[:, j], 0.0)
                              / pb.req_vec[j])
    gate = np.asarray(pb.static_mask) & np.asarray(pb.volume_mask)
    return np.where(gate, frac, 0.0), gate


def _fit_only(pb: enc.EncodedProblem) -> bool:
    """No dynamic gate beyond NodeResourcesFit: greedy capacity equals the
    sum of per-node fit caps regardless of scoring order, so the per-node
    floors double as a constructive (lower-bound) schedule."""
    return (pb.profile.filter_enabled("NodeResourcesFit")
            and not pb.profile.extenders
            and pb.pod_level_reason is None
            and not pb.clone_has_host_ports
            and not pb.volume_self_conflict
            and not pb.rwop_self_conflict
            and not pb.dra_shared_colocate
            and not np.asarray(pb.shared_req_vec).any()
            and pb.spread_hard.num_constraints == 0
            and not pb.ipa.active
            and not np.asarray(pb.ipa.existing_anti_static).any())


def exact_capacity(pb: enc.EncodedProblem) -> bool:
    """Gates under which lower == upper is provable AND a pruned row's fail
    message is recomputable on the host: fit-only capacity plus an order-
    independent terminal (deterministic profile, full sampling) — the same
    family resilience/analyzer._mask_exact admits to the batched solve."""
    profile = pb.profile
    return (_fit_only(pb)
            and profile.deterministic
            and not profile.adaptive_sampling
            and profile.percentage_of_nodes_to_score >= 100
            and sim._num_feasible_nodes_to_find(profile, pb.num_alive) == 0)


def _spread_fold_host(pb: enc.EncodedProblem, caps_up: np.ndarray) -> float:
    """Every hard spread constraint folded as a row cap on the upper bound.

    self-matching constraints evolve with placements: with m = min over
    valid domains of (existing + domain capacity) — an overestimate of the
    final global min — a domain d can absorb at most
    max(0, m + maxSkew - existing_d) clones (each placement passes the
    per-step skew check against a min that only grows), capped by the
    domain's fit capacity; nodes missing the key are infeasible.  Constraints
    the clone does NOT match keep static counts, so the fold is the initial
    violation mask.  minDomains below the valid-domain count zeroes the min
    term, mirroring ops/pod_topology_spread.hard_filter."""
    sh = pb.spread_hard
    if sh.num_constraints == 0:
        return float("inf")
    dom = np.asarray(sh.node_domain)
    e = np.asarray(sh.init_counts, dtype=np.float64)
    valid = np.asarray(sh.domain_valid)
    best = float("inf")
    for c in range(sh.num_constraints):
        keyed = dom[c] >= 0
        d_idx = np.clip(dom[c], 0, max(e.shape[1] - 1, 0))
        cap_d = np.zeros(e.shape[1])
        np.add.at(cap_d, d_idx[keyed], caps_up[keyed])
        ndom = int(valid[c].sum())
        skew = float(sh.max_skew[c])
        enough = ndom >= float(sh.min_domains[c])
        if bool(sh.self_match[c]):
            m = float(np.min(np.where(valid[c], e[c] + cap_d, np.inf))) \
                if ndom else 0.0
            m_eff = m if enough else 0.0
            allow = np.maximum(m_eff + skew - e[c], 0.0)
            fold = float(np.sum(np.where(valid[c],
                                         np.minimum(cap_d, allow), cap_d)))
        else:
            m_e = float(np.min(np.where(valid[c], e[c], np.inf))) \
                if ndom else 0.0
            m_eff = m_e if enough else 0.0
            ok = keyed & ~((e[c][d_idx] - m_eff) > skew)
            fold = float(np.sum(caps_up[ok]))
        best = min(best, fold)
    return best


def bracket_host(pb: enc.EncodedProblem) -> CapacityBracket:
    """Oracle-side bracket: f64 numpy, same formulas as the device kernel.
    Used for parity checking every device shot, as the fault-degraded
    fallback, and by the sweep/scan budget clamps (`upper_bound_host`)."""
    if pb.pod_level_reason is not None:
        return CapacityBracket(0, 0, exact=False, method="pod_level")
    if not pb.profile.filter_enabled("NodeResourcesFit"):
        return CapacityBracket(0, UNBOUNDED, exact=False, method="no_fit")
    frac, _gate = _host_planes(pb)
    caps = np.floor(frac)                 # == fast_path._per_node_caps
    upper = float(np.sum(caps))
    lower = upper
    upper = min(upper, _spread_fold_host(pb, caps))
    if not _fit_only(pb):
        # a dynamic gate (spread/IPA/self-conflict/extender/...) can block
        # placements the relaxation admits: the upper bound stays valid,
        # the constructive per-node lower does not
        lower = 0.0
    lower = min(lower, upper)
    return CapacityBracket(int(min(lower, UNBOUNDED)),
                           int(min(upper, UNBOUNDED)),
                           exact=exact_capacity(pb),
                           frac=float(np.sum(frac)))


def upper_bound_host(pb: enc.EncodedProblem) -> int:
    """Fit+spread upper bound for budget right-sizing (host, f64).  Always
    >= the true capacity; UNBOUNDED when no finite bound exists."""
    return bracket_host(pb).upper


# --------------------------------------------------------------------------
# device kernels
# --------------------------------------------------------------------------

def _quantize_batch(b: int) -> int:
    """Pad the scenario/template axis to a power of two so a sweep's varying
    batch sizes share a handful of compiled kernels (the same K-quantization
    fast_path's batched solve uses)."""
    out = 1
    while out < b:
        out *= 2
    return out


@functools.lru_cache(maxsize=16)
def _bracket_runner(num_constraints: int, num_domains: int, mesh=None):
    """Jitted bracket kernel, vmapped over the batch axis.  Static on the
    hard-constraint/domain counts; shapes (N, R, B) specialize via jit.

    With a mesh the same kernel is jitted under explicit in/out shardings:
    the batch axis (scenarios) over the mesh's "batch" axis, the node
    tables over "nodes" — the per-node floors reduce to per-problem scalars
    through XLA cross-shard collectives, so the pruning brackets shard the
    same way the sweep they right-size does (inputs must already be padded
    to the shard multiples; `bracket_device` does that)."""
    import jax
    import jax.numpy as jnp

    def one(free, req, pods_free, gate, dom, e, valid, skew, mindom, selfm):
        pos = req > 0
        ratio = jnp.where(pos[None, :],
                          jnp.maximum(free, 0.0)
                          / jnp.where(pos, req, 1.0)[None, :], _BIG)
        frac = jnp.minimum(jnp.min(ratio, axis=1),
                           jnp.maximum(pods_free, 0.0))
        frac = jnp.where(gate, jnp.maximum(frac, 0.0), 0.0)
        up = jnp.floor(frac)
        upper = jnp.sum(up)
        lower = upper
        # per-node LP headroom: the host sums it in f64, so the bracket's
        # frac does not depend on how a mesh splits the node axis
        lp = frac
        if num_constraints:
            onehot = (dom[:, :, None]
                      == jnp.arange(num_domains, dtype=dom.dtype)[None, None])
            cap_d = jnp.sum(jnp.where(onehot, up[None, :, None], 0.0), axis=1)
            ndom = jnp.sum(valid, axis=1).astype(jnp.float32)
            enough = ndom >= mindom
            m = jnp.min(jnp.where(valid, e + cap_d, _BIG), axis=1)
            m_eff = jnp.where(enough, m, 0.0)
            allow = jnp.maximum(m_eff[:, None] + skew[:, None] - e, 0.0)
            dyn = jnp.sum(jnp.where(valid, jnp.minimum(cap_d, allow), cap_d),
                          axis=1)
            m_e = jnp.min(jnp.where(valid, e, _BIG), axis=1)
            me_eff = jnp.where(enough, m_e, 0.0)
            e_at = jnp.take_along_axis(
                e, jnp.clip(dom, 0, num_domains - 1), axis=1)
            ok = (dom >= 0) & ~((e_at - me_eff[:, None]) > skew[:, None])
            stat = jnp.sum(jnp.where(ok, up[None, :], 0.0), axis=1)
            fold = jnp.min(jnp.where(selfm, dyn, stat))
            upper = jnp.minimum(upper, fold)
            lower = jnp.minimum(lower, upper)
        return lower, upper, lp

    vm = jax.vmap(one)
    if mesh is None:
        return jax.jit(vm)
    in_sh, out_sh = _bracket_shardings(mesh)
    return jax.jit(vm, in_shardings=in_sh, out_shardings=out_sh)


def _bracket_shardings(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..parallel.mesh import BATCH_AXIS, NODE_AXIS

    def s(*parts):
        return NamedSharding(mesh, P(BATCH_AXIS, *parts))

    in_sh = (s(NODE_AXIS, None),             # free [B, N, R]
             s(None),                        # req [B, R]
             s(NODE_AXIS),                   # pods_free [B, N]
             s(NODE_AXIS),                   # gate [B, N]
             s(None, NODE_AXIS),             # dom [B, C, N]
             s(None, None),                  # e [B, C, D]
             s(None, None),                  # valid [B, C, D]
             s(None),                        # skew [B, C]
             s(None),                        # mindom [B, C]
             s(None))                        # selfm [B, C]
    out_sh = (s(), s(), s(NODE_AXIS))        # lower/upper [B], lp [B, N]
    return in_sh, out_sh


def _on_mesh(shardings, args):
    """Host arrays -> global arrays on the mesh.  Every process holds the
    full arrays and supplies its addressable shards: jit refuses host
    arrays with non-trivial shardings once the mesh spans processes."""
    import jax
    return [jax.make_array_from_callback(a.shape, sh, lambda i, a=a: a[i])
            for a, sh in zip(args, shardings)]


def _spread_arrays(pb: enc.EncodedProblem, ch: int, dh: int, n: int):
    """This problem's hard-constraint planes padded to the group maxima
    (ch constraints × dh domains); padded rows are inert (no keyed node,
    no valid domain, huge skew)."""
    sh = pb.spread_hard
    dom = np.full((ch, n), -1, dtype=np.int32)
    e = np.zeros((ch, dh), dtype=np.float32)
    valid = np.zeros((ch, dh), dtype=bool)
    skew = np.full(ch, _BIG, dtype=np.float32)
    mindom = np.zeros(ch, dtype=np.float32)
    selfm = np.zeros(ch, dtype=bool)
    c, d = sh.node_domain.shape[0], sh.init_counts.shape[1]
    if sh.num_constraints:
        dom[:c] = sh.node_domain
        e[:c, :d] = sh.init_counts
        valid[:c, :d] = sh.domain_valid
        skew[:sh.num_constraints] = sh.max_skew[:sh.num_constraints]
        mindom[:c] = sh.min_domains
        selfm[:c] = sh.self_match
    return dom, e, valid, skew, mindom, selfm


def bracket_device(pbs: Sequence[enc.EncodedProblem], *,
                   mesh=None,
                   lower_only: bool = False) -> List[CapacityBracket]:
    """ONE batched device shot bracketing every problem: the fit planes (and
    any hard-spread planes, padded to group maxima) stack on a quantized
    leading axis and run through the vmapped kernel.  Problems must share
    the node/resource axes (the analyzer's scenario family and a sweep's
    template group both do).

    With a mesh the planes are padded to the shard multiples (pad scenarios
    are all-infeasible rows whose outputs are never read; pad nodes are
    gate-False, domainless — zero-capacity, so every reduction ignores
    them) and the shot runs under the sharded runner.  The host parity
    check in `bracket_group` covers the sharded shot the same as the
    unsharded one.

    Dispatch-set member (tools/irgate GD001): route every call through
    runtime/guard.run under faults.SITE_BOUNDS — `bracket_group` is the
    guarded entry."""
    pbs = list(pbs)
    if not pbs:
        return []
    n = pbs[0].snapshot.num_nodes
    r = pbs[0].req_vec.shape[0]
    for pb in pbs:
        if pb.snapshot.num_nodes != n or pb.req_vec.shape[0] != r:
            raise ValueError("bracket_device needs uniform node/resource "
                             "axes across the batch")
    ch = max(pb.spread_hard.node_domain.shape[0] for pb in pbs)
    ch = max(ch, max(pb.spread_hard.num_constraints for pb in pbs))
    dh = max(max(pb.spread_hard.init_counts.shape[1] for pb in pbs), 1)
    any_spread = any(pb.spread_hard.num_constraints for pb in pbs)

    b = len(pbs)
    bq = _quantize_batch(b)
    free = np.zeros((bq, n, r), dtype=np.float32)
    req = np.zeros((bq, r), dtype=np.float32)
    pods_free = np.zeros((bq, n), dtype=np.float32)
    gate = np.zeros((bq, n), dtype=bool)
    c_eff = ch if any_spread else 0
    dom = np.full((bq, c_eff, n), -1, dtype=np.int32)
    e = np.zeros((bq, c_eff, dh), dtype=np.float32)
    valid = np.zeros((bq, c_eff, dh), dtype=bool)
    skew = np.full((bq, c_eff), _BIG, dtype=np.float32)
    mindom = np.zeros((bq, c_eff), dtype=np.float32)
    selfm = np.zeros((bq, c_eff), dtype=bool)
    kernel_rows: List[int] = []
    for i, pb in enumerate(pbs):
        if pb.pod_level_reason is not None \
                or not pb.profile.filter_enabled("NodeResourcesFit"):
            continue                     # host-decided sentinel brackets
        kernel_rows.append(i)
        free[i] = _free_matrix(pb)
        rv = np.asarray(pb.req_vec, dtype=np.float32).copy()
        rv[IDX_PODS] = 0.0               # pod slots ride pods_free
        req[i] = rv
        pods_free[i] = (pb.allocatable[:, IDX_PODS]
                        - pb.init_requested[:, IDX_PODS])
        gate[i] = np.asarray(pb.static_mask) & np.asarray(pb.volume_mask)
        if c_eff:
            (dom[i], e[i], valid[i], skew[i], mindom[i],
             selfm[i]) = _spread_arrays(pb, c_eff, dh, n)

    lo = hi = lp = None
    if kernel_rows:
        if mesh is not None:
            from ..parallel import mesh as mesh_lib
            nb = int(mesh.shape[mesh_lib.BATCH_AXIS])
            nn = int(mesh.shape[mesh_lib.NODE_AXIS])
            bq2 = -(-bq // nb) * nb
            n2 = -(-n // nn) * nn
            free = mesh_lib._pad_axis(
                mesh_lib._pad_axis(free, 0, bq2, 0), 1, n2, 0)
            req = mesh_lib._pad_axis(req, 0, bq2, 0)
            pods_free = mesh_lib._pad_axis(
                mesh_lib._pad_axis(pods_free, 0, bq2, 0), 1, n2, 0)
            gate = mesh_lib._pad_axis(
                mesh_lib._pad_axis(gate, 0, bq2, False), 1, n2, False)
            dom = mesh_lib._pad_axis(
                mesh_lib._pad_axis(dom, 0, bq2, -1), 2, n2, -1)
            e = mesh_lib._pad_axis(e, 0, bq2, 0)
            valid = mesh_lib._pad_axis(valid, 0, bq2, False)
            skew = mesh_lib._pad_axis(skew, 0, bq2, _BIG)
            mindom = mesh_lib._pad_axis(mindom, 0, bq2, 0)
            selfm = mesh_lib._pad_axis(selfm, 0, bq2, False)
        runner = _bracket_runner(c_eff, dh, mesh)
        if lower_only:
            # tools/shardgate trace-without-execute seam (sweep.solve_group)
            return {"kind": "bracket", "runner": runner,
                    "args": (free, req, pods_free, gate,
                             dom, e, valid, skew, mindom, selfm),
                    "consts": {"free": free, "req": req,
                               "pods_free": pods_free, "gate": gate,
                               "dom": dom, "e": e, "valid": valid,
                               "skew": skew, "mindom": mindom,
                               "selfm": selfm},
                    "carry": None,
                    "meta": {"n_nodes": n, "n_pad": free.shape[1],
                             "batch": b, "b_pad": free.shape[0]}}
        args = (free, req, pods_free, gate, dom, e, valid, skew, mindom,
                selfm)
        if mesh is not None:
            args = _on_mesh(_bracket_shardings(mesh)[0], args)
        lo, hi, lp = runner(*args)
        lo, hi, lp = np.asarray(lo), np.asarray(hi), np.asarray(lp)
    elif lower_only:
        return None                      # all-sentinel batch: nothing lowers

    out: List[CapacityBracket] = []
    for i, pb in enumerate(pbs):
        if pb.pod_level_reason is not None:
            out.append(CapacityBracket(0, 0, exact=False, method="pod_level"))
        elif not pb.profile.filter_enabled("NodeResourcesFit"):
            out.append(CapacityBracket(0, UNBOUNDED, exact=False,
                                       method="no_fit"))
        else:
            upper = float(hi[i])
            lower = 0.0 if not _fit_only(pb) else float(lo[i])
            lower = min(lower, upper)
            out.append(CapacityBracket(int(min(lower, UNBOUNDED)),
                                       int(min(upper, UNBOUNDED)),
                                       exact=exact_capacity(pb),
                                       frac=float(np.sum(
                                           lp[i, :n], dtype=np.float64))))
    return out


@functools.lru_cache(maxsize=8)
def _auction_runner(rounds: int, mesh=None):
    """Jitted K-round FFD/auction: templates scan in order against the
    shared free matrix, each round claiming ceil(claimable / rounds-left)
    per node — round-robin fairness across the mix, everything claimable by
    the last round.  Static on the round count.

    With a mesh the shared free matrix shards over the "nodes" axis (there
    is no scenario batch: every template bids against ONE snapshot), so the
    per-template claim totals are cross-shard psums; inputs must be padded
    to the node-shard multiple (`auction_device` pads with gate-False
    zero-headroom nodes, which never win a claim)."""
    import jax
    import jax.numpy as jnp

    def run(free, pods_free, reqs, gates):
        def round_body(r, state):
            free, pods_free, claimed = state
            left = jnp.maximum(jnp.float32(rounds) - r.astype(jnp.float32),
                               1.0)

            def tmpl_body(carry, t_in):
                free, pods_free = carry
                req, gate = t_in
                pos = req > 0
                ratio = jnp.where(pos[None, :],
                                  jnp.maximum(free, 0.0)
                                  / jnp.where(pos, req, 1.0)[None, :], _BIG)
                cap = jnp.minimum(jnp.min(ratio, axis=1),
                                  jnp.maximum(pods_free, 0.0))
                cap = jnp.where(gate, jnp.maximum(jnp.floor(cap), 0.0), 0.0)
                take = jnp.minimum(cap, jnp.ceil(cap / left))
                free = free - take[:, None] * req[None, :]
                pods_free = pods_free - take
                return (free, pods_free), jnp.sum(take)

            (free, pods_free), takes = jax.lax.scan(
                tmpl_body, (free, pods_free), (reqs, gates))
            return free, pods_free, claimed + takes

        zero = jnp.zeros(reqs.shape[0], dtype=jnp.float32)
        _free, _pods, claimed = jax.lax.fori_loop(
            0, rounds, round_body, (free, pods_free, zero))
        return claimed

    if mesh is None:
        return jax.jit(run)
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.jit(run, in_shardings=_auction_shardings(mesh),
                   out_shardings=NamedSharding(mesh, P(None)))


def _auction_shardings(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..parallel.mesh import NODE_AXIS

    def s(*parts):
        return NamedSharding(mesh, P(*parts))

    return (s(NODE_AXIS, None),              # free [N, R]
            s(NODE_AXIS),                    # pods_free [N]
            s(None, None),                   # reqs [T, R]
            s(None, NODE_AXIS))              # gates [T, N]


def _mix_arrays(pbs: Sequence[enc.EncodedProblem]):
    pb0 = pbs[0]
    n, r = pb0.snapshot.num_nodes, pb0.req_vec.shape[0]
    free = np.asarray(_free_matrix(pb0), dtype=np.float32).copy()
    free[:, IDX_PODS] = 0.0
    pods_free = np.asarray(pb0.allocatable[:, IDX_PODS]
                           - pb0.init_requested[:, IDX_PODS],
                           dtype=np.float32)
    reqs = np.zeros((len(pbs), r), dtype=np.float32)
    gates = np.zeros((len(pbs), n), dtype=bool)
    for t, pb in enumerate(pbs):
        rv = np.asarray(pb.req_vec, dtype=np.float32).copy()
        rv[IDX_PODS] = 0.0
        reqs[t] = rv
        gates[t] = np.asarray(pb.static_mask) & np.asarray(pb.volume_mask)
    return free, pods_free, reqs, gates


def auction_device(pbs: Sequence[enc.EncodedProblem],
                   rounds: int = 4, *, mesh=None,
                   lower_only: bool = False) -> List[int]:
    """K-round auction on device: per-template constructive claims against
    the SHARED free matrix (templates must encode the same snapshot).
    Dispatch-set member (GD001) — `bracket_mix` is the guarded entry."""
    n = pbs[0].snapshot.num_nodes
    free, pods_free, reqs, gates = _mix_arrays(pbs)
    if mesh is not None:
        from ..parallel import mesh as mesh_lib
        nn = int(mesh.shape[mesh_lib.NODE_AXIS])
        n2 = -(-free.shape[0] // nn) * nn
        free = mesh_lib._pad_axis(free, 0, n2, 0)
        pods_free = mesh_lib._pad_axis(pods_free, 0, n2, 0)
        gates = mesh_lib._pad_axis(gates, 1, n2, False)
    runner = _auction_runner(int(rounds), mesh)
    if lower_only:
        # tools/shardgate trace-without-execute seam (sweep.solve_group)
        return {"kind": "auction", "runner": runner,
                "args": (free, pods_free, reqs, gates),
                "consts": {"free": free, "pods_free": pods_free,
                           "reqs": reqs, "gates": gates},
                "carry": None,
                "meta": {"n_nodes": n, "n_pad": free.shape[0],
                         "batch": len(pbs), "b_pad": len(pbs)}}
    args = (free, pods_free, reqs, gates)
    if mesh is not None:
        args = _on_mesh(_auction_shardings(mesh), args)
    claimed = np.asarray(runner(*args))
    return [int(c) for c in claimed]


def _auction_host(pbs: Sequence[enc.EncodedProblem],
                  rounds: int = 4) -> List[int]:
    """Oracle-side auction: f64 numpy mirror of the device kernel."""
    free, pods_free, reqs, gates = (a.astype(np.float64)
                                    if a.dtype != bool else a
                                    for a in _mix_arrays(pbs))
    claimed = [0.0] * len(pbs)
    for r in range(rounds):
        left = float(rounds - r)
        for t in range(len(pbs)):
            pos = reqs[t] > 0
            ratio = np.where(pos[None, :],
                             np.maximum(free, 0.0)
                             / np.where(pos, reqs[t], 1.0)[None, :],
                             np.inf)
            cap = np.minimum(np.min(ratio, axis=1),
                             np.maximum(pods_free, 0.0))
            cap = np.where(gates[t], np.maximum(np.floor(cap), 0.0), 0.0)
            take = np.minimum(cap, np.ceil(cap / left))
            free = free - take[:, None] * reqs[t][None, :]
            pods_free = pods_free - take
            claimed[t] += float(np.sum(take))
    return [int(c) for c in claimed]


# --------------------------------------------------------------------------
# guarded entries
# --------------------------------------------------------------------------

def _validate_brackets(brs: Sequence[CapacityBracket], *, site: str) -> None:
    """Post-guard output validation: a bracket has no placement planes for
    guard.validate_result, so corruption checks live here (the chaos drill
    injects ``bounds.bracket:corrupt`` and this must catch it)."""
    from ..runtime.errors import NumericCorruption
    for br in brs:
        if br.lower < 0 or br.upper < br.lower or br.upper > UNBOUNDED:
            raise NumericCorruption(
                f"capacity bracket [{br.lower}, {br.upper}] is not a valid "
                f"bracket", site=site)


def bracket_group(pbs: Sequence[enc.EncodedProblem], *,
                  parity: bool = True, mesh=None
                  ) -> Tuple[List[CapacityBracket], bool]:
    """Guarded batched bracketing: one device shot under guard.run at
    faults.SITE_BOUNDS, validated, then parity-checked against the host
    recomputation (pruning decisions must never ride a silently-wrong
    kernel).  Any classified fault — or a parity mismatch, raised as
    NumericCorruption — degrades to the host brackets, which share the
    formulas exactly.  With a mesh the shot shards over (batch, nodes) —
    the parity check applies unchanged, so a sharded bracket is held to the
    same bit-match bar as an unsharded one.  Returns (brackets, degraded)."""
    from ..parallel import mesh as mesh_lib
    from ..runtime import faults, guard
    from ..runtime.degrade import _record
    from ..runtime.errors import NumericCorruption, RuntimeFault

    pbs = list(pbs)
    if not pbs:
        return [], False
    try:
        try:
            brs = guard.run(lambda: bracket_device(pbs, mesh=mesh),
                            site=faults.SITE_BOUNDS, rung="bounds",
                            batch=len(pbs),
                            mesh_shape=mesh_lib.mesh_shape(mesh))
            _validate_brackets(brs, site=faults.SITE_BOUNDS)
            if parity:
                host = [bracket_host(pb) for pb in pbs]
                for h, d in zip(host, brs):
                    if h.lower != d.lower or h.upper != d.upper:
                        raise NumericCorruption(
                            f"device bracket [{d.lower}, {d.upper}] "
                            f"disagrees with host recomputation "
                            f"[{h.lower}, {h.upper}]",
                            site=faults.SITE_BOUNDS)
                return brs, False
            return brs, False
        except RuntimeFault as fault:
            _record(fault, "bounds_host")
            raise
    except RuntimeFault:
        return [bracket_host(pb) for pb in pbs], True


def bracket_mix(pbs: Sequence[enc.EncodedProblem], rounds: int = 4, *,
                mesh=None) -> Tuple[CapacityBracket, List[int], bool]:
    """Joint bracket for a template mix against ONE shared snapshot: the
    upper bound sums the per-template solo uppers (any joint schedule is
    dominated per template) capped by the pooled pod slots; the lower bound
    is the guarded K-round auction's total.  Returns (joint bracket,
    per-template claims, degraded)."""
    from ..parallel import mesh as mesh_lib
    from ..runtime import faults, guard
    from ..runtime.degrade import _record
    from ..runtime.errors import RuntimeFault

    pbs = list(pbs)
    if not pbs:
        return CapacityBracket(0, 0, exact=False), [], False
    degraded = False
    try:
        claims = guard.run(lambda: auction_device(pbs, rounds, mesh=mesh),
                           site=faults.SITE_BOUNDS, rung="bounds",
                           batch=len(pbs),
                           mesh_shape=mesh_lib.mesh_shape(mesh))
        if any(c < 0 for c in claims):
            from ..runtime.errors import NumericCorruption
            raise NumericCorruption("negative auction claim",
                                    site=faults.SITE_BOUNDS)
        host_claims = _auction_host(pbs, rounds)
        if claims != host_claims:
            from ..runtime.errors import NumericCorruption
            raise NumericCorruption(
                f"device auction claims {claims} disagree with host "
                f"recomputation {host_claims}", site=faults.SITE_BOUNDS)
    except RuntimeFault as fault:
        _record(fault, "bounds_host")
        claims = _auction_host(pbs, rounds)
        degraded = True
    solos = [bracket_host(pb) for pb in pbs]
    pods_free = np.maximum(
        np.asarray(pbs[0].allocatable[:, IDX_PODS]
                   - pbs[0].init_requested[:, IDX_PODS], dtype=np.float64),
        0.0)
    any_gate = np.zeros(pbs[0].snapshot.num_nodes, dtype=bool)
    for pb in pbs:
        any_gate |= np.asarray(pb.static_mask) & np.asarray(pb.volume_mask)
    upper = min(sum(s.upper for s in solos),
                int(np.sum(np.floor(pods_free[any_gate]))))
    lower = min(sum(claims), upper)
    exact = len(pbs) == 1 and solos[0].exact
    return (CapacityBracket(int(min(lower, UNBOUNDED)),
                            int(min(upper, UNBOUNDED)), exact=exact,
                            frac=float(sum(s.frac for s in solos))),
            claims, degraded)


# --------------------------------------------------------------------------
# prune-side host diagnosis
# --------------------------------------------------------------------------

def exhausted_fit_counts(pb: enc.EncodedProblem
                         ) -> Optional[Dict[str, int]]:
    """The FitError reason histogram at the caps-exhausted terminal of an
    `exact_capacity` problem, recomputed on the host: the terminal requested
    plane is init + caps·req regardless of placement order, so the counts —
    and therefore sim.format_fit_error's message — match what the scan's
    diagnose() would report, letting a pruned scenario row carry the same
    fail message a device solve would have.  Returns None when a node is
    somehow still feasible (caller must not prune)."""
    n = pb.snapshot.num_nodes
    frac, _gate = _host_planes(pb)
    caps = np.floor(frac)
    term_req = pb.init_requested + caps[:, None] * pb.req_vec[None, :]

    counts: Dict[str, int] = {}

    def add(reason: str, k: int = 1):
        if k:
            counts[reason] = counts.get(reason, 0) + int(k)

    remaining = np.ones(n, dtype=bool)
    static_code = np.asarray(pb.static_code)
    static_fail = static_code != enc.CODE_OK
    for code in np.unique(static_code[static_fail]):
        idxs = np.flatnonzero(static_code == code)
        if int(code) == enc.CODE_TAINT:
            for i in idxs:
                add(pb.taint_reasons[i] or "node(s) had untolerated taint")
        else:
            add(enc.STATIC_REASONS[int(code)], len(idxs))
    remaining &= ~static_fail

    # fit at the terminal plane — ops/node_resources_fit.fit_filter semantics
    too_many = term_req[:, IDX_PODS] + 1.0 > pb.allocatable[:, IDX_PODS]
    free = pb.allocatable - term_req
    insufficient = ((pb.req_vec[None, :] > free)
                    & (pb.req_vec > 0)[None, :])
    insufficient[:, IDX_PODS] = False
    fit_fail = too_many | insufficient.any(axis=1)
    take = remaining & fit_fail
    if take.any():
        from ..ops.dynamic_resources import (DRA_RESOURCE_PREFIX,
                                             REASON_CANNOT_ALLOCATE)
        add("Too many pods", int((take & too_many).sum()))
        dra_cols = [j for j, rn in enumerate(pb.resource_names)
                    if rn.startswith(DRA_RESOURCE_PREFIX)]
        for j, rname in enumerate(pb.resource_names):
            if j in dra_cols:
                continue
            add(f"Insufficient {rname}",
                int((take & insufficient[:, j]).sum()))
        if dra_cols:
            dra_any = np.logical_or.reduce(
                [insufficient[:, j] for j in dra_cols])
            add(REASON_CANNOT_ALLOCATE, int((take & dra_any).sum()))
    remaining &= ~take

    take = remaining & ~np.asarray(pb.volume_mask)
    for i in np.flatnonzero(take):
        add(pb.volume_reasons[i] or "volume conflict")
    remaining &= ~take

    if remaining.any():
        # a still-feasible node contradicts exhaustion — refuse to guess
        return None
    return counts
