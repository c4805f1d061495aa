"""The greedy placement engine: one `lax.scan` over the pod sequence.

This replaces the reference's entire event pipeline — scheduling queue, watch
channels, binder plugin, assume/confirm cache (simulator.go:356-431 +
schedule_one.go:66-364) — with a single batched solve: the scan carry is the
cluster's mutable state (requested resources, topology-domain counts), each
step computes all filter masks and the weighted score pipeline over the full
node axis, picks the argmax host, and scatter-updates the carry.  Binding is a
pure array update; there is no async cycle to keep coherent.

Cycle-order parity (schedule_one.go:150-277): filters run in the default
plugin order, scores are normalized per-cycle over the feasible set, weights
multiply after normalization (runtime/framework.go:1137-1240), and host
selection is argmax with lowest-index tie-break (the deterministic replacement
for selectHost's reservoir sampling, schedule_one.go:894-946) or uniform-among-
ties when profile.deterministic=False.

Compilation: the scan step is jitted once per (StaticConfig, array shapes) at
module level, so repeated solves — what-if sweeps, tests over the same cluster
shape — reuse the compiled executable.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from .. import obs
from . import encode as enc
from ..models.snapshot import IDX_CPU
from ..ops import inter_pod_affinity as ipa_ops
from ..ops import node_resources_fit as fit_ops
from ..ops import pod_topology_spread as spread_ops

# Above this many domains, a soft constraint's dense one-hot membership
# tensor ([C, D, N]) is too big — soft_score falls back to a scatter for the
# distinct-domain count instead.
_ONEHOT_DOMAIN_CAP = 128

FAIL_LIMIT_REACHED = "LimitReached"
FAIL_UNSCHEDULABLE = "Unschedulable"

_DEFAULT_UNLIMITED_CAP = 1_000_000
# Fused-kernel chunking: steps per kernel call and max pipelined calls per
# host sync.  Env override is a test hook (small chunks make the mid-solve
# checkpoints reachable in interpret mode).
_FUSED_CHUNK = int(os.environ.get("CC_TPU_FUSED_CHUNK", "4096"))
_FUSED_PIPELINE = 16
_FUSED_INFLIGHT = 2


class StaticConfig(NamedTuple):
    """Everything the jitted step specializes on.  Hashable → usable as a jit
    static argument, so compilation is cached across solve() calls."""

    dtype64: bool
    deterministic: bool
    fit_filter_on: bool
    clone_has_ports: bool
    volume_filter_on: bool
    volume_self_conflict: bool
    rwop_self_conflict: bool
    dra_shared_colocate: bool
    spread_hard_n: int
    spread_soft_n: int
    ipa_filter_on: bool
    ipa_num_aff: int
    ipa_num_anti: int
    ipa_num_pref: int
    ipa_escape_allowed: bool
    ipa_score_active: bool
    na_active: bool
    weights: Tuple[Tuple[str, int], ...]
    fit_strategy_type: str
    fit_shape: Tuple[Tuple[float, ...], Tuple[float, ...]]
    # Static resource-column views for the score strategies: baking the
    # indices into the compiled program turns per-step gathers into slices.
    fit_idx: Tuple[int, ...]
    fit_nz: Tuple[bool, ...]
    bal_idx: Tuple[int, ...]
    # True when the template's affinity map starts empty (the lonely-pod
    # escape hatch can only apply then, filtering.go:400-406).
    ipa_static_empty: bool
    # True when soft-spread distinct-domain counting can use the dense
    # one-hot matmul (domain cardinality under _ONEHOT_DOMAIN_CAP).
    ss_onehot_ok: bool
    # 0 = score all feasible nodes; otherwise numFeasibleNodesToFind
    # (schedule_one.go:697-725) emulated deterministically.
    sample_k: int


def _soft_nonhost_domains(ss) -> int:
    """Max domain cardinality across non-hostname soft constraints."""
    d_nh = 1
    for c in range(ss.num_constraints):
        if not ss.is_hostname[c] and (ss.node_domain[c] >= 0).any():
            d_nh = max(d_nh, int(ss.node_domain[c].max()) + 1)
    return d_nh


def _num_feasible_nodes_to_find(profile, num_all: int) -> int:
    """numFeasibleNodesToFind (schedule_one.go:697-725): 0 means score-all."""
    pct = profile.percentage_of_nodes_to_score
    if pct >= 100 and not profile.adaptive_sampling:
        return 0
    if num_all < 100:                     # minFeasibleNodesToFind
        return 0
    if profile.adaptive_sampling and pct >= 100:
        pct = max(5, 50 - num_all // 125)
    num = num_all * pct // 100
    if num < 100:
        return 100
    return num


def static_config(pb: enc.EncodedProblem) -> StaticConfig:
    profile = pb.profile
    ipa = pb.ipa
    return StaticConfig(
        dtype64=(profile.compute_dtype == "float64"),
        deterministic=profile.deterministic,
        fit_filter_on=profile.filter_enabled("NodeResourcesFit"),
        clone_has_ports=pb.clone_has_host_ports,
        volume_filter_on=bool(not pb.volume_mask.all()),
        volume_self_conflict=pb.volume_self_conflict,
        rwop_self_conflict=pb.rwop_self_conflict,
        dra_shared_colocate=pb.dra_shared_colocate,
        spread_hard_n=pb.spread_hard.num_constraints,
        spread_soft_n=pb.spread_soft.num_constraints,
        ipa_filter_on=profile.filter_enabled("InterPodAffinity") and (
            ipa.num_aff_terms > 0 or ipa.num_anti_terms > 0 or
            bool(ipa.existing_anti_static.any())),
        ipa_num_aff=ipa.num_aff_terms,
        ipa_num_anti=ipa.num_anti_terms,
        ipa_num_pref=ipa.num_pref_terms,
        ipa_escape_allowed=ipa.escape_allowed,
        ipa_score_active=ipa.has_any_score_terms,
        na_active=pb.node_affinity_active,
        weights=tuple(sorted(profile.score_weights.items())),
        fit_strategy_type=profile.fit_strategy.type,
        fit_shape=(tuple(profile.fit_strategy.shape_utilization),
                   tuple(profile.fit_strategy.shape_score)),
        fit_idx=tuple(int(j) for j in pb.fit_res_idx),
        fit_nz=tuple(bool(b) for b in pb.fit_uses_nonzero),
        bal_idx=tuple(int(j) for j in pb.balanced_res_idx),
        ipa_static_empty=bool(ipa.aff_init.sum() == 0),
        ss_onehot_ok=_soft_nonhost_domains(pb.spread_soft) <= _ONEHOT_DOMAIN_CAP,
        # num_alive, not the axis length: nodes masked out by a resilience
        # alive_mask are not part of the cluster percentageOfNodesToScore sees
        sample_k=_num_feasible_nodes_to_find(profile, pb.num_alive),
    )


class Carry(NamedTuple):
    """The cluster's mutable state.  All topology state is carried as dense
    PER-NODE count tensors ([C, N]/[G, N], sharded over the node axis on a
    mesh) rather than domain-indexed maps — every step is then elementwise +
    reduction work with no gathers/scatters/sorts on the hot path."""

    requested: "jax.Array"          # f[N, R]
    nonzero: "jax.Array"            # f[N, 2]
    placed: "jax.Array"             # i32[N]
    sh_cnt: "jax.Array"             # f[Ch, N] — hard-spread match counts
    ss_cnt: "jax.Array"             # f[Cs, N] — soft-spread match counts
    aff_cnt: "jax.Array"            # f[G, N] — dynamic affinity counts
    anti_cnt: "jax.Array"           # f[G, N] — dynamic anti-affinity counts
    pref_cnt: "jax.Array"           # f[G, N] — dynamic preferred weights
    aff_total: "jax.Array"          # f[] — total dynamic affinity count
    placed_count: "jax.Array"       # i32
    stopped: "jax.Array"            # bool
    next_start: "jax.Array"         # i32 — rotating sample start index
    rng: "jax.Array"                # PRNG key (unused when deterministic)


@dataclass
class SolveResult:
    placements: List[int]                    # node index per placed pod, in order
    placed_count: int
    fail_type: str
    fail_message: str
    fail_counts: Dict[str, int] = field(default_factory=dict)
    node_names: List[str] = field(default_factory=list)
    # Hardened-runtime provenance: which degradation-ladder rung served this
    # result ('' = unsupervised direct engine call) and whether any
    # classified fault occurred on the way (runtime/degrade.py).
    rung: str = ""
    degraded: bool = False
    # Attribution artifact (explain/artifacts.Explanation) when the solve ran
    # with explain=True; None otherwise.
    explain: Optional[object] = None

    @property
    def per_node_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for i in self.placements:
            name = self.node_names[i]
            out[name] = out.get(name, 0) + 1
        return out


def _dt(cfg: StaticConfig):
    import jax.numpy as jnp
    return jnp.float64 if cfg.dtype64 else jnp.float32


def _weight(cfg: StaticConfig, name: str) -> int:
    for k, v in cfg.weights:
        if k == name:
            return v
    return 0


def _default_normalize(raw, feasible, reverse: bool):
    """helper.DefaultNormalizeScore (normalize_score.go:28-56) over the
    feasible set: floor(100*s/max); reverse subtracts from 100; max==0 → all
    100 when reverse else untouched raws."""
    import jax.numpy as jnp
    max_s = jnp.max(jnp.where(feasible, raw, 0.0))
    scaled = jnp.where(max_s > 0,
                       jnp.floor(100.0 * raw / jnp.where(max_s > 0, max_s, 1.0)),
                       raw)
    if reverse:
        scaled = jnp.where(max_s > 0, 100.0 - scaled, 100.0)
    return jnp.where(feasible, scaled, 0.0)


def _expand_counts(init_counts: np.ndarray, node_domain: np.ndarray) -> np.ndarray:
    """Materialize counts[c, dom[c, n]] per node (0 where the key is absent) —
    the static seed of the carried per-node count tensors."""
    if not init_counts.any():
        # no existing pods contribute counts (the what-if sweep norm): the
        # expansion is all zeros — skip the [C, N] gather per template
        return np.zeros(node_domain.shape, dtype=init_counts.dtype)
    safe = np.clip(node_domain, 0, init_counts.shape[1] - 1)
    out = np.take_along_axis(init_counts, safe, axis=1)
    return np.where(node_domain >= 0, out, 0.0)


def build_consts(pb: enc.EncodedProblem,
                 ss_dnh_min: int = 1,
                 device: bool = True) -> Dict[str, "jax.Array"]:
    """Move all static arrays to device once, in the profile dtype.

    ss_dnh_min pads the soft-spread one-hot's domain axis up to a group-wide
    size so batched sweeps can stack consts across templates.

    device=False keeps every array on the host as numpy: the batched sweep
    builds B per-template const dicts, np.stacks them, and pays ONE device
    transfer per key instead of ~33 x B small ones."""
    if device:
        import jax.numpy as jnp
        xp = jnp
    else:
        xp = np
    dt = np.float64 if pb.profile.compute_dtype == "float64" else np.float32
    f = lambda a: xp.asarray(a, dtype=dt)
    jnp = xp  # the literal asarray calls below follow the same backend

    def f_snap(a, name):
        # Host-path cast of a snapshot-owned array, memoized on the snapshot:
        # every template of a sweep group then holds the SAME object, so the
        # group dedup (parallel/sweep._group_uniform) is an `is` check
        # instead of a B-way content compare.
        if not device and a is getattr(pb.snapshot, name, None):
            return pb.snapshot.memo(("consts_cast", name, str(dt)),
                                    lambda: np.asarray(a, dtype=dt))
        return f(a)
    sh, ss, ipa = pb.spread_hard, pb.spread_soft, pb.ipa

    # Soft-constraint domain membership one-hots for NON-hostname rows: the
    # per-step distinct-domain count (topology size, scoring.go:141-145)
    # becomes one small matmul.  Hostname rows stay zero (their size is the
    # scorable-node count — no domain structure needed).
    dom_s = ss.node_domain
    d_nh = max(1, ss_dnh_min)
    for c in range(ss.num_constraints):
        if not ss.is_hostname[c] and (dom_s[c] >= 0).any():
            d_nh = max(d_nh, int(dom_s[c].max()) + 1)
    if d_nh > _ONEHOT_DOMAIN_CAP:
        # high-cardinality topology key: soft_score scatters instead
        ss_onehot = np.zeros((dom_s.shape[0], 1, dom_s.shape[1]))
    else:
        ss_onehot = np.zeros((dom_s.shape[0], d_nh, dom_s.shape[1]))
        for c in range(ss.num_constraints):
            if not ss.is_hostname[c]:
                nodes = np.nonzero(dom_s[c] >= 0)[0]
                ss_onehot[c, dom_s[c][nodes], nodes] = 1.0

    # Per-GROUP IPA statics (shared with the fused kernel's meta packing).
    ghas_aff, ghas_anti, aff_ginc, anti_ginc, pref_gw = \
        ipa_ops.group_fold(ipa)

    return {
        "allocatable": f_snap(pb.allocatable, "allocatable"),
        "req_vec": f(pb.req_vec),
        "shared_req_vec": f(pb.shared_req_vec),
        "req_nonzero": f(pb.req_nonzero),
        "static_mask": jnp.asarray(pb.static_mask),
        "taint_raw": f(pb.taint_raw),
        "na_raw": f(pb.node_affinity_raw),
        "il_score": f(pb.image_locality_score),
        "fit_w": f(pb.fit_res_weights),
        "fit_req": f(pb.fit_req),
        "bal_req": f(pb.balanced_req),
        "volume_mask": jnp.asarray(pb.volume_mask),
        "sh_dom": jnp.asarray(sh.node_domain),
        "sh_countable": jnp.asarray(sh.node_countable),
        "sh_skew": f(sh.max_skew),
        "sh_mindom": f(sh.min_domains),
        "sh_domnum": f(sh.domain_valid.sum(axis=1)),
        "sh_self": jnp.asarray(sh.self_match),
        "sh_missing": jnp.asarray(~sh.node_has_all_keys),
        "sh_cnt_init": f(_expand_counts(sh.init_counts, sh.node_domain)),
        "ss_dom": jnp.asarray(ss.node_domain),
        "ss_countable": jnp.asarray(ss.node_countable),
        "ss_skew": f(ss.max_skew),
        "ss_self": jnp.asarray(ss.self_match),
        "ss_host": jnp.asarray(ss.is_hostname),
        "ss_node_existing": f(ss.node_existing),
        "ss_ignored": jnp.asarray(pb.spread_ignored),
        "ss_cnt_init": f(_expand_counts(ss.init_counts, ss.node_domain)),
        "ss_onehot": f(ss_onehot),
        "ipa_dom": jnp.asarray(ipa.node_domain),
        "ipa_ghas_aff": jnp.asarray(ghas_aff),
        "ipa_ghas_anti": jnp.asarray(ghas_anti),
        "ipa_aff_ginc": f(aff_ginc),
        "ipa_anti_ginc": f(anti_ginc),
        "ipa_pref_gw": f(pref_gw),
        "ipa_aff_scnt": f(_expand_counts(ipa.aff_init, ipa.node_domain)),
        "ipa_anti_scnt": f(_expand_counts(ipa.anti_init, ipa.node_domain)),
        "ipa_eanti_static": jnp.asarray(ipa.existing_anti_static),
        "ipa_static_pref": f(pb.ipa.static_pref_score),
        # per-template self-conflict gate scalars: in a single-template
        # solve each equals its StaticConfig flag; in a stacked group the
        # cfg flag goes on when ANY template needs the gate and these
        # scalars keep it inert for the others (the interleave engine's
        # per-template Carry views rely on this)
        "vol_self_gate": f(1.0 if pb.volume_self_conflict else 0.0),
        "rwop_gate": f(1.0 if pb.rwop_self_conflict else 0.0),
        "dra_colo_gate": f(1.0 if pb.dra_shared_colocate else 0.0),
    }


def cached_static_config(pb: enc.EncodedProblem) -> StaticConfig:
    """static_config memoized on the problem instance.  The config is a pure
    function of the encoded problem, so repeated solves of the same pb (the
    watch loop, explain-after-solve, fast-path retries) share one object —
    and one jit static-arg cache key."""
    cfg = pb.__dict__.get("_static_config_memo")
    if cfg is None:
        cfg = static_config(pb)
        pb.__dict__["_static_config_memo"] = cfg
    return cfg


def cached_consts(pb: enc.EncodedProblem) -> Dict[str, "jax.Array"]:
    """build_consts (device form, default padding) memoized on the problem
    instance: ~33 host→device transfers collapse to one per problem instead
    of one per solve call.  Callers treat the dict as frozen — nothing in
    the engine mutates consts after construction."""
    consts = pb.__dict__.get("_device_consts_memo")
    if consts is None:
        consts = build_consts(pb)
        pb.__dict__["_device_consts_memo"] = consts
    return consts


def _init_carry(pb: enc.EncodedProblem, consts, seed: int,
                device: bool = True) -> Carry:
    """device=False mirrors build_consts(device=False): numpy leaves for the
    batched sweep's host-side stack (the PRNG key bytes are identical —
    np.asarray of the same PRNGKey)."""
    if device:
        import jax.numpy as jnp
    else:
        jnp = np
    dt = consts["allocatable"].dtype
    n = pb.snapshot.num_nodes
    g = pb.ipa.node_domain.shape[0]
    return Carry(
        requested=jnp.asarray(pb.init_requested, dtype=dt),
        nonzero=jnp.asarray(pb.init_nonzero, dtype=dt),
        placed=jnp.zeros(n, dtype=jnp.int32),
        sh_cnt=consts["sh_cnt_init"],
        ss_cnt=consts["ss_cnt_init"],
        aff_cnt=jnp.zeros((g, n), dtype=dt),
        anti_cnt=jnp.zeros((g, n), dtype=dt),
        pref_cnt=jnp.zeros((g, n), dtype=dt),
        aff_total=jnp.zeros((), dtype=dt),
        placed_count=jnp.zeros((), dtype=jnp.int32),
        stopped=jnp.zeros((), dtype=bool),
        next_start=jnp.zeros((), dtype=jnp.int32),
        rng=_prng_key(seed, device=device),
    )


@functools.lru_cache(maxsize=None)
def _prng_key_host(seed: int) -> np.ndarray:
    import jax
    return np.asarray(jax.random.PRNGKey(seed))


def _prng_key(seed: int, device: bool = True):
    if device:
        import jax
        return jax.random.PRNGKey(seed)
    return _prng_key_host(seed)


def _col(mat: "jax.Array", chosen: "jax.Array") -> "jax.Array":
    """mat[:, chosen] as a dynamic slice (no gather)."""
    import jax
    return jax.lax.dynamic_slice_in_dim(mat, chosen, 1, axis=1)[:, 0]


def _row_add(arr: "jax.Array", idx: "jax.Array", delta: "jax.Array") -> "jax.Array":
    """arr[idx] += delta via dynamic slice + update (no scatter).  delta must
    carry the leading singleton axis ([1, ...] / [1])."""
    import jax
    row = jax.lax.dynamic_slice_in_dim(arr, idx, 1, axis=0)
    return jax.lax.dynamic_update_slice_in_dim(arr, row + delta, idx, axis=0)


def _feasibility(cfg: StaticConfig, consts, carry: Carry, eanti_dyn=None,
                 ports_blocked=None):
    """All filter masks for the current state.  Returns (feasible, parts dict
    for diagnosis).

    eanti_dyn overrides the dynamic existing-pods-anti-affinity counts.  In a
    single-template solve the placed clones are identical, so 'pods matching
    my anti terms' and 'pods whose anti terms match me' coincide and both
    read carry.anti_cnt; the tensor interleave engine carries them
    separately (another template's clone can have anti terms this template's
    own selector never matches).

    ports_blocked (bool[N]) overrides the dynamic host-port conflict rule:
    the single-template rule is 'any own clone on the node' (carry.placed),
    but the interleave engine must also block on OTHER templates' clones
    with overlapping ports — it computes the mask from its cross-template
    port-conflict matrix and passes it here so the diagnosis attribution
    slot (before fit, mirroring the filter chain order) stays shared."""
    feasible = consts["static_mask"]
    parts = {}

    if cfg.fit_filter_on:
        req_vec = consts["req_vec"]
        if cfg.dra_shared_colocate:
            # unallocated shared claim: its devices are requested only by
            # the FIRST placement (the allocation)
            import jax.numpy as jnp
            req_vec = req_vec + jnp.where(carry.placed_count == 0,
                                          consts["shared_req_vec"], 0.0)
        fitv = fit_ops.fit_filter(consts["allocatable"], carry.requested,
                                  req_vec)
        parts["fit"] = fitv
        feasible = feasible & fitv.mask

    if cfg.clone_has_ports or ports_blocked is not None:
        if ports_blocked is not None:
            ports_ok = ~ports_blocked
        else:
            ports_ok = ~(carry.placed > 0)
        parts["ports_dyn"] = ports_ok
        feasible = feasible & ports_ok

    if cfg.volume_filter_on:
        feasible = feasible & consts["volume_mask"]
    if cfg.volume_self_conflict:
        feasible = feasible & ~((carry.placed > 0)
                                & (consts["vol_self_gate"] > 0))
    if cfg.rwop_self_conflict:
        feasible = feasible & ((carry.placed_count == 0)
                               | (consts["rwop_gate"] == 0))
    if cfg.dra_shared_colocate:
        # shared ResourceClaim: all users share one allocation → colocate
        feasible = feasible & ((carry.placed > 0) | (carry.placed_count == 0)
                               | (consts["dra_colo_gate"] == 0))

    if cfg.spread_hard_n > 0:
        sp_ok, sp_missing = spread_ops.hard_filter(
            carry.sh_cnt, consts["sh_dom"], consts["sh_countable"],
            consts["sh_skew"], consts["sh_mindom"], consts["sh_domnum"],
            consts["sh_self"], consts["sh_missing"])
        parts["spread_ok"] = sp_ok
        parts["spread_missing"] = sp_missing
        feasible = feasible & sp_ok

    if cfg.ipa_filter_on:
        import jax.numpy as jnp
        map_empty = (carry.aff_total == 0) if cfg.ipa_static_empty \
            else jnp.asarray(False)
        ok, f_aff, f_anti, f_eanti = ipa_ops.filter_all(
            consts["ipa_aff_scnt"] + carry.aff_cnt,
            consts["ipa_anti_scnt"] + carry.anti_cnt,
            carry.anti_cnt if eanti_dyn is None else eanti_dyn,
            consts["ipa_dom"],
            consts["ipa_ghas_aff"], consts["ipa_ghas_anti"],
            cfg.ipa_num_aff, cfg.ipa_num_anti, map_empty,
            cfg.ipa_escape_allowed, consts["ipa_eanti_static"])
        parts["ipa"] = (f_aff, f_anti, f_eanti)
        feasible = feasible & ok
    return feasible, parts


def _score_terms(cfg: StaticConfig, consts, carry: Carry, feasible):
    """Ordered (plugin name, already-weighted [N] term) pairs for the active
    score plugins.  _scores sums them in order, so the expression tree — and
    with it the compiled program — is identical to the historical inline
    accumulation; explain/ reads the same terms per placement without a
    second scoring pass."""
    import jax.numpy as jnp
    dt = _dt(cfg)
    terms = []

    w = _weight(cfg, "NodeResourcesFit")
    if w:
        # Static column views (indices baked into the program → slices, not
        # gathers); cpu/mem use NonZeroRequested (resource_allocation.go:85-91).
        alloc = jnp.stack([consts["allocatable"][:, j] for j in cfg.fit_idx],
                          axis=1)
        req = jnp.stack(
            [carry.nonzero[:, 0 if j == IDX_CPU else 1] if nz
             else carry.requested[:, j]
             for j, nz in zip(cfg.fit_idx, cfg.fit_nz)], axis=1)
        req = req + consts["fit_req"][None, :]
        if cfg.fit_strategy_type == "MostAllocated":
            s = fit_ops.most_allocated_score(alloc, req, consts["fit_w"])
        elif cfg.fit_strategy_type == "RequestedToCapacityRatio":
            s = fit_ops.requested_to_capacity_ratio_score(
                alloc, req, consts["fit_w"], cfg.fit_shape[0], cfg.fit_shape[1])
        else:
            s = fit_ops.least_allocated_score(alloc, req, consts["fit_w"])
        terms.append(("NodeResourcesFit", w * jnp.where(feasible, s, 0.0)))

    w = _weight(cfg, "NodeResourcesBalancedAllocation")
    if w:
        alloc = jnp.stack([consts["allocatable"][:, j] for j in cfg.bal_idx],
                          axis=1)
        req = jnp.stack([carry.requested[:, j] for j in cfg.bal_idx],
                        axis=1) + consts["bal_req"][None, :]
        s = fit_ops.balanced_allocation_score(alloc, req)
        terms.append(("NodeResourcesBalancedAllocation",
                      w * jnp.where(feasible, s, 0.0)))

    w = _weight(cfg, "TaintToleration")
    if w:
        terms.append(("TaintToleration",
                      w * _default_normalize(consts["taint_raw"], feasible,
                                             reverse=True)))

    w = _weight(cfg, "NodeAffinity")
    if w and cfg.na_active:
        terms.append(("NodeAffinity",
                      w * _default_normalize(consts["na_raw"], feasible,
                                             reverse=False)))

    w = _weight(cfg, "ImageLocality")
    if w:
        terms.append(("ImageLocality",
                      w * jnp.where(feasible, consts["il_score"], 0.0)))

    w = _weight(cfg, "PodTopologySpread")
    if w and cfg.spread_soft_n > 0:
        hostname_cnt = consts["ss_node_existing"] + \
            jnp.where(consts["ss_self"][:, None],
                      carry.placed[None, :].astype(dt), 0.0)
        raw, scored = spread_ops.soft_score(
            carry.ss_cnt, hostname_cnt, consts["ss_dom"], consts["ss_host"],
            consts["ss_skew"], consts["ss_onehot"], consts["ss_ignored"],
            feasible, use_onehot=cfg.ss_onehot_ok)
        terms.append(("PodTopologySpread",
                      w * spread_ops.soft_normalize(raw, scored)))

    w = _weight(cfg, "InterPodAffinity")
    if w and cfg.ipa_score_active:
        raw = ipa_ops.pref_score(carry.pref_cnt, consts["ipa_dom"],
                                 consts["ipa_static_pref"], cfg.ipa_num_pref)
        terms.append(("InterPodAffinity",
                      w * ipa_ops.normalize(raw, feasible, True)))

    return terms


def _scores(cfg: StaticConfig, consts, carry: Carry, feasible):
    import jax.numpy as jnp
    n = consts["static_mask"].shape[0]
    total = jnp.zeros(n, dtype=_dt(cfg))
    for _name, term in _score_terms(cfg, consts, carry, feasible):
        total = total + term
    return total


def _sample_scorable(cfg: StaticConfig, feasible, next_start):
    """Deterministic emulation of findNodesThatPassFilters' truncation
    (schedule_one.go:610-694): take the first K feasible nodes in
    round-robin order from the rotating start index, and advance the
    index past the last node examined.  The K-th feasible node's rank
    comes from a rotation + prefix sum — no per-step sort.  Shared by the
    scan step and the tensor interleave engine (parallel/interleave.py)."""
    import jax
    import jax.numpy as jnp
    if cfg.sample_k <= 0:
        return feasible, next_start
    n = feasible.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    rank = jnp.remainder(idx - next_start, n)
    rot = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([feasible, feasible]), next_start, n)
    # 0/1 values summed over n <= the node-count cap << 2**31: the int32
    # prefix sum cannot overflow.
    csum = jnp.cumsum(rot.astype(jnp.int32))  # jaxlint: disable=DT002
    reached = csum >= min(cfg.sample_k, n)
    threshold = jnp.where(jnp.any(reached),
                          jnp.argmax(reached).astype(jnp.int32), n - 1)
    scorable = feasible & (rank <= threshold)
    processed = threshold + 1
    return scorable, jnp.remainder(next_start + processed, n)


def _step(cfg: StaticConfig, consts, carry: Carry):
    import jax
    import jax.numpy as jnp
    dt = _dt(cfg)

    feasible, _parts = _feasibility(cfg, consts, carry)
    any_feasible = jnp.any(feasible)

    scorable, next_start = _sample_scorable(cfg, feasible, carry.next_start)

    total = _scores(cfg, consts, carry, scorable)

    neg_one = jnp.asarray(-1.0, dt)
    keyed = jnp.where(scorable, total, neg_one)
    if cfg.deterministic:
        chosen = jnp.argmax(keyed).astype(jnp.int32)
        rng = carry.rng
    else:
        rng, sub = jax.random.split(carry.rng)
        jitter = jax.random.uniform(sub, keyed.shape, dtype=jnp.float32)
        # integer scores: +0.5*U(0,1) breaks ties uniformly (the stationary
        # equivalent of selectHost's reservoir sampling) without reordering
        # distinct scores.
        chosen = jnp.argmax(keyed + 0.5 * jitter.astype(dt)).astype(jnp.int32)

    place = any_feasible & ~carry.stopped
    new_carry = _apply_placement(cfg, consts, carry, chosen, place, next_start,
                                 rng)
    new_carry = new_carry._replace(stopped=carry.stopped | ~any_feasible)
    return new_carry, jnp.where(place, chosen, -1)


def _apply_placement(cfg: StaticConfig, consts, carry: Carry, chosen,
                     place, next_start=None, rng=None) -> Carry:
    """Commit one placement into the carry (the binder-plugin analog —
    plugin.go:34-53 sets NodeName+Running).  All updates are dense or
    single-row dynamic slices; the topology tensors get their increment via
    dense_count_update (every node sharing the chosen node's domain)."""
    import jax.numpy as jnp
    dt = _dt(cfg)
    if next_start is None:
        next_start = carry.next_start
    if rng is None:
        rng = carry.rng
    gate = place.astype(dt)

    req_vec = consts["req_vec"]
    if cfg.dra_shared_colocate:
        req_vec = req_vec + jnp.where(carry.placed_count == 0,
                                      consts["shared_req_vec"], 0.0)
    requested = _row_add(carry.requested, chosen, (gate * req_vec)[None, :])
    nonzero = _row_add(carry.nonzero, chosen,
                       (gate * consts["req_nonzero"])[None, :])
    placed = _row_add(carry.placed, chosen,
                      place.astype(jnp.int32).reshape(1))

    sh_cnt = carry.sh_cnt
    if cfg.spread_hard_n > 0:
        dom_ch = _col(consts["sh_dom"], chosen)
        inc = (consts["sh_self"] & _col(consts["sh_countable"], chosen)
               ).astype(dt) * gate
        sh_cnt = spread_ops.dense_count_update(carry.sh_cnt,
                                               consts["sh_dom"], dom_ch, inc)
    ss_cnt = carry.ss_cnt
    if cfg.spread_soft_n > 0:
        dom_ch = _col(consts["ss_dom"], chosen)
        inc = (consts["ss_self"] & _col(consts["ss_countable"], chosen)
               ).astype(dt) * gate
        ss_cnt = spread_ops.dense_count_update(carry.ss_cnt,
                                               consts["ss_dom"], dom_ch, inc)

    aff_cnt, anti_cnt, pref_cnt = carry.aff_cnt, carry.anti_cnt, carry.pref_cnt
    aff_total = carry.aff_total
    if cfg.ipa_num_aff > 0 or cfg.ipa_num_anti > 0 or cfg.ipa_num_pref > 0:
        ipa_dom_ch = _col(consts["ipa_dom"], chosen)
        ipa_valid = (ipa_dom_ch >= 0).astype(dt)
    if cfg.ipa_num_aff > 0:
        inc = consts["ipa_aff_ginc"] * ipa_valid * gate
        aff_cnt = spread_ops.dense_count_update(carry.aff_cnt,
                                                consts["ipa_dom"],
                                                ipa_dom_ch, inc)
        aff_total = carry.aff_total + jnp.sum(inc)
    if cfg.ipa_num_anti > 0:
        inc = consts["ipa_anti_ginc"] * ipa_valid * gate
        anti_cnt = spread_ops.dense_count_update(carry.anti_cnt,
                                                 consts["ipa_dom"],
                                                 ipa_dom_ch, inc)
    if cfg.ipa_num_pref > 0:
        # ipa_pref_gw carries the pre-folded per-placement group weight: 2x
        # for soft terms (both directions of processExistingPod apply between
        # identical clones), 1x HardPodAffinityWeight for required terms.
        inc = consts["ipa_pref_gw"] * ipa_valid * gate
        pref_cnt = spread_ops.dense_count_update(carry.pref_cnt,
                                                 consts["ipa_dom"],
                                                 ipa_dom_ch, inc)

    return Carry(
        requested=requested, nonzero=nonzero, placed=placed,
        sh_cnt=sh_cnt, ss_cnt=ss_cnt,
        aff_cnt=aff_cnt, anti_cnt=anti_cnt, pref_cnt=pref_cnt,
        aff_total=aff_total,
        placed_count=carry.placed_count + place.astype(jnp.int32),
        stopped=carry.stopped,
        next_start=jnp.where(carry.stopped, carry.next_start, next_start),
        rng=rng,
    )


@functools.lru_cache(maxsize=None)
def _chunk_runner():
    """Module-level jitted scan, cached once; jit's own cache then reuses
    compiled executables across solves keyed on (cfg, shapes, n)."""
    import jax

    @functools.partial(jax.jit, static_argnames=("cfg", "n"))
    def run_chunk(cfg: StaticConfig, consts, carry: Carry, n: int):
        def body(c, _):
            return _step(cfg, consts, c)
        return jax.lax.scan(body, carry, None, length=n)

    return run_chunk


def _ensure_x64(profile):
    import jax
    if profile.compute_dtype == "float64" and not jax.config.jax_enable_x64:
        # Parity mode promises bit-exact int64 score math; float32 silently
        # breaks it near capacity boundaries.  Enable x64 for the process.
        # concgate: disable=LK005 -- idempotent one-shot latch: fires only
        # while x64 is still off, and every threaded entry point (daemon
        # CLI, test harness) enables x64 at startup before worker threads
        # exist, so a concurrent mid-trace flip cannot occur
        jax.config.update("jax_enable_x64", True)


def solve(pb: enc.EncodedProblem, max_limit: int = 0,
          chunk_size: int = 1024, mesh=None, explain: bool = False,
          bounds: bool = True) -> SolveResult:
    """Run the greedy placement loop to completion.

    The scan runs in fixed-size chunks of a jitted `lax.scan`; chunks repeat
    until the carry reports a stop or the step budget is exhausted.

    With `mesh` given, consts and carry shard over it (node axis across
    devices, multi-host included) and XLA inserts the ICI/DCN collectives;
    placements are identical to the unsharded solve.

    With `explain`, the solve runs the explain scan runner instead of the
    canonical one (same placements — the explain step replays _step
    op-for-op) and attaches an explain/artifacts.Explanation to the result:
    why-here score attribution per placement, the why-not elimination tensor
    per node, and the bottleneck table.  Attribution rides the scan as extra
    outputs read back at the same per-chunk sync the solve already pays; the
    fused Pallas drive is skipped (it packs the carry in kernel-private
    layout and exposes no per-step score terms).  `explain` is ignored on
    mesh-sharded solves.

    With `bounds` (default), the step budget is clamped to the capacity
    upper bound + 1 (bounds/bracket.py) so unlimited-profile solves stop
    scanning right after saturation instead of burning the full hint;
    placements and messages are unchanged — the bound always admits the
    exhaustion step."""
    import jax
    import numpy as np

    if pb.snapshot.num_nodes == 0:
        return SolveResult(placements=[], placed_count=0,
                           fail_type=FAIL_UNSCHEDULABLE,
                           fail_message="0/0 nodes are available",
                           node_names=[])

    if pb.pod_level_reason:
        # PreEnqueue/PreFilter pod-level rejection: the FitError message is
        # "0/N nodes are available: <PreFilterMsg>." (types.go:788-793).
        n = pb.snapshot.num_nodes
        expl_obj = None
        if explain:
            from ..explain import artifacts as _art
            expl_obj = _art.build_explanation(
                pb, histogram={pb.pod_level_reason: n}, rung="scan")
        return SolveResult(
            placements=[], placed_count=0,
            fail_type=pb.pod_level_fail_type,
            fail_message=f"0/{n} nodes are available: {pb.pod_level_reason}.",
            fail_counts={pb.pod_level_reason: n},
            node_names=pb.snapshot.node_names,
            explain=expl_obj)

    _ensure_x64(pb.profile)
    with obs.span("cc.setup"):
        cfg = cached_static_config(pb)
        consts = cached_consts(pb)
        carry = _init_carry(pb, consts, pb.profile.seed)
        host_consts = consts
        if mesh is not None:
            from ..parallel import mesh as mesh_lib
            consts = mesh_lib.shard_consts(mesh, consts)
            carry = mesh_lib.shard_carry(mesh, carry)
        run_chunk = _chunk_runner()

        budget = pb.max_steps_hint + 1
        if max_limit and max_limit > 0:
            budget = min(max_limit, budget)
        budget = max(1, min(budget, _DEFAULT_UNLIMITED_CAP))
        if bounds:
            # right-size against the capacity upper bound (bounds/
            # bracket.py, host f64 — same caps formula the fast path uses):
            # the scan cannot place more than `upper` clones, so the final
            # chunk stops wasting steps past saturation.  +1 keeps one step
            # past the bound so the scan still discovers exhaustion and
            # emits the FitError message.
            from ..bounds.bracket import upper_bound_host
            budget = max(1, min(budget, upper_bound_host(pb) + 1))
        # Chunks always run at full length (steps no-op once stopped) so one
        # compiled executable serves every solve of this shape; placements are
        # trimmed to the budget afterwards.
        chunk_size = min(chunk_size, budget)

        # The fused Pallas kernel runs whole chunks in one device kernel
        # when the config allows; its first min(48, budget) steps are
        # cross-checked against the XLA step, and a divergence or
        # compile/runtime failure raises a KernelFault on the chip
        # (fused.mark_failed).  Between fused chunks the carry stays packed
        # on device — only the chosen indices and the stop flag cross to
        # the host.
        from . import fused
        explain = explain and mesh is None
        fused_runner = None
        if mesh is None and not explain:
            # the Pallas kernel is single-device; meshes use XLA.  Explain
            # also takes the XLA scan: the fused kernel's packed carry
            # exposes no per-step score terms to attribute.
            fused_runner = fused.make_runner(
                cfg, pb, consts,
                verify_against=(consts, carry, min(48, budget)))

    placements: List[int] = []
    stopped = False
    if fused_runner is not None:
        # Pipelined fused drive: a host round trip per chunk would dominate
        # the kernel's per-chunk cost, so (a)
        # each sync covers a WINDOW of chained chunks, the window doubling
        # from one chunk up to _FUSED_PIPELINE — an early stop wastes at
        # most as many speculative steps as were already executed — and (b)
        # up to _FUSED_INFLIGHT windows stay issued AHEAD of the one being
        # collected, so each sync's round trip overlaps the device execution
        # of the windows behind it.  Steps after a stop are no-ops inside
        # the kernel, so speculation never affects the placement sequence.
        from collections import deque
        fused_chunk = min(max(chunk_size, _FUSED_CHUNK), budget)
        # Mid-solve re-verification: at each checkpoint
        # the solve snapshots the carry, then compares the NEXT window's
        # first 48 fused placements against the XLA step run from that
        # snapshot.  A divergence proves the kernel wrong somewhere, so
        # EVERYTHING it produced is suspect: the solve restarts from the
        # initial carry on pure XLA (mark_failed bans the shape).  Keyed by
        # kernel shape AND the kernel's and the XLA step's inputs (consts,
        # initial carry, static config) — different cluster data under the
        # same shape re-verifies; a re-encode of the same cluster does not.
        # `due` counts the checkpoints this solve still has to verify.
        with obs.span("cc.verify") as sp:
            verify_key = (fused_runner.pk.meta, fused_runner.interpret,
                          fused.kernel_input_fingerprint(cfg, pb))
            done_ckpts = fused._verified_windows.setdefault(verify_key,
                                                            set())
            ckpts = [c for c in fused.verify_checkpoints(budget, fused_chunk)
                     if c not in done_ckpts]
            sp.attrs["due"] = len(ckpts)
        pending = None          # (carry at snapshot, checkpoint step)
        carry0 = carry
        diverged = False
        last_good = None
        try:
            with obs.span("cc.setup"):
                fused_state = fused_runner.pack(carry)
            last_good = fused_state
            inflight: deque = deque()
            issued = 0
            steps_done = 0
            depth = 1
            while True:
                while (issued < budget and not stopped
                       and len(inflight) < _FUSED_INFLIGHT):
                    w = min(depth, -(-(budget - issued) // fused_chunk))
                    fused_state, window = fused_runner.issue_window(
                        fused_state, fused_chunk, w)
                    inflight.append((fused_state, window))
                    issued += w * fused_chunk
                    depth = min(depth * 2, _FUSED_PIPELINE)
                if not inflight:
                    break
                state_after, window = inflight.popleft()
                chosen, stopped = fused_runner.collect(window)
                if pending is not None:
                    carry_v, ckpt = pending
                    pending = None
                    w_v = min(48, len(chosen))
                    with obs.span("cc.verify"):
                        _xc, x_chosen = run_chunk(cfg, consts, carry_v, w_v)
                        if not np.array_equal(np.asarray(x_chosen),
                                              chosen[:w_v]):
                            fused.mark_failed(
                                fused_runner, "mid-solve cross-check "
                                f"divergence at checkpoint step {ckpt}")
                            diverged = True
                            break
                        done_ckpts.add(ckpt)
                        fused.STATS["verified_windows"].append(
                            (ckpt, fused_runner.pk.meta.n))
                last_good = state_after
                placements.extend(chosen[chosen >= 0].tolist())
                steps_done += len(chosen)
                nxt = next((c for c in ckpts
                            if c <= steps_done and c not in done_ckpts),
                           None)
                if nxt is not None and not stopped:
                    pending = (fused_runner.unpack(last_good, carry), nxt)
            if not diverged:
                carry = fused_runner.unpack(last_good, carry)
            else:
                # a proven divergence taints every fused placement, not just
                # the window it was caught in — restart clean on XLA
                placements.clear()
                carry = carry0
                stopped = False
        except Exception as e:
            # Lazy Mosaic compile/runtime failure: raises on the chip
            # (mark_failed); in interpret mode the XLA loop below resumes
            # from last_good, the carry after the last window whose sync
            # SUCCEEDED — placements collected so far end exactly there.
            from ..runtime.errors import RuntimeFault
            if isinstance(e, RuntimeFault):
                raise
            fused.mark_failed(fused_runner, f"{type(e).__name__}: {e}", e)
            if last_good is not None:
                carry = fused_runner.unpack(last_good, carry)
            stopped = False    # unknown at the fallback point; XLA decides
    expl_state = None
    why_rows: List[np.ndarray] = []
    if explain:
        import jax.numpy as jnp
        from ..explain import attribution as _attr
        run_explain = _attr.chunk_runner()
        static_code_dev = jnp.asarray(pb.static_code, dtype=jnp.int32)
        expl_state = _attr.init_state(carry)
        while not stopped and len(placements) < budget:
            with obs.span("cc.issue", steps=chunk_size, lanes=1):
                expl_state, (chosen, contribs) = run_explain(
                    cfg, consts, static_code_dev, expl_state, chunk_size)
            carry = expl_state.carry
            with obs.span("cc.wait"):
                stopped = bool(np.asarray(carry.stopped))
                chosen = np.asarray(chosen)
            keep = chosen >= 0
            placements.extend(chosen[keep].tolist())
            why_rows.append(np.asarray(contribs)[keep])
    else:
        while not stopped and len(placements) < budget:
            with obs.span("cc.issue", steps=chunk_size, lanes=1):
                carry, chosen = run_chunk(cfg, consts, carry, chunk_size)
            with obs.span("cc.wait"):
                stopped = bool(np.asarray(carry.stopped))
                chosen = np.asarray(chosen)
            placements.extend(chosen[chosen >= 0].tolist())
            if stopped:
                break
    placements = placements[:budget]
    placed = len(placements)
    with obs.span("cc.wait"):
        stopped = bool(np.asarray(carry.stopped))

    expl_obj = None
    if expl_state is not None:
        from ..explain import artifacts as _art
        from ..explain import attribution as _attr
        codes, insuff, toomany = _attr.final_codes_runner()(
            cfg, consts, static_code_dev, carry)
        why_here = (np.concatenate(why_rows)[:placed] if why_rows
                    else np.zeros((0, len(_art.PLUGINS))))
        expl_obj = _art.build_explanation(
            pb, why_here=why_here,
            final_codes=np.asarray(codes),
            elim_step=np.asarray(expl_state.elim_step),
            elim_code=np.asarray(expl_state.elim_code),
            insufficient=np.asarray(insuff),
            too_many=np.asarray(toomany),
            rung="scan")

    if max_limit and placed >= max_limit:
        # postBindHook limit semantics (simulator.go:297-312).
        return SolveResult(placements=placements, placed_count=placed,
                           fail_type=FAIL_LIMIT_REACHED,
                           fail_message=f"Maximum number of pods simulated: {max_limit}",
                           node_names=pb.snapshot.node_names,
                           explain=expl_obj)
    if mesh is not None and jax.process_count() > 1:
        # gather the node-sharded carry to every host for diagnosis (one
        # all-gather over DCN at the very end of the solve)
        carry = jax.tree.map(np.asarray, _replicator(mesh)(carry))
    if stopped:
        counts = diagnose(pb, cfg, host_consts, carry)
        msg = format_fit_error(pb.snapshot.num_nodes, counts)
        return SolveResult(placements=placements, placed_count=placed,
                           fail_type=FAIL_UNSCHEDULABLE, fail_message=msg,
                           fail_counts=counts,
                           node_names=pb.snapshot.node_names,
                           explain=expl_obj)
    # Internal step budget exhausted without a user limit (only reachable when
    # the fit filter is disabled, so the hint bound is not authoritative).
    return SolveResult(placements=placements, placed_count=placed,
                       fail_type=FAIL_LIMIT_REACHED,
                       fail_message=(f"Simulation step budget exhausted after "
                                     f"{placed} placements; set max_limit to "
                                     f"bound unlimited profiles"),
                       node_names=pb.snapshot.node_names,
                       explain=expl_obj)


@functools.lru_cache(maxsize=8)
def _replicator(mesh):
    """Jitted identity that gathers a node-sharded carry to every host;
    the single out_sharding is a pytree prefix, broadcast to every carry
    leaf.  Cached per mesh so repeated multi-host solves reuse one
    compiled all-gather instead of retracing at the end of each solve."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.jit(lambda c: c, out_shardings=NamedSharding(mesh, P()))


def diagnose(pb: enc.EncodedProblem, cfg: StaticConfig, consts,
             carry: Carry, eanti_dyn=None,
             ports_blocked=None) -> Dict[str, int]:
    """Per-reason node counts at the stopping state — the tensor equivalent of
    the FitError reasons histogram (types.go:787-828).  Each infeasible node
    contributes the reason(s) of its first failing plugin in filter order; the
    fit plugin contributes every insufficient resource (fit.go:564-660)."""
    with obs.span("cc.diagnose"):
        feasible, parts = _feasibility(cfg, consts, carry, eanti_dyn=eanti_dyn,
                                       ports_blocked=ports_blocked)
        n = pb.snapshot.num_nodes
        static_code = np.asarray(pb.static_code)

        fit = parts.get("fit")
        fit_fail = ~np.asarray(fit.mask) if fit is not None \
            else np.zeros(n, bool)
        insufficient = np.asarray(fit.insufficient) if fit is not None \
            else None
        too_many = np.asarray(fit.too_many_pods) if fit is not None else None
        ports_dyn_fail = ~np.asarray(parts["ports_dyn"]) \
            if "ports_dyn" in parts else np.zeros(n, bool)
        spread_ok = np.asarray(parts.get("spread_ok", np.ones(n, bool)))
        spread_missing = np.asarray(parts.get("spread_missing",
                                              np.zeros(n, bool)))
        if "ipa" in parts:
            f_aff, f_anti, f_eanti = (np.asarray(x) for x in parts["ipa"])
        else:
            f_aff = f_anti = f_eanti = np.zeros(n, bool)

        counts: Dict[str, int] = {}

        def add(reason: str, k: int = 1):
            if k:
                counts[reason] = counts.get(reason, 0) + int(k)

        # Vectorized first-fail attribution in plugin order.  `remaining` tracks
        # nodes not yet attributed to an earlier plugin.
        remaining = np.ones(n, dtype=bool)

        # static (pre-fit) codes, incl. per-taint message strings
        static_fail = static_code != enc.CODE_OK
        for code in np.unique(static_code[static_fail]):
            idxs = np.flatnonzero(static_code == code)
            if int(code) == enc.CODE_TAINT:
                for i in idxs:
                    add(pb.taint_reasons[i] or "node(s) had untolerated taint")
            else:
                add(enc.STATIC_REASONS[int(code)], len(idxs))
        remaining &= ~static_fail

        take = remaining & ports_dyn_fail
        add(enc.STATIC_REASONS[enc.CODE_PORTS], int(take.sum()))
        remaining &= ~take

        take = remaining & fit_fail
        if take.any():
            from ..ops.dynamic_resources import (DRA_RESOURCE_PREFIX,
                                                 REASON_CANNOT_ALLOCATE)
            if too_many is not None:
                add("Too many pods", int((take & too_many).sum()))
            if insufficient is not None:
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

        vol_fail = ~pb.volume_mask
        take = remaining & vol_fail
        for i in np.flatnonzero(take):
            add(pb.volume_reasons[i] or "volume conflict")
        remaining &= ~take

        if cfg.volume_self_conflict \
                and float(np.asarray(consts["vol_self_gate"])) > 0:
            placed_np = np.asarray(carry.placed)
            take = remaining & (placed_np > 0)
            from ..ops.volumes import REASON_DISK_CONFLICT
            add(REASON_DISK_CONFLICT, int(take.sum()))
            remaining &= ~take
        if cfg.rwop_self_conflict \
                and float(np.asarray(consts["rwop_gate"])) > 0 \
                and int(np.asarray(carry.placed_count)) > 0:
            from ..ops.volumes import REASON_RWOP_CONFLICT
            add(REASON_RWOP_CONFLICT, int(remaining.sum()))
            remaining &= False
        if cfg.dra_shared_colocate \
                and float(np.asarray(consts["dra_colo_gate"])) > 0 \
                and int(np.asarray(carry.placed_count)) > 0:
            from ..ops.dynamic_resources import REASON_CANNOT_ALLOCATE
            placed_np = np.asarray(carry.placed)
            take = remaining & ~(placed_np > 0)
            add(REASON_CANNOT_ALLOCATE, int(take.sum()))
            remaining &= ~take

        take = remaining & spread_missing
        add(enc.STATIC_REASONS[enc.CODE_SPREAD_MISSING_LABEL], int(take.sum()))
        remaining &= ~take
        take = remaining & ~spread_ok
        add(enc.STATIC_REASONS[enc.CODE_SPREAD], int(take.sum()))
        remaining &= ~take

        for mask, code in ((f_aff, enc.CODE_IPA_AFFINITY),
                           (f_anti, enc.CODE_IPA_ANTI),
                           (f_eanti, enc.CODE_IPA_EXISTING_ANTI)):
            take = remaining & mask
            add(enc.STATIC_REASONS[code], int(take.sum()))
            remaining &= ~take

        return counts


def format_fit_error(num_nodes: int, counts: Dict[str, int]) -> str:
    """FitError.Error() (types.go:787-828): '0/N nodes are available: '
    + lexicographically-sorted '<count> <reason>' strings + '.'"""
    reason_strings = sorted(f"{v} {k}" for k, v in counts.items())
    msg = f"0/{num_nodes} nodes are available"
    if reason_strings:
        msg += ": " + ", ".join(reason_strings) + "."
    return msg
