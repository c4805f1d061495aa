"""Batched what-if sweeps: many pod templates against one snapshot.

The reference answers one podspec per process run; sweeping (the genpod use
case, BASELINE.md config 3) costs a full simulator run per spec.  Here the
sweep is a leading `vmap` axis over templates: per-template request vectors,
static masks and static score vectors stack to [B, ...] tensors, and the scan
engine runs all B greedy simulations in lockstep on device — sharded over a
(batch, nodes) mesh when one is provided.

Topology-constrained templates batch too: per-template PodTopologySpread and
InterPodAffinity state is carried as per-node count tensors whose constraint/
group axes pad to a group-wide maximum with inert always-pass rows, so
heterogeneous spread/affinity templates (BASELINE config 3) share one
compiled vmapped solve — bit-identical to their sequential solves
(tests/test_sweep_batched.py).  Only clone self-conflict gates (host ports,
inline-disk, RWOP, shared DRA claims) and pod-level rejections stay
sequential.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import obs
from ..engine import encode as enc
from ..engine import simulator as sim
from ..models.snapshot import ClusterSnapshot
from ..utils.config import SchedulerProfile
from . import mesh as mesh_lib


def _self_conflict_gates(pb: enc.EncodedProblem) -> set:
    """Named clone self-conflict gates on a template.  Single source for
    _batchable and interleave.eligible: the interleave engine subtracts the
    gates it runs natively ('disk', 'rwop' — per-template consts scalars ×
    per-template Carry views), so a NEW gate added here falls both engines
    back together until someone deliberately tensorizes it."""
    out = set()
    if pb.volume_self_conflict:
        out.add("disk")
    if pb.rwop_self_conflict:
        out.add("rwop")
    if pb.dra_shared_colocate:
        out.add("dra")
    return out


def _clone_self_conflict(pb: enc.EncodedProblem) -> bool:
    return bool(_self_conflict_gates(pb))


def _batchable(pb: enc.EncodedProblem) -> bool:
    """Templates whose constraints can ride a vmapped group solve.  Spread
    and inter-pod-affinity templates batch too (their per-node count tensors
    pad to a group-wide constraint/group count with inert rows); only the
    rare clone self-conflict gates and pod-level rejections stay sequential."""
    return (not pb.clone_has_host_ports and
            pb.pod_level_reason is None and not _clone_self_conflict(pb))


def _group_key(pb: enc.EncodedProblem, cfg) -> tuple:
    """Group templates that can share ONE compiled vmapped step.  Count
    fields that padding makes uniform are normalized to any/none; everything
    else in StaticConfig must match exactly."""
    norm = cfg._replace(
        spread_hard_n=0, spread_soft_n=0,
        ipa_num_aff=0, ipa_num_anti=0, ipa_num_pref=0,
        ipa_filter_on=False, ipa_score_active=False, na_active=False,
        volume_filter_on=False,
        # the lonely-pod escape statics only matter to templates with
        # required affinity terms; others merge freely
        ipa_escape_allowed=cfg.ipa_escape_allowed if cfg.ipa_num_aff else False,
        ipa_static_empty=cfg.ipa_static_empty if cfg.ipa_num_aff else False,
    )
    return (norm, pb.req_vec.shape, pb.fit_res_idx.shape,
            pb.balanced_res_idx.shape)


def _pad_group(pbs: List[enc.EncodedProblem]) -> tuple:
    """Pad every template's constraint/group axes to the group maxima.
    Returns (padded problems, uniform StaticConfig, ss_dnh)."""
    from ..ops import inter_pod_affinity as ipa_ops
    from ..ops import pod_topology_spread as spread_ops
    import dataclasses

    ch = max(pb.spread_hard.node_domain.shape[0] for pb in pbs)
    cs = max(pb.spread_soft.node_domain.shape[0] for pb in pbs)
    g = max(pb.ipa.node_domain.shape[0] for pb in pbs)
    dnh = max(sim._soft_nonhost_domains(pb.spread_soft) for pb in pbs)

    padded = []
    for pb in pbs:
        padded.append(dataclasses.replace(
            pb,
            spread_hard=spread_ops.pad_constraints(pb.spread_hard, ch),
            spread_soft=spread_ops.pad_constraints(pb.spread_soft, cs),
            ipa=ipa_ops.pad_groups(pb.ipa, g)))

    # Uniform step config: count gates switch on when ANY template needs the
    # plugin — inert padded rows make it a no-op for the others.
    cfgs = [sim.static_config(pb) for pb in padded]
    aff_cfgs = [c for c in cfgs if c.ipa_num_aff]
    cfg = cfgs[0]
    cfg = cfg._replace(
        spread_hard_n=max(c.spread_hard_n for c in cfgs),
        spread_soft_n=max(c.spread_soft_n for c in cfgs),
        ipa_num_aff=max(c.ipa_num_aff for c in cfgs),
        ipa_num_anti=max(c.ipa_num_anti for c in cfgs),
        ipa_num_pref=max(c.ipa_num_pref for c in cfgs),
        ipa_filter_on=any(c.ipa_filter_on for c in cfgs),
        ipa_score_active=any(c.ipa_score_active for c in cfgs),
        na_active=any(c.na_active for c in cfgs),
        volume_filter_on=any(c.volume_filter_on for c in cfgs),
        ipa_escape_allowed=any(c.ipa_escape_allowed for c in aff_cfgs),
        ipa_static_empty=any(c.ipa_static_empty for c in aff_cfgs),
    )
    return padded, cfg, dnh


def sweep(snapshot: ClusterSnapshot, templates: Sequence[dict],
          profile: Optional[SchedulerProfile] = None, max_limit: int = 0,
          mesh=None, queue_sort: bool = False,
          explain: bool = False,
          bounds: bool = True) -> List[sim.SolveResult]:
    """Solve capacity for every template; batched where possible.

    queue_sort=True orders the templates the way the scheduling queue would
    (PrioritySort: priority desc, creation asc — ops/priority_sort.py) before
    solving; results still align with the INPUT order.

    explain=True attaches full attribution (why-here + why-not + bottleneck)
    to every result by routing each template through the per-template
    hardened ladder instead of the batched kernels — attribution is a
    per-template product, and explain is an opt-in diagnostic mode, so the
    sweep trades the batched throughput for it.  Placements are identical
    either way (the rungs are pairwise bit-identical)."""
    profile = profile or SchedulerProfile()
    templates = list(templates)
    if queue_sort:
        from ..ops.priority_sort import sort_pods
        order = sort_pods(templates, snapshot.priority_classes)
        # solve in queue order, then restore input alignment
        results_by_id = {}
        for t in order:
            results_by_id[id(t)] = None
        ordered_results = sweep(snapshot, order, profile=profile,
                                max_limit=max_limit, mesh=mesh,
                                explain=explain, bounds=bounds)
        for t, r in zip(order, ordered_results):
            results_by_id[id(t)] = r
        return [results_by_id[id(t)] for t in templates]
    problems = [enc.encode_problem(snapshot, t, profile) for t in templates]

    from ..engine import fast_path

    results: List[Optional[sim.SolveResult]] = [None] * len(templates)

    # Behavioral dedup: solve one representative per signature class and
    # share the result (the solve is a pure function of the encoded
    # tensors; only the representative's result object is built once and
    # reused read-only).
    digest_cache: dict = {}
    sig_rep: Dict[bytes, int] = {}
    dup_of: Dict[int, int] = {}
    rep_idx: List[int] = []
    for i, pb in enumerate(problems):
        sig = _solve_signature(pb, digest_cache)
        j = sig_rep.get(sig)
        if j is None:
            sig_rep[sig] = i
            rep_idx.append(i)
        else:
            dup_of[i] = j
    # Group batchable templates by their StaticConfig — the jitted step
    # specializes on it, so each group runs as one vmapped solve.  Templates
    # the analytic fast path can solve outright skip the scan entirely:
    # unbounded/large-limit runs as per-template sorts, small-limit runs
    # (the config-5 probe pattern) as ONE batched [B, N*K] argsort per
    # group (fast_path.solve_fast_batched).
    groups: Dict[tuple, List[int]] = {}
    fp_groups: Dict[tuple, List[int]] = {}
    rest_idx: List[int] = []
    # the batched analytic solve is single-device; under a mesh it stays
    # off — fully-eligible templates then take the (also single-device,
    # exact) unbounded analytic path, and only batchable groups of 2+ run
    # the sharded scan
    small_limit = bool(max_limit) and max_limit <= 4096 and mesh is None
    for i in rep_idx:
        pb = problems[i]
        if explain:
            # attribution is a per-template product (why-here needs the
            # per-step score terms) — the ladder serves every template
            rest_idx.append(i)
        elif not small_limit and fast_path.eligible(pb):
            rest_idx.append(i)    # unbounded analytic (pre-mesh semantics)
        elif small_limit and fast_path.eligible_limited(pb):
            key = _group_key(pb, sim.static_config(pb))
            fp_groups.setdefault(key, []).append(i)
        elif _batchable(pb):
            key = _group_key(pb, sim.static_config(pb))
            groups.setdefault(key, []).append(i)
        else:
            rest_idx.append(i)

    from ..runtime import degrade, faults, guard
    from ..runtime.errors import RuntimeFault

    # Sweep progress gauges (obs/names.py): how the template set split
    # across solve modes — sequential count is refreshed below once
    # singleton groups fold into rest_idx.
    from ..obs import names as obs_names
    from ..utils.metrics import default_registry as _registry
    _registry.set_gauge(obs_names.SWEEP_TEMPLATES, len(templates))
    _registry.set_gauge(obs_names.SWEEP_GROUPS, len(fp_groups),
                        mode="fast_path")
    _registry.set_gauge(obs_names.SWEEP_GROUPS, len(groups), mode="batched")

    for _key, idxs in fp_groups.items():
        if len(idxs) == 1:
            rest_idx.append(idxs[0])
            continue
        try:
            batch = guard.run(
                lambda idxs=idxs: fast_path.solve_fast_batched(
                    [problems[i] for i in idxs], max_limit),
                site=faults.SITE_FAST_PATH,
                validate_nodes=snapshot.num_nodes,
                rung=degrade.RUNG_FAST_PATH, batch=len(idxs))
        except RuntimeFault:
            # batched analytic kernel faulted: the per-template ladder
            # below serves these, flagged degraded
            for i in idxs:
                results[i] = degrade.solve_one_guarded(
                    problems[i], max_limit=max_limit, degraded=True)
            continue
        for i, r in zip(idxs, batch):
            if r is None:
                rest_idx.append(i)        # zero capacity / monotonicity
            else:
                results[i] = r

    # Batched groups and per-template solves run under the hardened runtime
    # (runtime/degrade.py): OOM splits a group geometrically, other
    # classified faults descend the ladder, results carry rung/degraded.
    from ..runtime import degrade

    for cfg_key, idxs in groups.items():
        if len(idxs) == 1:
            rest_idx.append(idxs[0])
            continue
        batch_results = degrade.solve_group_guarded(
            [problems[i] for i in idxs], max_limit=max_limit, mesh=mesh,
            bounds=bounds)
        for i, r in zip(idxs, batch_results):
            results[i] = r

    _registry.set_gauge(obs_names.SWEEP_GROUPS, len(rest_idx),
                        mode="sequential")
    for i in rest_idx:
        results[i] = degrade.solve_one_guarded(problems[i],
                                               max_limit=max_limit,
                                               explain=explain,
                                               bounds=bounds)
    if dup_of:
        import dataclasses as _dc
        for i, j in dup_of.items():
            r = results[j]
            # replace() copies the dataclass but still aliases its mutable
            # fields; give each duplicate its own placements/fail_counts so
            # a caller mutating one result can't corrupt its class siblings
            # (node_names stays shared — it is read-only by convention).
            if _dc.is_dataclass(r):
                results[i] = _dc.replace(r, placements=list(r.placements),
                                         fail_counts=dict(r.fail_counts))
            else:
                results[i] = r
    return results  # type: ignore[return-value]


def _solve_signature(pb: enc.EncodedProblem, digest_cache: dict) -> bytes:
    """Content hash of everything the engine reads from an EncodedProblem.
    Two templates with equal signatures (against the same snapshot/profile)
    are behaviorally identical — the solve is a pure function of these
    tensors — so a sweep solves one representative per class and shares the
    result (what-if sweeps routinely submit near-duplicate templates whose
    labels only reference themselves).  Snapshot-memoized arrays hash once
    via the id cache."""
    import hashlib
    import json
    h = hashlib.sha1()        # SHA-NI accelerated on this host class

    def add(v):
        if isinstance(v, np.ndarray):
            key = id(v)
            d = digest_cache.get(key)
            if d is None:
                hb = hashlib.sha1(np.ascontiguousarray(v).tobytes())
                hb.update(repr(v.shape).encode())
                hb.update(v.dtype.str.encode())
                d = hb.digest()
                digest_cache[key] = d
            h.update(d)
        elif isinstance(v, (list, tuple)) and len(v) > 256:
            # long derived lists (one entry per node): pickle in C, digest
            # once per object
            import pickle
            key = id(v)
            d = digest_cache.get(key)
            if d is None:
                d = hashlib.sha1(pickle.dumps(v, protocol=4)).digest()
                digest_cache[key] = d
            h.update(d)
        elif isinstance(v, (list, tuple)):
            h.update(b"(")
            for x in v:
                add(x)
            h.update(b")")
        else:
            h.update(repr(v).encode())

    # The two per-node reason LISTS are pure functions of (snapshot, a small
    # pod slice): hash the slice instead of 50k strings.  Contract pinned at
    # taint_toleration.static_mask_and_reasons / volumes.evaluate — they
    # read only tolerations resp. (namespace, spec.volumes) from the pod.
    from ..models.podspec import pod_tolerations
    from ..ops.taint_toleration import _tols_key
    add(("taint_src", _tols_key(pod_tolerations(pb.pod))))
    spec = pb.pod.get("spec") or {}
    add(("vol_src",
         (pb.pod.get("metadata") or {}).get("namespace") or "default",
         json.dumps(spec.get("volumes"), sort_keys=True, default=str)))

    import dataclasses
    for f in dataclasses.fields(pb):
        if f.name in ("snapshot", "pod", "profile",
                      "taint_reasons", "volume_reasons"):
            continue          # one snapshot/profile per sweep; pod identity
                              # only reaches the engine through the tensors;
                              # reason lists hashed via their sources above
        v = getattr(pb, f.name)
        if dataclasses.is_dataclass(v):
            for g in dataclasses.fields(v):
                if g.name in ("raw_aff_terms", "raw_anti_terms",
                              "raw_soft_terms", "selectors"):
                    # raw labelSelector terms feed ONLY the tensor
                    # interleave engine's cross-template increment matrices
                    # (verified: no engine/ solve path reads them) — two
                    # templates whose selectors differ but encode to the
                    # same tensors place identically, so these must NOT
                    # split a behavior class
                    continue
                add(getattr(v, g.name))
        else:
            add(v)
    return h.digest()


def _group_uniform(arrs: List[np.ndarray]) -> bool:
    """True when every template's array is the same value.  Object identity
    first (snapshot-memoized casts make this the common hit); a content
    compare only for arrays big enough that stacking B copies costs more
    than one memcmp sweep, bailing on the first mismatch."""
    a0 = arrs[0]
    rest = [a for a in arrs[1:] if a is not a0]
    if not rest:
        return True
    if a0.nbytes < (1 << 16):
        return False
    return all(np.array_equal(a, a0) for a in rest)


def solve_group(pbs: List[enc.EncodedProblem], max_limit: int = 0,
                mesh=None, explain: bool = False,
                bounds: bool = True,
                lower_only: bool = False) -> List[sim.SolveResult]:
    """Public batched-group entry for pre-encoded problems.

    The resilience analyzer (resilience/analyzer.py) encodes one problem per
    failure scenario — same probe and profile, per-scenario alive_mask folded
    into static_mask — and solves the family here as ONE batched device solve:
    the scenario axis batches exactly like sweep()'s template axis.  Callers
    must pass problems sharing a group key (_group_key) and batchable shape
    (_batchable); sweep() derives those itself.

    With `explain`, each result carries a why-not Explanation computed from
    its slice of the batched terminal carry (per-template reason codes +
    bottleneck).  Why-here attribution is a per-template product — callers
    wanting it route through the per-template ladder (sweep(explain=True)
    does exactly that).

    `lower_only=True` stops at the traceable boundary: the group is encoded,
    padded, and sharded exactly as a real solve would be, but instead of
    dispatching, the assembled chunk runner and its concrete arguments are
    returned (see _batched_solve) so static analyzers (tools/shardgate) can
    trace/lower the production computation without executing it."""
    # lower_only is forwarded only when set: callers (and tests) wrap
    # _batched_solve with the pre-seam signature, and the solve path must
    # keep calling it exactly as before.
    kw = {"lower_only": True} if lower_only else {}
    return _batched_solve(list(pbs), max_limit, mesh=mesh, explain=explain,
                          bounds=bounds, **kw)


def _batched_solve(pbs: List[enc.EncodedProblem], max_limit: int,
                   mesh=None, explain: bool = False,
                   bounds: bool = True,
                   lower_only: bool = False) -> List[sim.SolveResult]:
    import jax
    import jax.numpy as jnp

    from ..engine import fused_batched

    # Segment huge groups: bounds the batched kernel's HBM slab AND the
    # vmapped executable's working set; templates are independent, so
    # segment results concatenate losslessly.
    if len(pbs) > fused_batched.MAX_BATCH:
        out: List[sim.SolveResult] = []
        for i in range(0, len(pbs), fused_batched.MAX_BATCH):
            out.extend(_batched_solve(pbs[i:i + fused_batched.MAX_BATCH],
                                      max_limit, mesh=mesh, explain=explain,
                                      bounds=bounds))
        return out

    with obs.span("cc.setup"):
        sim._ensure_x64(pbs[0].profile)
        pbs, cfg, dnh = _pad_group(pbs)
        # Host-side consts/carry per template, stacked in numpy, ONE device
        # transfer per key — not ~33 x B small transfers (the r4 profile
        # showed per-template jnp.asarray + jnp.stack dominating the warm
        # sweep).
        consts_list = [sim.build_consts(pb, ss_dnh_min=dnh, device=False)
                       for pb in pbs]
        carry_list = [sim._init_carry(pb, c, pb.profile.seed, device=False)
                      for pb, c in zip(pbs, consts_list)]
        # Group dedup: consts identical across every template (the
        # snapshot's allocatable, shared topology one-hots, ...) ride the
        # vmapped step UNMAPPED — no B-way host stack, no B-way transfer, no
        # B-way read per step.  Only genuinely per-template arrays stack.
        # (The mesh path keeps the full stacked layout: shard_consts shards
        # the batch axis.)
        n_nodes = pbs[0].snapshot.num_nodes
        shared: Dict[str, "jax.Array"] = {}
        if mesh is not None:
            # full stacked layout, padded to the mesh's shard multiples
            # (batch: duplicate templates, node: inert infeasible rows), then
            # ONE sharded device_put per key — XLA's partitioner owns the
            # layout from here and the scan never gathers a node table to one
            # device.
            stacked_np = {k: np.stack([c[k] for c in consts_list])
                          for k in consts_list[0]}
            carry_np = jax.tree.map(lambda *xs: np.stack(xs), *carry_list)
            stacked_np, carry_np = mesh_lib.pad_for_mesh(mesh, stacked_np,
                                                         carry_np)
            stacked = mesh_lib.shard_consts(mesh, stacked_np, batched=True)
            carry = mesh_lib.shard_carry(mesh, carry_np, batched=True)
        else:
            stacked = {}
            for k in consts_list[0]:
                arrs = [c[k] for c in consts_list]
                if _group_uniform(arrs):
                    shared[k] = jnp.asarray(arrs[0])
                else:
                    stacked[k] = jnp.asarray(np.stack(arrs))
            carry = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)),
                                 *carry_list)
        consts = (shared, stacked)

        if bounds:
            # right-size the group budget from the per-template capacity
            # upper bounds (bounds/bracket.py, host f64): the group scans
            # until its LAST template saturates, so the max over (hint,
            # bound)-clamped per-template budgets shaves every step past the
            # slowest template's provable saturation.  +1 keeps the
            # exhaustion-discovery step.
            from ..bounds.bracket import upper_bound_host
            budget = max(min(pb.max_steps_hint, upper_bound_host(pb))
                         for pb in pbs) + 1
        else:
            budget = max(pb.max_steps_hint for pb in pbs) + 1
        if max_limit and max_limit > 0:
            budget = min(max_limit, budget)
        budget = max(1, min(budget, sim._DEFAULT_UNLIMITED_CAP))

        if mesh is not None:
            run_chunk = _batched_chunk_runner_sharded(mesh, consts, carry)
        else:
            run_chunk = _batched_chunk_runner()

    if lower_only:
        # Static-analysis escape hatch (tools/shardgate): hand back the
        # production runner + the exact concrete arguments a real solve
        # would dispatch, WITHOUT running a step.  The chunk quantization
        # below is duplicated so the static arg matches the real call.
        chunk = min(1024, budget)
        if chunk > 1:
            chunk = 1 << (chunk - 1).bit_length()
        b_pad, n_pad = carry.placed.shape
        return {"kind": "sweep", "runner": run_chunk,
                "args": (cfg, consts, carry, chunk),
                "consts": stacked if mesh is not None else {**shared,
                                                            **stacked},
                "carry": carry,
                "meta": {"n_nodes": n_nodes, "n_pad": int(n_pad),
                         "batch": len(pbs), "b_pad": int(b_pad),
                         "chunk": chunk}}

    # The batched fused kernel runs whole chunks for the whole group in one
    # Pallas call (grid over templates, per-template scalars from SMEM) when
    # the group is kernel-eligible — BASELINE configs 3/5 ride it on TPU.
    # Its first min(48, budget) steps are cross-checked against the vmapped
    # XLA step; divergence or compile failure falls back for this group.
    bfused = None
    if mesh is None:
        with obs.span("cc.setup"):
            bfused = fused_batched.make_batched_runner(
                cfg, pbs, consts_list, max_dnh=dnh,
                verify_against=(consts, carry, min(48, budget), run_chunk))

    placements: List[List[int]] = [[] for _ in pbs]
    steps_done = 0
    # Quantize the chunk length up to a power of two: `n` is a static arg of
    # the chunk runner, so without this every budget wobble (the serving
    # daemon's pod churn moves the capacity upper bound a little each drain)
    # would retrace the jit.  Bit-identity is preserved — the budget already
    # exceeds every template's provable saturation, so steps past it place
    # nothing (the loop below stops on all_stopped), and a max_limit-bound
    # budget is re-trimmed after the loop.
    chunk = min(1024, budget)
    if chunk > 1:
        chunk = 1 << (chunk - 1).bit_length()
    bstate = None
    while steps_done < budget:
        if bfused is not None:
            try:
                if bstate is None:
                    with obs.span("cc.setup"):
                        bstate = bfused.pack(carry)
                bstate, chosen, all_stopped = bfused.run_packed(bstate, chunk)
            except Exception as e:
                # Lazy Mosaic compile/runtime failure: raises on the chip
                # (_mark_failed); in interpret mode recover the last
                # completed chunk's carry and resume on the XLA path.
                from ..runtime.errors import RuntimeFault
                if isinstance(e, RuntimeFault):
                    raise
                fused_batched._mark_failed(
                    bfused, f"{type(e).__name__}: {e}", e)
                if bstate is not None:
                    carry = bfused.unpack(bstate, carry)
                bfused = None
                bstate = None
                continue
        else:
            with obs.span("cc.issue", steps=chunk, lanes=len(pbs)):
                carry, chosen = run_chunk(cfg, consts, carry, chunk)
            with obs.span("cc.wait"):
                chosen = np.asarray(chosen)                       # [n, B]
                all_stopped = bool(np.all(np.asarray(carry.stopped)))
        for b in range(len(pbs)):
            col = chosen[:, b]
            placements[b].extend(col[col >= 0].tolist())
        steps_done += chunk
        if all_stopped:
            break
    if max_limit and max_limit > 0:
        placements = [p[:max_limit] for p in placements]

    explain = explain and mesh is None   # attribution is a per-template
    if mesh is not None:
        from ..obs import names as obs_names
        from ..utils.metrics import default_registry
        default_registry.set_gauge(
            obs_names.SHARDED_CARRY_DEVICES,
            len({sh.device for sh in carry.placed.addressable_shards}))
        # slice the node-axis pads back off before any host-side consumer
        # (diagnose reads the carry against the UNPADDED host consts)
        carry = mesh_lib.unpad_carry(carry, n_nodes)
    if bstate is not None:
        # Unpack the packed planes (a [B, P, S*128] device->host round trip)
        # only when some template actually stopped short of its limit and
        # needs the carry for diagnose(), or explain needs terminal codes;
        # pure limit-reached sweeps skip it.
        with obs.span("cc.wait"):
            stopped = bfused.stopped_flags(bstate)
        if explain or any(bool(stopped[b])
                          and not (max_limit
                                   and len(placements[b]) >= max_limit)
                          for b in range(len(pbs))):
            carry = bfused.unpack(bstate, carry)
    else:
        with obs.span("cc.wait"):
            stopped = np.asarray(carry.stopped)

    def _explain_b(pb, b):
        # Why-not from this template's slice of the batched terminal carry:
        # the same jitted final-codes entry every rung shares.  Why-here is
        # not produced here (per-template product; see solve_group doc).
        from ..explain import artifacts as _art
        from ..explain import attribution as _attr
        carry_b = jax.tree.map(lambda x: x[b], carry)
        codes, insuff, toomany = _attr.final_codes_runner()(
            cfg, consts_list[b],
            jnp.asarray(pb.static_code, dtype=jnp.int32), carry_b)
        return _art.build_explanation(
            pb, final_codes=np.asarray(codes),
            insufficient=np.asarray(insuff), too_many=np.asarray(toomany),
            rung="fused_batched")

    results = []
    for b, pb in enumerate(pbs):
        placed = len(placements[b])
        expl_obj = _explain_b(pb, b) if explain else None
        if max_limit and placed >= max_limit:
            results.append(sim.SolveResult(
                placements=placements[b], placed_count=placed,
                fail_type=sim.FAIL_LIMIT_REACHED,
                fail_message=f"Maximum number of pods simulated: {max_limit}",
                node_names=pb.snapshot.node_names, explain=expl_obj))
        elif stopped[b]:
            carry_b = jax.tree.map(lambda x: x[b], carry)
            counts = sim.diagnose(pb, cfg, consts_list[b], carry_b)
            msg = sim.format_fit_error(pb.snapshot.num_nodes, counts)
            results.append(sim.SolveResult(
                placements=placements[b], placed_count=placed,
                fail_type=sim.FAIL_UNSCHEDULABLE, fail_message=msg,
                fail_counts=counts, node_names=pb.snapshot.node_names,
                explain=expl_obj))
        else:
            results.append(sim.SolveResult(
                placements=placements[b], placed_count=placed,
                fail_type=sim.FAIL_LIMIT_REACHED,
                fail_message=(f"Simulation step budget exhausted after "
                              f"{placed} placements"),
                node_names=pb.snapshot.node_names, explain=expl_obj))
    return results


def _add_curable_reasons():
    """pod-ADD QueueingHints analog: failure classes a new pod can cure.
    Shared by the object queue loop and the tensor interleave engine."""
    from ..ops import inter_pod_affinity as ipa_ops
    from ..ops import node_ports as ports_ops
    from ..ops import pod_topology_spread as spread_ops
    return {ipa_ops.REASON_AFFINITY, ipa_ops.REASON_ANTI_AFFINITY,
            ipa_ops.REASON_EXISTING_ANTI, spread_ops.REASON_CONSTRAINTS,
            spread_ops.REASON_MISSING_LABEL, ports_ops.REASON}


def sweep_interleaved(snapshot: ClusterSnapshot, templates: Sequence[dict],
                      profile: Optional[SchedulerProfile] = None,
                      max_total: int = 0) -> List[sim.SolveResult]:
    """Heterogeneous templates racing through ONE shared cluster state, the
    way the reference's scheduling queue would run them (ROADMAP #8).

    Queue semantics (backend/queue/scheduling_queue.go + PrioritySort,
    priority_sort.go): the activeQ pops the highest-priority pod first,
    FIFO within a priority — and because each binding enqueues the
    template's NEXT clone at the tail, equal-priority templates interleave
    round-robin (A0, B0, A1, B1, ...), each placement consuming shared
    capacity.  A template whose clone goes Unschedulable leaves the queue.

    Feature parity with single-template runs (framework.py:129-232):
    extender Filter/Prioritize/Bind run per cycle (filter after the
    sampling window, schedule_one.go:482-565 order), and an Unschedulable
    clone triggers the DefaultPreemption PostFilter (preemption.go:234) —
    victims (initial pods OR lower-priority clones placed by other
    templates) are evicted from the shared state and the preemptor retries
    at the front of its priority tier (approximating the reference's
    nominatedNodeName reservation, schedule_one.go:209: the freed capacity
    is not stolen by an equal-priority peer).  Evictions rebuild the
    working snapshot (volume verdicts included) and are pod-DELETE events:
    every parked template re-enters the queue
    (scheduling_queue.go:177-193).  Placements are pod-ADD events: parked
    templates whose failure was affinity/spread/ports-shaped re-enter too
    (the QueueingHints analog — those are the reasons a new pod can cure).
    Already-bound clones stay in their template's report even when later
    preempted, matching the reference's bind-time accounting (postBindHook
    appends and never removes, simulator.go:297-312).

    This is inherently per-pod sequential (every placement changes every
    other template's world), so it runs on the object-level oracle
    machinery — the parity path for multi-template queue studies."""
    import heapq

    from ..engine import oracle
    from ..engine.extenders import (REASON_EXTENDER_FILTER, make_node_ok,
                                    run_bind, run_filter_chain,
                                    run_prioritize_chain)
    from ..engine.preemption import (evaluate as preempt_evaluate,
                                     format_preemption_message,
                                     resolve_priority, victim_matcher)
    from ..models import podspec as ps
    from ..ops import volumes as vol_ops

    from ..models import snapshot as snapshot_mod

    profile = profile or SchedulerProfile()
    n = snapshot.num_nodes
    snap_cur = snapshot
    state = oracle.OracleState(snapshot)
    extenders = list(profile.extenders or [])
    preempt_on = "DefaultPreemption" in profile.post_filters
    node_objs = {nm: o for nm, o in zip(snapshot.node_names, snapshot.nodes)}

    results: List[Optional[sim.SolveResult]] = [None] * len(templates)
    placements: List[List[int]] = [[] for _ in templates]
    verdicts = [vol_ops.evaluate(snapshot, t, profile.filter_enabled)
                for t in templates]
    placed_per_node = [[0] * n for _ in templates]
    live_clones = [0] * len(templates)      # bound minus evicted
    clone_owner: Dict[int, int] = {}        # id(clone) -> ti
    parked: Dict[int, set] = {}             # ti -> fail-reason keys at park
    # Safety valve for pathological preempt/requeue cycles between priority
    # tiers (the reference can't hit this: it never runs multiple templates)
    preempt_budget = 10 * len(templates) + 100

    _ADD_CURABLE = _add_curable_reasons()

    heap: List[tuple] = []
    seq = 0
    for ti, t in enumerate(templates):
        heapq.heappush(heap, (-resolve_priority(
            t, snapshot.priority_classes), seq, ti))
        seq += 1

    def node_reason(ti: int, i: int) -> Optional[str]:
        t = templates[ti]
        r = oracle._filter_node(state, i, t, profile)
        if r is not None:
            return r
        v = verdicts[ti]
        if ps.pod_host_ports(t) and profile.filter_enabled("NodePorts") \
                and placed_per_node[ti][i] > 0:
            return ("node(s) didn't have free ports for the requested "
                    "pod ports")
        if not v.mask[i]:
            return v.reasons[i]
        if v.self_disk_conflict and placed_per_node[ti][i] > 0:
            return vol_ops.REASON_DISK_CONFLICT
        if v.rwop_self_conflict and live_clones[ti] > 0:
            return vol_ops.REASON_RWOP_CONFLICT
        return None

    def requeue(tis) -> None:
        nonlocal seq
        for tj in sorted(tis):
            if tj in parked:
                del parked[tj]
                results[tj] = None
                heapq.heappush(heap, (-resolve_priority(
                    templates[tj], snapshot.priority_classes), seq, tj))
                seq += 1

    def rebuild_after_eviction(changed) -> None:
        """Evictions invalidate everything derived from the pod set: the
        working snapshot, the per-template volume verdicts, and the oracle
        state.  framework._solve_with_preemption re-snapshots the same way
        (with_pods_by_node incremental, full rebuild fallback)."""
        nonlocal snap_cur, state, verdicts
        new_pbn = state.pods_by_node
        next_snap = snapshot_mod.with_pods_by_node(snap_cur, new_pbn,
                                                   sorted(changed))
        if next_snap is None:
            # keep the existing node-axis order: sort_nodes would re-sort by
            # name and desynchronize every index-based bookkeeping structure
            next_snap = ClusterSnapshot.from_objects(
                snap_cur.nodes, [p for plist in new_pbn for p in plist],
                sort_nodes=False, use_native=False,
                **{k: getattr(snap_cur, k)
                   for k in snapshot_mod.OBJECT_FIELDS})
        snap_cur = next_snap
        state = oracle.OracleState(snap_cur)
        # from_objects dict-copies pods; restore the ORIGINAL clone dicts so
        # clone_owner identity lookups survive any number of rebuilds
        state.pods_by_node = [list(p) for p in new_pbn]
        verdicts = [vol_ops.evaluate(snap_cur, t, profile.filter_enabled)
                    for t in templates]

    # deterministic sampling state per template (numFeasibleNodesToFind —
    # the queue parity path must sample exactly like single-template runs)
    from ..engine.simulator import _num_feasible_nodes_to_find
    sample_k = _num_feasible_nodes_to_find(profile, n)
    next_start = [0] * len(templates)

    total = 0
    front_seq = 0          # decreasing: pops before every same-priority peer
    while heap and (not max_total or total < max_total):
        _prio, _s, ti = heapq.heappop(heap)
        t = templates[ti]
        if (t.get("spec") or {}).get("schedulingGates"):
            # PreEnqueue: gated pods never enter a cycle (sim.solve parity)
            reason = enc.REASON_SCHEDULING_GATED
            results[ti] = sim.SolveResult(
                placements=[], placed_count=0,
                fail_type="SchedulingGated",
                fail_message=f"0/{n} nodes are available: {reason}.",
                fail_counts={reason: n},
                node_names=snapshot.node_names)
            continue
        if verdicts[ti].pod_level_reason:
            results[ti] = sim.SolveResult(
                placements=[], placed_count=0,
                fail_type=sim.FAIL_UNSCHEDULABLE,
                fail_message=f"0/{n} nodes are available: "
                             f"{verdicts[ti].pod_level_reason}.",
                fail_counts={verdicts[ti].pod_level_reason: n},
                node_names=snapshot.node_names)
            continue
        feasible = [i for i in range(n) if node_reason(ti, i) is None]
        scorable: List[int] = []
        ext_rejected = 0
        if feasible:
            scorable, next_start[ti] = oracle.sample_window(
                feasible, n, sample_k, next_start[ti])
            if extenders:
                # extender Filter chain on the SAMPLED window, after the
                # in-tree filters (findNodesThatFitPod order,
                # schedule_one.go:482-565: sample first, extenders second)
                surviving = set(run_filter_chain(
                    extenders, t,
                    [snapshot.node_names[i] for i in scorable], node_objs))
                ext_rejected = sum(1 for i in scorable
                                   if snapshot.node_names[i] not in surviving)
                scorable = [i for i in scorable
                            if snapshot.node_names[i] in surviving]
        if not scorable:
            # DefaultPreemption PostFilter (framework.py:160-221 analog):
            # victims come from the SHARED state — initial pods or other
            # templates' lower-priority clones.
            pre_msg = None
            if preempt_on and preempt_budget > 0:
                outcome = preempt_evaluate(
                    snap_cur, state.pods_by_node, t, profile,
                    node_ok=make_node_ok(extenders, t, snapshot.node_names,
                                         snapshot.nodes),
                    extenders=extenders)
                if outcome.succeeded and outcome.victims:
                    # the valve counts EVICTIONS (the only way a preempt/
                    # requeue cycle can spin); failed evaluations just park
                    preempt_budget -= 1
                    is_victim = victim_matcher(outcome.victims)
                    changed = set()
                    for i in range(n):
                        kept = []
                        for p in state.pods_by_node[i]:
                            if is_victim(p):
                                owner = clone_owner.pop(id(p), None)
                                if owner is not None:
                                    placed_per_node[owner][i] -= 1
                                    live_clones[owner] -= 1
                                changed.add(i)
                            else:
                                kept.append(p)
                        state.pods_by_node[i] = kept
                    rebuild_after_eviction(changed)
                    # pod-delete events reactivate every parked template
                    # (scheduling_queue.go:177-193)
                    requeue(list(parked))
                    # the preemptor retries FIRST within its tier: the
                    # nominatedNodeName reservation analog — its freed
                    # capacity must not be stolen by an equal-priority peer
                    front_seq -= 1
                    heapq.heappush(heap, (_prio, front_seq, ti))
                    next_start[ti] = 0   # fresh cycle, framework parity
                    continue
                if profile.include_preemption_message and \
                        outcome.message_counts:
                    pre_msg = format_preemption_message(
                        n, outcome.message_counts)
            reasons: Dict[str, int] = {}
            if ext_rejected:
                # every in-tree-feasible node went unused only because the
                # extender chain emptied the sampled window — attribute the
                # whole feasible set so counts sum to n (same bucket as
                # solve_with_extenders)
                reasons[REASON_EXTENDER_FILTER] = len(feasible)
            for i in range(n):
                r = node_reason(ti, i)
                if r and (r.startswith("Insufficient")
                          or r == "Too many pods"):
                    for fr in oracle._fit_reasons(state, i, t):
                        reasons[fr] = reasons.get(fr, 0) + 1
                elif r:
                    reasons[r] = reasons.get(r, 0) + 1
            msg = sim.format_fit_error(n, reasons)
            if pre_msg:
                msg += " " + pre_msg
            results[ti] = sim.SolveResult(
                placements=placements[ti],
                placed_count=len(placements[ti]),
                fail_type=sim.FAIL_UNSCHEDULABLE,
                fail_message=msg,
                fail_counts=reasons, node_names=snapshot.node_names)
            parked[ti] = set(reasons)
            continue
        totals = oracle._score_nodes(state, scorable, t, profile)
        if extenders:
            bonus = run_prioritize_chain(
                extenders, t, [snapshot.node_names[i] for i in scorable])
            for i in scorable:
                totals[i] += bonus[snapshot.node_names[i]]
        best = max(scorable, key=lambda i: (totals[i], -i))
        clone = ps.make_clone(t, len(placements[ti]))
        clone["spec"]["nodeName"] = snapshot.node_names[best]
        run_bind(extenders, clone, snapshot.node_names[best])
        placements[ti].append(best)
        placed_per_node[ti][best] += 1
        live_clones[ti] += 1
        state.pods_by_node[best].append(clone)
        clone_owner[id(clone)] = ti
        total += 1
        # pod-ADD event: requeue parked templates whose failure a new pod
        # can cure (affinity/spread/ports — the QueueingHints analog)
        requeue([tj for tj, rs in parked.items() if rs & _ADD_CURABLE])
        heapq.heappush(heap, (_prio, seq, ti))    # next clone to the tail
        seq += 1

    for ti in range(len(templates)):
        if results[ti] is None:                    # stopped by max_total
            results[ti] = sim.SolveResult(
                placements=placements[ti],
                placed_count=len(placements[ti]),
                fail_type=sim.FAIL_LIMIT_REACHED,
                fail_message=f"Maximum number of pods simulated: {max_total}",
                node_names=snapshot.node_names)
    return results


@functools.lru_cache(maxsize=None)
def _batched_chunk_runner():
    """consts is (shared, stacked): `shared` arrays are group-uniform and
    ride the vmapped step unmapped (closure capture — vmap broadcasts);
    `stacked` arrays carry a leading template axis.  A plain dict of fully
    stacked consts still works as ({}, consts)."""
    import jax

    @functools.partial(jax.jit, static_argnames=("cfg", "n"))
    def run_chunk(cfg, consts, carry, n: int):
        shared, stacked = consts if isinstance(consts, tuple) else ({}, consts)

        def body(c, _):
            new_c, chosen = jax.vmap(
                lambda st, cc: sim._step(cfg, {**shared, **st}, cc))(stacked, c)
            return new_c, chosen
        return jax.lax.scan(body, carry, None, length=n)

    return run_chunk


# Compiled sharded runners, keyed on (mesh, shared keys, stacked keys): the
# in/out sharding pytrees depend on which consts the group carries, so the
# jit wrapper is built per key-set and reused — an alive-mask change on a
# fixed mesh hits the same wrapper AND the same executable (shapes, specs
# and StaticConfig all match; tests/test_multichip.py pins zero recompiles).
_SHARDED_RUNNERS: Dict[tuple, object] = {}


def _batched_chunk_runner_sharded(mesh, consts, carry):
    """Mesh-sharded chunk runner: the same vmapped scan step, dispatched
    under jax.jit with explicit `in_shardings` from consts_shardings /
    carry_shardings (batch axis over templates/scenarios, node axis over the
    node tables) and the carry buffer donated — the scan updates the carried
    per-node count planes in place across chunks.  The step's reductions
    (min over countable nodes, global argmax over scores, per-domain spread
    folds) cross the node axis, so GSPMD lowers them to collectives over the
    mesh instead of gathering node tables to one device; the irgate contract
    (IC007) pins that no full node-table all_gather survives lowering."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    shared, stacked = consts
    key = (mesh, tuple(sorted(shared)), tuple(sorted(stacked)))
    fn = _SHARDED_RUNNERS.get(key)
    if fn is not None:
        return fn

    rep = NamedSharding(mesh, P())
    in_sh = (
        ({k: rep for k in shared},
         mesh_lib.consts_shardings(mesh, stacked, batched=True)),
        mesh_lib.carry_shardings(mesh, carry, batched=True),
    )
    # chosen stacks to [n_steps, B]: steps replicated, templates on batch
    out_sh = (in_sh[1], NamedSharding(mesh, P(None, mesh_lib.BATCH_AXIS)))

    @functools.partial(jax.jit, static_argnames=("cfg", "n"),
                       in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnames=("carry",))
    def run_chunk(cfg, consts, carry, n: int):
        shared, stacked = consts

        def body(c, _):
            new_c, chosen = jax.vmap(
                lambda st, cc: sim._step(cfg, {**shared, **st}, cc))(stacked, c)
            return new_c, chosen
        return jax.lax.scan(body, carry, None, length=n)

    _SHARDED_RUNNERS[key] = run_chunk
    return run_chunk
