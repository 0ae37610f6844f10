"""The batched scheduling core: feasibility → score → conflict-resolving
commit, all inside one jitted program.

Replaces the reference's per-pod scheduling cycle (SURVEY.md 3.1,
k8s scheduleOne + frameworkext transformers):

- HOT LOOP #1 (Filter, parallel over nodes) -> a fused [P, N] feasibility
  mask: node schedulable + resource fit + nodeSelector gate + LoadAware
  usage gate + ElasticQuota admission + gang quorum.
- HOT LOOP #2 (Score) -> the LoadAware [P, N] score matrix.
- selectHost + assume + Permit + Bind -> `num_rounds` commit rounds inside
  lax.scan. Each round every unplaced pod picks its best node (argmax = the
  top-k reduce); conflicts on a node are resolved in pod-priority order by a
  sorted segment prefix-sum (the batched equivalent of sequential assume),
  quota admission is enforced per tree level the same way, accepted pods
  scatter their requests/estimates into the carried node and quota tensors,
  and losers retry next round against updated state. Strict gangs that miss
  minMember by the end of the batch are rolled back (Permit barrier,
  coscheduling core.go:311-341).
- Reservations ride the same machinery as VIRTUAL NODE columns (owner-
  restricted, capacity = reserved free, MaxNodeScore preference), so
  consumer admission interleaves exactly with normal pods across the node/
  quota/NUMA prefix gates (plugins/reservation.py).
- NUMA-bound pods additionally pass a zone-level prefix gate and commit
  into zone usage (plugins/numaaware.py).

Sequential-equivalence note: within a round, an accepted pod's effect on the
*scores* of later pods lands at the next round boundary (its effect on
capacity is exact via the prefix sums). With num_rounds >= 2 this matches the
reference's assume semantics at batch granularity; per-pod equivalence is
recovered with chunk size 1 (golden tests do both).

Float note: capacities are float32 in millicores/MiB; prefix sums over a
100k-pod chunk keep absolute error well under one millicore/MiB at realistic
magnitudes, and comparisons use a 0.5-unit tolerance, conservative on the
safe (no-overcommit) side.
"""

from __future__ import annotations

import functools
from typing import Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from koordinator_tpu.scheduler.batching import (
    EPS,
    rank_by_priority,
    segment_prefix_ok,
    stable_rank,
)
from koordinator_tpu import obs
from koordinator_tpu.obs import phases as obs_phases
from koordinator_tpu.scheduler import topologymanager
from koordinator_tpu.scheduler.cascade import stage1_mask, static_gates
from koordinator_tpu.scheduler.plugins import deviceshare, loadaware, numaaware
from koordinator_tpu.scheduler.plugins.numaaware import CPU as CPU_KIND, MEM as MEM_KIND
from koordinator_tpu.scheduler.plugins.reservation import (
    MAX_NODE_SCORE,
    rebuild_reservations,
    slot_columns,
)
from koordinator_tpu.snapshot import hostbatch
from koordinator_tpu.snapshot.schema import (
    ClusterSnapshot,
    MAX_QUOTA_DEPTH,
    NUM_AUX_TYPES,
    NUM_DEV_DIMS,
    PER_POD_FIELDS,
    PodBatch,
    register_struct,
    shape_contract,
)


# ScheduleResult fields indexed by pod row — a caller that reorders the
# batch (prefix packing) must inverse-permute exactly these
PER_POD_RESULT_FIELDS = ("assignment", "chosen_score", "numa_zone",
                         "numa_take", "gpu_take", "aux_inst", "res_slot")


@flax.struct.dataclass
class ScheduleResult:
    assignment: jnp.ndarray      # i32[P] node index, -1 = unschedulable
    chosen_score: jnp.ndarray    # f32[P] score of the chosen node (debug)
    numa_zone: jnp.ndarray       # i32[P] zone taken by NUMA-bound pods, -1
                                 # (feeds the resource-status annotation /
                                 # host cpuset accumulator at bind time)
    numa_take: jnp.ndarray       # f32[P, Z, 2] per-zone (cpu, mem) actually
                                 # charged by topology-engaged pods — multi-
                                 # zone under best-effort/restricted policy
                                 # (resource_manager.go NUMANodeResources)
    gpu_take: jnp.ndarray        # bool[P, I] GPU instances taken on the
                                 # assigned node (feeds the device-allocation
                                 # annotation at bind, plugin.go PreBind)
    aux_inst: jnp.ndarray        # i32[P, A] aux (rdma/fpga) instance, -1
    res_slot: jnp.ndarray        # i32[P] reservation slot consumed, -1 —
                                 # feeds the reservation-allocated
                                 # annotation at bind and the forget path
    gang_failed: jnp.ndarray     # bool[G] strict gangs PROVEN below quorum
                                 # this batch (no members outstanding) —
                                 # members assumed in EARLIER batches still
                                 # hold capacity; the host reclaims them
                                 # through the forget/un-assume path without
                                 # waiting for the Permit timeout
    snapshot: ClusterSnapshot    # post-commit snapshot (requested/used updated)
    amplified: bool = flax.struct.field(pytree_node=False, default=False)
    # ^ whether the amplified-CPU gates produced this result; the forget/
    #   un-assume path MUST mirror it so returned CPU equals charged CPU


register_struct(ScheduleResult, {
    "assignment": "i32[P~pad:-1]",
    "chosen_score": "f32[P~pad:-1]",  # pad rows are never placed
    "numa_zone": "i32[P~pad:-1]",
    "numa_take": "f32[P~pad:zero,Z~pad:zero,2]",
    "gpu_take": "bool[P~pad:false,I~pad:false]",
    "aux_inst": "i32[P~pad:-1,AX]",
    "res_slot": "i32[P~pad:-1]",
    "gang_failed": "bool[G~pad:false]",
    "snapshot": "ClusterSnapshot",
})


@shape_contract(
    snap="ClusterSnapshot", pods="PodBatch", cfg="LoadAwareConfig",
    _returns="ScheduleResult",
    _static={"num_rounds": 2, "k_choices": 2, "quota_depth": 2},
    _pad="pods.valid masks padded pod rows (assignment -1); "
         "nodes.schedulable masks padded node columns; every "
         "[P]-leading result field is -1/0/False for unplaced rows")
@functools.partial(jax.jit, static_argnames=("num_rounds", "k_choices",
                                             "score_dims", "tie_break",
                                             "enable_numa",
                                             "numa_strategy",
                                             "enable_devices",
                                             "device_strategy",
                                             "quota_depth",
                                             "fit_dims",
                                             "enable_amplification",
                                             "topo_prefix",
                                             "dom_classes",
                                             "numa_prefix",
                                             "gpu_prefix",
                                             "cascade"))
def schedule_batch(snap: ClusterSnapshot, pods: PodBatch,
                   cfg: loadaware.LoadAwareConfig,
                   num_rounds: int = 4, k_choices: int = 8,
                   score_dims: tuple = None,
                   tie_break: bool = False,
                   enable_numa: bool = True,
                   numa_strategy: str = "most",
                   enable_devices: bool = True,
                   device_strategy: str = "least",
                   quota_depth: int = MAX_QUOTA_DEPTH,
                   fit_dims: tuple = None,
                   enable_amplification: bool = False,
                   topo_prefix: int = None,
                   dom_classes: tuple = None,
                   numa_prefix: int = None,
                   gpu_prefix: int = None,
                   cascade: bool = False) -> ScheduleResult:
    """Schedule a pod batch against the snapshot. Pure function; the caller
    publishes `result.snapshot` as the next version (store.update).

    `fit_dims`: static tuple of ResourceKind indices the capacity/quota
    gates check; None = all dims. k8s noderesources.Fit only evaluates the
    resources a pod requests, so restricting to the union of dims any pod
    in the workload uses is semantically faithful and skips dead matmul
    columns (the scatter-commits always update the full R axis).

    `topo_prefix` (static): PACKING CONTRACT — when set, every pod with any
    spread/anti/aff membership or carried term sits in batch rows
    [0, topo_prefix). The per-group same-domain [P, P] prefix machinery and
    the (pod x group) gate matmuls then run on [topo_prefix, ...] slices —
    the dominant inner-commit cost on constraint-sparse workloads shrinks
    quadratically (~16x for a 512-row prefix of 2,000 pods) with bit-identical
    results. The caller MUST enforce the contract host-side
    (synthetic.pack_topo_prefix validates; SchedulerService's auto-pack
    derives it per batch): a member outside the prefix silently drops out
    of ALL in-batch topology accounting — the in-step gates and the
    round-level counts alike. None = full width (every row gated; no
    contract).

    `dom_classes` (static): DOMAIN-CLASS CONTRACT — groups sharing an
    upstream topologyKey have IDENTICAL rows in their domain matrix, so
    their in-step same-domain masks are equal. A 3-tuple
    (spread_classes, anti_classes, aff_classes), each a tuple of
    group-id tuples partitioning that family's groups into equal-row
    classes: the inner commit then builds ONE mask per class and
    batches the per-group matvecs into a single [pc, pc] x [pc, Gc]
    matmul — group-count-independent cost. The sums are 0/1 floats, so
    batching is bit-identical to the per-group loop. Callers derive
    classes host-side from the actual domain rows
    (synthetic.dom_classes); a class containing groups with UNEQUAL
    rows silently mis-gates. None = every group its own class (the
    reference per-group behavior).

    `numa_prefix` / `gpu_prefix` (static): further packing contracts in
    the same spirit as topo_prefix (synthetic.pack_gate_prefixes
    establishes all three at once). numa_prefix: every CPU-bind
    (numa_single) pod sits below it AND no node in the snapshot carries
    a topology-manager policy (numa_policy == NONE everywhere — with a
    policy node, ANY pod choosing it engages the manager and the
    prefix is invalid; such callers must leave numa_prefix=None).
    gpu_prefix: every device-requesting pod (deviceshare.
    has_device_request) sits below it. The per-inner-step topology-
    manager machinery and zone prefix gates then run on numa_prefix
    rows, and the GPU instance gates on gpu_prefix rows.

    `cascade` (static): the Filter->Score gate cascade
    (scheduler/cascade.py). Stage 1 folds a cheap candidate mask —
    batch-start resource fit + quota ceilings on top of the static
    gates — into the node columns; stage 2 narrows the HEAVY per-pair
    batch gates (device prefilter/score [P, N, I], zone prefilter/score
    [P, N, Z], policy combined-fit) to the numa_prefix / gpu_prefix
    rows, padding pass-through rows back in. Both layers are placement-
    preserving (monotone batch-start state; the prefix contracts), so
    cascade=False — the default, and the conformance oracle — produces
    bit-for-bit identical results (tests/test_cascade.py).

    `pods` may also arrive as its one-buffer upload (a
    `snapshot.hostbatch.HostBatch`): the program takes it apart on the
    device first, so a batch costs one host->device transfer."""
    pods = hostbatch.unpack(pods)
    nodes0, quotas0, gangs0 = snap.nodes, snap.quotas, snap.gangs
    devices0 = snap.devices
    n_nodes = nodes0.num_nodes
    n_quotas = quotas0.min.shape[0]
    n_gangs = gangs0.min_member.shape[0]
    p = pods.num_pods
    # device pools are skipped entirely when the snapshot has no instance
    # capacity (static shapes, so this specializes the compiled program)
    n_inst = devices0.gpu_free.shape[1]
    n_aux = devices0.aux_free.shape[2]
    use_gpu = enable_devices and n_inst > 0
    use_aux = enable_devices and n_aux > 0

    fd = list(fit_dims) if fit_dims is not None else None

    def dims(x):
        """Restrict a [..., R] operand to the checked resource dims."""
        return x if fd is None else x[..., fd]

    # constrained-prefix width for the topology families (see docstring);
    # pc == p (the default) keeps every slice full-width and the tail
    # concatenations zero-size — one code path for both modes
    pc = p if topo_prefix is None else max(min(int(topo_prefix), p), 0)
    pn = p if numa_prefix is None else max(min(int(numa_prefix), p), 0)
    pg = p if gpu_prefix is None else max(min(int(gpu_prefix), p), 0)

    rank = rank_by_priority(pods)
    # rank[p'] < rank[p], shared by every prefix gate in the commit
    earlier = rank[None, :] < rank[:, None]                      # [P, P]

    # --- static (per-batch) gates — stage 1 of the gate cascade ------------
    # gang quorum (PreFilter, coscheduling core/core.go:220-274); a
    # match-policy-satisfied gang short-circuits the quorum check — its
    # members schedule individually (core.go:236 OnceSatisfied fast path)
    gid = jnp.maximum(pods.gang_id, 0)
    gang_quorum = ((gangs0.member_count >= gangs0.min_member)
                   | gangs0.satisfied) & gangs0.valid
    gang_ok = (pods.gang_id < 0) | gang_quorum[gid]              # [P]

    quota_id = jnp.maximum(pods.quota_id, 0)
    # ancestor chain per pod per depth, -1 = none
    pod_anc = jnp.where(pods.quota_id[:, None] >= 0,
                        quotas0.depth_ancestor[quota_id], -1)    # [P, D]

    # nodeSelector + round-invariant LoadAware filter + schedulable +
    # taint forbids/penalty: one shared implementation for both cascade
    # modes (cascade.static_gates — the cheap per-batch node gates)
    static_ok, taint_penalty = static_gates(nodes0, pods, cfg)
    # the slot columns see the gates BEFORE the stage-1 fit mask and the
    # device/NUMA prefilters: those reason about the node's open pools,
    # but a consumer draws from the reservation's own hold (restore
    # semantics)
    static_base = static_ok
    if cascade:
        # stage-1 candidate mask: batch-start resource fit + quota
        # ceilings fold in up front. Placement-preserving: node
        # requested and quota used are monotone within the batch, so
        # every pruned pair would be rejected by the exact round gates
        # anyway (cascade.stage1_mask's contract).
        static_ok = stage1_mask(snap, pods, static_ok,
                                fit_dims=fit_dims, quota_depth=quota_depth)

    def heavy_rows(rows):
        """View of the columns the heavy per-pair batch gates read,
        sliced to a class-prefix width (stage 2 of the cascade): pods
        beyond the numa/gpu packing prefixes cannot engage those gates,
        so their [*, N, Z] / [*, N, I] tensors shrink ~P/rows x."""
        return pods.replace(requests=pods.requests[:rows],
                            gpu_ratio=pods.gpu_ratio[:rows],
                            numa_single=pods.numa_single[:rows])

    def and_rows(mask, gate, rows):
        """AND a [rows, N] gate into the first `rows` rows of `mask`;
        rows beyond pass through (the sliced gate is vacuously True
        there under the packing contract)."""
        return jnp.concatenate([mask[:rows] & gate, mask[rows:]], axis=0)

    # heavy-gate row widths: full width unless the cascade is on AND the
    # corresponding packing contract is established (gpu_prefix /
    # numa_prefix); under the contract the sliced gates are bit-identical
    dev_pg = pg if (cascade and pg < p) else p
    if enable_devices:
        # batch-start device upper bound (exact instance gates run in the
        # inner commit); also rejects device pods on device-less nodes —
        # including ratio-only GPU requests, which don't appear in the
        # node-allocatable columns (deviceshare
        # UnschedulableAndUnresolvable). Runs even with zero instance
        # capacity so such pods never silently place without a GPU.
        with obs.phase(obs_phases.PHASE_STAGE2_DEVICESHARE):
            static_ok = and_rows(
                static_ok,
                deviceshare.prefilter(devices0, heavy_rows(dev_pg)),
                dev_pg)
    if use_gpu:
        with obs.phase(obs_phases.PHASE_STAGE2_DEVICESHARE):
            dev_scores = deviceshare.score_matrix(
                devices0, heavy_rows(dev_pg), device_strategy)
            if dev_pg < p:
                # exact pad: rows beyond pg carry no device request, so
                # their score rows are 0 by construction
                dev_scores = jnp.concatenate(
                    [dev_scores,
                     jnp.zeros((p - dev_pg, n_nodes), dev_scores.dtype)],
                    axis=0)
    numa_used0 = nodes0.numa_cap - nodes0.numa_free              # [N, Z, 2]
    if enable_numa:
        numa_pn = pn if (cascade and pn < p) else p
        # single-NUMA-node prefilter (upper bound; exact gate in the inner
        # commit) + zone-allocation score preference (nodenumaresource
        # topology_hint.go + scoring.go). Under the cascade these run on
        # numa_prefix rows: CPU-bind pods all sit below pn, and the
        # numa_prefix contract guarantees a policy-free snapshot, so
        # rows beyond pass the gates and score 0.
        pods_pn = heavy_rows(numa_pn)
        with obs.phase(obs_phases.PHASE_STAGE2_NUMA):
            static_ok = and_rows(
                static_ok, numaaware.zone_prefilter(nodes0, pods_pn),
                numa_pn)
            numa_scores = numaaware.numa_score_matrix(nodes0, pods_pn,
                                                      numa_strategy)
            if numa_pn < p:
                numa_scores = jnp.concatenate(
                    [numa_scores,
                     jnp.zeros((p - numa_pn, n_nodes), numa_scores.dtype)],
                    axis=0)
        n_zones = nodes0.numa_cap.shape[1]
        # every pod's (cpu, mem) zone demand: on a node whose topology
        # policy engages the manager, ALL pods charge zone usage
        # (resource_manager.go allocates NUMANodeResources per pod), not
        # just the CPU-bind ones
        req2_all = jnp.stack([pods.requests[:, int(CPU_KIND)],
                              pods.requests[:, int(MEM_KIND)]], axis=-1)
        numa_policy0 = nodes0.numa_policy                        # i32[N]
        # policy-node combined-fit prefilter (upper bound): a policy node
        # whose total valid-zone free cannot hold the pod is infeasible
        with obs.phase(obs_phases.PHASE_STAGE2_POLICY):
            total_zfree = jnp.sum(
                nodes0.numa_free * nodes0.numa_valid[:, :, None], axis=1)
            static_ok = and_rows(
                static_ok,
                (numa_policy0 == topologymanager.POLICY_NONE)[None]
                | jnp.all(total_zfree[None] + EPS
                          >= req2_all[:numa_pn, None, :], axis=-1),
                numa_pn)

    # --- reservations as virtual nodes (transformer.go restore/nominate) ---
    # Each reservation slot is an extra owner-restricted column with the
    # slot's remaining free as capacity and MaxNodeScore preference, so
    # consumer admission rides the SAME priority-ordered prefix gates as
    # normal pods (no pre-pass, no priority inversion).
    slot_ok, slot_alloc0, slot_node = slot_columns(snap, pods, static_base)
    n_slots = slot_node.shape[0]
    n_ext = n_nodes + n_slots
    ext_alloc = jnp.concatenate([nodes0.allocatable, slot_alloc0], 0)
    ext_static = jnp.concatenate([static_ok, slot_ok], 1)        # [P, N+V]
    resv0 = snap.reservations
    is_once = resv0.allocate_once                                # bool[V]
    slot_node_c = jnp.maximum(slot_node, 0)

    # --- amplified CPU (nodenumaresource filterAmplifiedCPUs) -------------
    # On a node with amplification ratio > 1 the webhook published
    # AMPLIFIED allocatable; a CPU-bind (exclusive-cpuset) pod's cores cost
    # request x ratio against it, charged amplified at commit so later
    # pods see the true remaining capacity. Zone capacities stay raw:
    # amplifying both the zone resources and the bind-pod zone request by
    # the same ratio (util.go amplifyNUMANodeResources + getResourceOptions)
    # cancels in the fit comparison. Reservation slot columns draw from the
    # reservation's own hold and stay unamplified (documented deviation:
    # the reference amplifies reserved cpusets as reusableResources).
    ci = int(CPU_KIND)
    if enable_amplification:
        amp_ext = jnp.concatenate(
            [nodes0.cpu_amplification,
             jnp.ones((n_slots,), jnp.float32)], 0)              # [N+V]

    def to_real(ext_idx):
        """Map an extended node id to its real node (slots -> their node)."""
        if n_slots == 0:
            return ext_idx
        s = jnp.clip(ext_idx - n_nodes, 0, n_slots - 1)
        return jnp.where(ext_idx >= n_nodes, slot_node_c[s], ext_idx)

    # --- reservation fine-grained holds as EXTENDED pool rows -------------
    # Slot v's reserved GPU instances / NUMA zone capacity appear as row
    # N+v of the instance/zone pools: the existing per-instance and
    # per-zone prefix gates then hand consumers exactly the reserved
    # minors/zone with zero extra machinery (deviceshare/nodenumaresource
    # ReservationRestorePlugin; instance ids are the node's minors).
    if use_gpu and n_slots:
        devices_x = devices0.replace(
            gpu_total=jnp.concatenate(
                [devices0.gpu_total, devices0.gpu_total[slot_node_c]], 0),
            gpu_free=jnp.concatenate(
                [devices0.gpu_free, resv0.gpu_free], 0),
            gpu_valid=jnp.concatenate(
                [devices0.gpu_valid, resv0.gpu_valid], 0),
            gpu_numa=jnp.concatenate(
                [devices0.gpu_numa, devices0.gpu_numa[slot_node_c]], 0),
            gpu_pcie=jnp.concatenate(
                [devices0.gpu_pcie, devices0.gpu_pcie[slot_node_c]], 0))
    else:
        devices_x = devices0
    n_gpu_rows = devices_x.gpu_free.shape[0] if use_gpu else n_nodes
    if enable_numa:
        if n_slots:
            numa_cap_x = jnp.concatenate(
                [nodes0.numa_cap, resv0.numa_free], 0)       # [N+V, Z, 2]
            numa_valid_x = jnp.concatenate(
                [nodes0.numa_valid, resv0.numa_valid], 0)
            # slot rows engage only CPU-bind consumers (the reservation's
            # R-vector free covers plain consumers)
            numa_policy_x = jnp.concatenate(
                [numa_policy0,
                 jnp.zeros((n_slots,), numa_policy0.dtype)], 0)
            numa_used0_x = jnp.concatenate(
                [numa_used0, jnp.zeros_like(resv0.numa_free)], 0)
        else:
            numa_cap_x, numa_valid_x = nodes0.numa_cap, nodes0.numa_valid
            numa_policy_x, numa_used0_x = numa_policy0, numa_used0
        n_numa_rows = numa_cap_x.shape[0]
    else:
        numa_used0_x = numa_used0

    # PodTopologySpread (upstream hard constraints): [1, 1] matrices mean
    # no spread modeling and everything below compiles out. Within a
    # batch the gate is exact: the round-level feasibility and the
    # inner prefix cap both read counts derived from the carried
    # assignment. ACROSS batches the counts come from spread_count0,
    # which the builder recomputes from running + assumed pods — callers
    # chunking one logical workload must rebuild batches through the
    # builder (the informer/service flow) so each chunk sees the
    # previous chunks' assumes.
    def domain_machinery(dom_matrix, count0, member):
        """Shared (group x topology-domain) machinery for spread and
        inter-pod (anti-)affinity: the extended domain map (slot columns
        inherit their node's domain) and a counts closure over the
        carried assignment. `member[P, G]` marks which placed batch pods
        charge group g's domain count — membership is by selector match,
        so a matching pod placed in the same batch counts even when it
        carries no such constraint itself."""
        n_g, n_d = count0.shape
        if n_slots:
            dom_x = jnp.concatenate(
                [dom_matrix, dom_matrix[:, slot_node_c]], 1)  # [G, N+V]
        else:
            dom_x = dom_matrix

        def counts_flat(placed_now):
            # one charging implementation for in-batch and cross-batch
            # counts (charge_domain_counts); dom_x here is the
            # slot-extended map, so extended placements land on their
            # node's domain. Rows are sliced to the packing prefix —
            # members beyond it cannot exist under the contract (and
            # contribute nothing at full width), so the scatter shrinks
            # with the prefix, bit-identically.
            return charge_domain_counts(count0, dom_x, member[:pc],
                                        placed_now[:pc]).reshape(-1)

        return dom_x, counts_flat, n_g, n_d

    def _fit_rows(x, rows, fill):
        """Slice or pad the leading axis to `rows` (prefix interop:
        e.g. the numa block consumes per-instance GPU rows computed at
        the gpu prefix width)."""
        if x.shape[0] == rows:
            return x
        if x.shape[0] > rows:
            return x[:rows]
        pad = jnp.full((rows - x.shape[0],) + x.shape[1:], fill, x.dtype)
        return jnp.concatenate([x, pad], axis=0)

    _s_cls, _a_cls, _f_cls = dom_classes if dom_classes is not None \
        else (None, None, None)

    def _norm_classes(cls, n_g):
        """Singleton classes (the default) reduce the batched per-class
        matmul to the per-group matvec exactly."""
        if cls is None:
            return tuple((g,) for g in range(n_g))
        got = sorted(g for c in cls for g in c)
        if got != list(range(n_g)) or not all(len(c) for c in cls):
            raise ValueError(f"dom_classes must partition range({n_g}) "
                             f"into non-empty classes; got {cls}")
        return tuple(tuple(c) for c in cls)

    use_spread = pods.has_spread
    if use_spread:
        spread_domain_x, spread_counts_flat, n_sg, n_dom = \
            domain_machinery(pods.spread_domain, pods.spread_count0,
                             pods.spread_member)
        # multi-constraint gating rides the carrier MATRIX (zone +
        # hostname together is the upstream default profile): per-group
        # [Sg, N+V] admissibility maps combined by one bool matmul over
        # the CARRIED groups — the same shape as the anti gates
        spread_carrier_f = pods.spread_carrier.astype(jnp.float32)
        # SOFT groups (ScheduleAnyway) carry skew = inf from the
        # builder; they never filter — keyless nodes included
        spread_soft = ~jnp.isfinite(pods.spread_max_skew)      # [Sg]
        spread_classes = _norm_classes(_s_cls, n_sg)
    # inter-pod anti-affinity: a domain admits a gated pod only at count
    # 0; nodes LACKING the topology key pass (no topology pair can
    # exist there — upstream admits them).
    use_anti = pods.has_anti
    if use_anti:
        anti_domain_x, anti_counts_flat, n_ag, n_ad = \
            domain_machinery(pods.anti_domain, pods.anti_count0,
                             pods.anti_member)
        # direction (b): carrier occupancy per (group, domain)
        _, anti_carrier_flat, _, _ = \
            domain_machinery(pods.anti_domain, pods.anti_carrier_count0,
                             pods.anti_carrier)
        anti_member_f = pods.anti_member.astype(jnp.float32)  # [P, Ag]
        anti_carrier_f = pods.anti_carrier.astype(jnp.float32)
        anti_classes = _norm_classes(_a_cls, n_ag)
    # inter-pod affinity: a domain admits a gated pod only when it holds
    # a matching pod — except the bootstrap: when nothing matches
    # anywhere, any self-matching member may OPEN a domain, capped to
    # one opener per group per inner step so the group still converges
    # to co-location (upstream's self-affinity special case, without
    # pinning the bootstrap to one member that might be unschedulable).
    use_aff = pods.has_aff
    if use_aff:
        # multi-term gating rides the carrier matrix; the bootstrap is
        # per (pod, carried group): a self-matching member of an EMPTY
        # group may open any domain of that group
        aff_self = pods.aff_member & pods.aff_carrier       # bool[P, Fg]
        aff_domain_x, aff_counts_flat, n_fg, n_fd = \
            domain_machinery(pods.aff_domain, pods.aff_count0,
                             pods.aff_member)
        aff_classes = _norm_classes(_f_cls, n_fg)

    def round_body(carry, _):
        requested, quota_used, numa_used, gpu_free, aux_free, once_taken, \
            assigned_est, prod_assigned_est, gang_placed, placed, out_score, \
            out_zone, out_take, out_gpu_take, out_aux = carry
        active = pods.valid & (placed < 0) & gang_ok

        nodes = nodes0.replace(
            requested=requested[:n_nodes],
            assigned_estimated=assigned_est,
            prod_assigned_estimated=prod_assigned_est)

        # --- feasibility [P, N+V] (HOT LOOP #1) ---
        fit = jnp.all(dims(pods.requests)[:, None, :] + dims(requested)[None]
                      <= dims(ext_alloc)[None] + EPS, axis=-1)
        if enable_amplification and (fd is None or ci in fd):
            # CPU-bind pods must also fit their AMPLIFIED cpu request —
            # but only when the caller checks the CPU dim at all
            # (fit_dims excluding CPU must stay excluded)
            amp_cpu = pods.requests[:, ci][:, None] * jnp.where(
                pods.numa_single[:, None], amp_ext[None, :], 1.0)  # [P, N+V]
            fit &= amp_cpu + requested[None, :, ci] \
                <= ext_alloc[None, :, ci] + EPS
        feasible = fit & ext_static & active[:, None]
        if n_slots:
            # consumed AllocateOnce slots admit nobody (plugin.go:509-510)
            feasible &= ~jnp.concatenate(
                [jnp.zeros((n_nodes,), bool), is_once & once_taken])[None, :]

        # The three topology families gate only CONSTRAINED pods (rows
        # [0, pc) under the packing contract): their (pod x group)
        # matmuls run on prefix rows and the blocks merge into
        # `feasible` with one concatenation below.
        topo_blocks_pc = []
        if use_spread:
            # counts = initial matching pods + this batch's placements
            counts = spread_counts_flat(placed).reshape(n_sg, n_dom)
            min_c = jnp.min(jnp.where(pods.spread_dvalid, counts,
                                      jnp.inf), axis=1)             # [Sg]
            # no eligible domain -> minimum 0 (the sequential reference
            # in preemption.constraints_admit uses default=0, keeping a
            # hard group with unreachable domains RESTRICTIVE, not open)
            min_c = jnp.where(jnp.isfinite(min_c), min_c, 0.0)
            # per-(group, node) admissibility: placing one more pod in
            # the node's domain keeps the skew within the group's bound
            cnt_at = jnp.where(
                spread_domain_x >= 0,
                jnp.take_along_axis(counts,
                                    jnp.maximum(spread_domain_x, 0),
                                    axis=1), 0.0)        # [Sg, N+V]
            ok_map = (spread_soft[:, None]
                      | ((spread_domain_x >= 0)
                         & (cnt_at + 1.0 - min_c[:, None]
                            <= pods.spread_max_skew[:, None] + EPS)))
            # a pod is blocked where ANY carried group rejects the node
            topo_blocks_pc.append((spread_carrier_f[:pc]
                                   @ (~ok_map).astype(jnp.float32)) > 0.5)
            # preference (upstream spread Score): emptier domains rank
            # higher for BOTH hard and soft spread pods; normalize PER
            # GROUP (a crowded unrelated group must not flatten another
            # group's preference; the oracle mirrors) and SUM over the
            # pod's carried constraints (upstream sums per-constraint
            # scores)
            group_max = jnp.max(counts, axis=1)              # [Sg]
            penalty_map = jnp.where(
                spread_domain_x >= 0,
                cnt_at / jnp.maximum(group_max[:, None], 1.0)
                * MAX_NODE_SCORE, 0.0)                   # [Sg, N+V]
            spread_penalty_pc = jnp.matmul(
                spread_carrier_f[:pc], penalty_map,
                precision=jax.lax.Precision.HIGHEST)
        if use_anti:
            counts_an = anti_counts_flat(placed).reshape(n_ag, n_ad)
            # (a) carriers avoid domains holding selector-matching pods
            # — a per-group [Ag, N+V] occupancy map and one bool matmul
            # over the CARRIED groups, so a pod carrying SEVERAL anti
            # terms is gated by each (multi-term pods; same shape as
            # direction (b)). Keyless nodes stay open per group: no
            # topology pair can exist there.
            occ_a = (jnp.where(
                anti_domain_x >= 0,
                jnp.take_along_axis(counts_an,
                                    jnp.maximum(anti_domain_x, 0),
                                    axis=1), 0.0) > 0.5)  # [Ag, N+V]
            topo_blocks_pc.append(
                (anti_carrier_f[:pc] @ occ_a.astype(jnp.float32)) > 0.5)
            # (b) selector-matching pods avoid CARRIER domains — one
            # bool matmul over groups covers pods matching several terms
            carr = anti_carrier_flat(placed).reshape(n_ag, n_ad)
            occ_b = (jnp.where(
                anti_domain_x >= 0,
                jnp.take_along_axis(carr, jnp.maximum(anti_domain_x, 0),
                                    axis=1), 0.0) > 0.5)  # [Ag, N+V]
            topo_blocks_pc.append(
                (anti_member_f[:pc] @ occ_b.astype(jnp.float32)) > 0.5)
        if use_aff:
            counts_af = aff_counts_flat(placed).reshape(n_fg, n_fd)
            total_af = jnp.sum(counts_af, axis=1)         # [Fg]
            cc_map = jnp.where(
                aff_domain_x >= 0,
                jnp.take_along_axis(counts_af,
                                    jnp.maximum(aff_domain_x, 0),
                                    axis=1), 0.0)         # [Fg, N+V]
            # bootstrap feasibility per (pod, carried group): ANY active
            # self-matching member of an empty group may open any of its
            # domains; the inner prefix caps openers to one per group
            # per step
            boot_pg = (active[:pc, None] & aff_self[:pc]
                       & (total_af < 0.5)[None, :])       # [pc, Fg]
            carried = pods.aff_carrier[:pc]
            # non-boot carried groups need a matching pod in the node's
            # domain; boot groups only need the domain to exist
            bad_nonboot = ((aff_domain_x < 0)
                           | (cc_map <= 0.5)).astype(jnp.float32)
            bad_boot = (aff_domain_x < 0).astype(jnp.float32)
            topo_blocks_pc.append((
                (carried & ~boot_pg).astype(jnp.float32) @ bad_nonboot
                + boot_pg.astype(jnp.float32) @ bad_boot) > 0.5)
        if topo_blocks_pc:
            blocked_pc = functools.reduce(jnp.logical_or, topo_blocks_pc)
            feasible = jnp.concatenate(
                [feasible[:pc] & ~blocked_pc, feasible[pc:]], axis=0)

        # quota admission (ElasticQuota PreFilter, plugin.go:211-257):
        # used + request <= runtime at every tree level
        quota_admit = jnp.ones((p,), bool)
        for d in range(quota_depth):
            anc = pod_anc[:, d]
            a = jnp.maximum(anc, 0)
            level_ok = jnp.all(dims(quota_used)[a] + dims(pods.requests)
                               <= dims(quotas0.runtime)[a] + EPS, axis=-1)
            quota_admit &= (anc < 0) | level_ok
        feasible &= quota_admit[:, None]

        # --- score [P, N] (HOT LOOP #2) + top-k select ---
        # The [P, N] matrices are computed ONCE per round; the commit then
        # runs k cheap [P]-sized inner steps in which every rejected pod
        # falls through to its next-best node. Within a round the LoadAware
        # inputs are frozen (the reference's NodeMetric does not change on
        # assume either); capacity and quota stay exact via prefix sums.
        scores = loadaware.score_matrix(nodes, pods, cfg, score_dims)
        if enable_numa:
            # framework sums plugin scores; NUMA preference only affects
            # NUMA-bound pods (numa_scores is 0 elsewhere)
            scores = scores + numa_scores
        if use_gpu:
            # device preference likewise only affects GPU-requesting pods
            scores = scores + dev_scores
        if taint_penalty is not None:
            # demote, never filter (upstream tainttoleration only scores):
            # the clamp keeps penalized-but-feasible nodes above the
            # infeasible sentinel (-1.0) and the inner 'trying' threshold
            scores = jnp.maximum(scores - taint_penalty, 0.0)
        if use_spread:
            # real-node columns only: slot columns carry their fixed
            # owner preference above any node score; non-carrier rows
            # (outside the packing prefix) have zero penalty by
            # construction
            scores = jnp.concatenate(
                [jnp.maximum(scores[:pc] - spread_penalty_pc[:, :n_nodes],
                             0.0), scores[pc:]], axis=0)
        if n_slots:
            # slot columns outscore any node sum: owners strictly prefer
            # their reservation (nominator preference); safe because slot-
            # eligible pods are never NUMA-bound nor device-requesting, so
            # their node scores top out at MAX_NODE_SCORE
            scores = jnp.concatenate(
                [scores, jnp.full((p, n_slots), 3.0 * MAX_NODE_SCORE + 1.0)],
                axis=1)
        if tie_break:
            # k8s selectHost picks uniformly among max-score nodes
            # (schedule_one.go reservoir sample); a deterministic per-
            # (pod, node) jitter < 0.5 reproduces that spread without
            # reordering distinct integer scores, and de-clusters the
            # batched argmax under contention.
            pi = jnp.arange(p, dtype=jnp.uint32)[:, None]
            ni = jnp.arange(n_ext, dtype=jnp.uint32)[None, :]
            h = (pi * jnp.uint32(2654435761) + ni * jnp.uint32(40503)) & 1023
            scores = scores + h.astype(jnp.float32) * (0.49 / 1024.0)
        with obs.phase(obs_phases.PHASE_TOPK):
            masked = jnp.where(feasible, scores, -1.0)
            k = min(k_choices, n_ext)
            topk_val, topk_idx = jax.lax.top_k(masked, k)
            topk_idx = topk_idx.astype(jnp.int32)

        def inner(inner_carry, _):
            requested, quota_used, numa_used, gpu_free, aux_free, \
                once_taken, placed, kptr, out_score, out_zone, out_take, \
                out_gpu_take, out_aux = inner_carry
            val = jnp.take_along_axis(topk_val, kptr[:, None], 1)[:, 0]
            choice = jnp.take_along_axis(topk_idx, kptr[:, None], 1)[:, 0]
            trying = active & (placed < 0) & (kptr < k) & (val > -0.5)
            if n_slots:
                # a once slot consumed by an earlier inner step admits nobody
                slot_of = jnp.clip(choice - n_nodes, 0, n_slots - 1)
                on_slot = choice >= n_nodes
                trying &= ~(on_slot & (is_once & once_taken)[slot_of])
            choice_eff = jnp.where(trying, choice, n_ext)

            # node/slot capacity prefix in priority order; a CPU-bind pod
            # charges its amplified cpu request on amplified nodes
            if enable_amplification:
                f_amp = jnp.where(
                    pods.numa_single,
                    amp_ext[jnp.clip(choice_eff, 0, n_ext - 1)], 1.0)  # [P]
                req_node = pods.requests.at[:, ci].mul(f_amp)
            else:
                req_node = pods.requests
            eff_req = jnp.where(trying[:, None], dims(req_node), 0.0)
            accept = trying & segment_prefix_ok(
                choice_eff, earlier, eff_req, dims(requested),
                dims(ext_alloc), n_ext)

            # In-step topology gates run on the packing prefix: every
            # member/carrier row sits below pc (contract), so the
            # same-domain [pc, pc] masks and matvecs cover all charges
            # and all gated pods; rows >= pc merge back accepted-as-is.
            if use_spread or use_anti or use_aff:
                earlier_pc = earlier[:pc, :pc]
                trying_pc = trying[:pc]
                choice_pc = jnp.clip(choice_eff[:pc], 0, n_ext - 1)
                accept_pc = accept[:pc]
            if use_spread:
                # spread within the step: per group, priority order caps
                # each domain at skew + round-start min (min rises
                # between rounds, releasing more; SOFT groups never
                # gate). Current counts come from the CARRIED
                # assignment, so allowance consumed in earlier inner
                # steps (kptr fall-throughs) is charged too. Groups
                # iterate per domain CLASS (identical domain rows share
                # one same-domain mask; the per-group matvecs batch into
                # one matmul), and the per-group columns let a pod
                # charge every group it MATCHES while being gated by
                # every group it CARRIES — multi-constraint pods.
                counts_s_now = spread_counts_flat(placed).reshape(
                    n_sg, n_dom)
                for cls in spread_classes:
                    ci_ = np.asarray(cls, dtype=np.int32)
                    dom_g = spread_domain_x[ci_[0], choice_pc]   # [pc]
                    has_dom = (dom_g >= 0)[:, None]
                    same_d = dom_g[:, None] == dom_g[None, :]
                    e_mask = (same_d & earlier_pc).astype(jnp.float32)
                    dom_c = jnp.maximum(dom_g, 0)
                    contrib = (trying_pc[:, None]
                               & pods.spread_member[:pc, ci_]
                               & has_dom).astype(jnp.float32)  # [pc, Gc]
                    gated = (trying_pc[:, None]
                             & pods.spread_carrier[:pc, ci_]
                             & has_dom & ~spread_soft[ci_][None, :])
                    occ = counts_s_now[ci_][:, dom_c].T \
                        + e_mask @ contrib                     # [pc, Gc]
                    limit_c = (pods.spread_max_skew[ci_]
                               + min_c[ci_])[None, :]
                    accept_pc &= jnp.all(
                        ~gated | (occ + 1.0 <= limit_c + EPS), axis=1)
            if use_anti:
                # anti-affinity within the step: every trying MEMBER
                # (selector-matching pod, gated or not) charges its
                # chosen domain; gated pods are rejected when any
                # earlier-ranked charge (or an initial count) occupies
                # it. Same class batching as spread; the per-group
                # columns let a pod contribute to several groups'
                # accounting while being gated by only its own.
                counts_an_now = anti_counts_flat(placed).reshape(
                    n_ag, n_ad)
                carr_now = anti_carrier_flat(placed).reshape(n_ag, n_ad)
                for cls in anti_classes:
                    ci_ = np.asarray(cls, dtype=np.int32)
                    dom_g = anti_domain_x[ci_[0], choice_pc]     # [pc]
                    has_dom = (dom_g >= 0)[:, None]
                    same_d = dom_g[:, None] == dom_g[None, :]
                    e_mask = (same_d & earlier_pc).astype(jnp.float32)
                    dom_c = jnp.maximum(dom_g, 0)
                    member_c = pods.anti_member[:pc, ci_]
                    carrier_c = pods.anti_carrier[:pc, ci_]
                    # occupancy of the pod's chosen domain BEFORE it:
                    # carried counts + earlier-ranked in-step charges
                    # (a) matching pods charge; carriers are gated
                    contrib_a = (trying_pc[:, None] & member_c
                                 & has_dom).astype(jnp.float32)
                    gated_a = trying_pc[:, None] & carrier_c & has_dom
                    occ_a = counts_an_now[ci_][:, dom_c].T \
                        + e_mask @ contrib_a
                    accept_pc &= jnp.all((occ_a < 0.5) | ~gated_a,
                                         axis=1)
                    # (b) carriers charge; matching pods are gated
                    contrib_b = (trying_pc[:, None] & carrier_c
                                 & has_dom).astype(jnp.float32)
                    gated_b = trying_pc[:, None] & member_c & has_dom
                    occ_b_g = carr_now[ci_][:, dom_c].T \
                        + e_mask @ contrib_b
                    accept_pc &= jnp.all((occ_b_g < 0.5) | ~gated_b,
                                         axis=1)
            if use_aff:
                # bootstrap cap: attempts into an EMPTY domain of an
                # empty group are limited to one per group per step —
                # per carried group, so a pod opening several groups is
                # capped in each (multi-term pods). The opener-ordering
                # mask is the plain earlier matrix (no same-domain
                # term), so all groups of a class batch into one matmul.
                counts_af_now = aff_counts_flat(placed).reshape(n_fg,
                                                                n_fd)
                total_now = jnp.sum(counts_af_now, axis=1)  # [Fg]
                e_full = earlier_pc.astype(jnp.float32)
                for cls in aff_classes:
                    ci_ = np.asarray(cls, dtype=np.int32)
                    dom_g = aff_domain_x[ci_[0], choice_pc]      # [pc]
                    cc_now = counts_af_now[ci_][
                        :, jnp.maximum(dom_g, 0)].T            # [pc, Gc]
                    # a carried pod trying an EMPTY domain of g is an
                    # opener attempt; it succeeds only when the whole
                    # group is still empty AND no earlier-ranked opener
                    # exists — once g is populated, empty-domain tries
                    # are rejected so the pod falls through (kptr) to
                    # the opened domain
                    boot_try = (trying_pc[:, None]
                                & pods.aff_carrier[:pc, ci_]
                                & (dom_g >= 0)[:, None]
                                & (cc_now < 0.5))              # [pc, Gc]
                    openers_before = e_full @ boot_try.astype(
                        jnp.float32)                           # [pc, Gc]
                    accept_pc &= jnp.all(
                        ~boot_try | (total_now[ci_][None, :]
                                     + openers_before < 0.5), axis=1)
            if use_spread or use_anti or use_aff:
                accept = jnp.concatenate([accept_pc, accept[pc:]], axis=0)

            # quota prefix per tree level, same trick
            for d in range(quota_depth):
                anc = jnp.where(accept, pod_anc[:, d], -1)
                anc_eff = jnp.where(anc >= 0, anc, n_quotas)
                acc_req = jnp.where(accept[:, None], dims(pods.requests), 0.0)
                accept &= segment_prefix_ok(
                    anc_eff, earlier, acc_req, dims(quota_used),
                    dims(quotas0.runtime), n_quotas)

            # All remaining gates only SHRINK accept; every scatter-commit
            # is deferred until accept is final, so a pod rejected by a
            # later gate (device, AllocateOnce) never leaves a stale zone/
            # instance charge behind.
            if use_gpu:
                # per-instance request at the chosen node, computed on
                # the device-prefix rows; the view slices ONLY the
                # fields per_instance_at reads (requests, gpu_ratio)
                pods_pg = pods.replace(requests=pods.requests[:pg],
                                       gpu_ratio=pods.gpu_ratio[:pg])
                g_count, g_per = deviceshare.per_instance_at(
                    devices_x, pods_pg, choice_eff[:pg])  # [pg], [pg, 3]
            if enable_numa:
                # --- topology manager (frameworkext/topologymanager) ---
                # Per-pod effective policy: a CPU-bind pod requires single-
                # numa-node everywhere (incl. on a reservation slot, whose
                # row holds the reserved zone); otherwise the chosen node's
                # policy applies (slot rows carry policy none). Under the
                # numa_prefix contract (no policy nodes; CPU-bind pods
                # packed below pn) only prefix rows can engage, so the
                # whole block runs on [pn] rows.
                choice_pn = choice_eff[:pn]
                nc_z = jnp.clip(choice_pn, 0, n_numa_rows - 1)
                eff_policy = jnp.where(
                    pods.numa_single[:pn],
                    topologymanager.POLICY_SINGLE_NUMA_NODE,
                    numa_policy_x[nc_z])
                eff_policy = jnp.where(trying[:pn], eff_policy, 0)
                engaged = eff_policy > topologymanager.POLICY_NONE
                free_z = jnp.maximum(
                    numa_cap_x[nc_z] - numa_used[nc_z], 0.0)
                validz = numa_valid_x[nc_z]                  # [pn, Z]
                req2_eff = req2_all[:pn] * engaged[:, None]
                provider_hints = [topologymanager.capacity_hints(
                    free_z, req2_eff, validz)]
                if use_gpu:
                    # gpu rows fitted to the numa width: rows in
                    # [pg, pn) carry no GPU request by contract, and
                    # zero-padding reproduces their per_instance_at
                    # output exactly
                    zcounts = deviceshare.gpu_zone_counts(
                        gpu_free, devices_x, choice_pn,
                        _fit_rows(g_per, pn, 0.0), n_zones)
                    provider_hints.append(topologymanager.count_hints(
                        zcounts, _fit_rows(g_count, pn, 0) * engaged))
                fit_m, pref_m = topologymanager.merge_hints(provider_hints)
                affinity, admit, _ = topologymanager.resolve(
                    fit_m, pref_m, eff_policy, free_z[..., 0], validz,
                    numa_strategy)
                numa_take, filled = topologymanager.greedy_take(
                    free_z, req2_eff, affinity, numa_strategy)
                acc_pn = accept[:pn] & admit & (~engaged | filled)
                # per-zone capacity prefix gates in priority order (the
                # same sequential-exactness trick as node capacity, one
                # [N+V, 2] segment space per zone; each zone observes
                # the previous zone's gate, like the full-width loop)
                for zz in range(n_zones):
                    znow = acc_pn & engaged
                    zseg = jnp.where(znow, choice_pn, n_numa_rows)
                    acc_pn &= segment_prefix_ok(
                        zseg, earlier[:pn, :pn],
                        numa_take[:, zz, :] * znow[:, None],
                        numa_used[:, zz, :], numa_cap_x[:, zz, :],
                        n_numa_rows)
                accept = jnp.concatenate([acc_pn, accept[pn:]], axis=0)

            if use_gpu:
                # --- GPU instance gates (deviceshare allocateDevices) ---
                # choice_eff indexes the EXTENDED instance pool: node rows
                # are the open per-instance free, slot rows the remaining
                # reserved holds — consumers take reserved minors here.
                # Under the gpu_prefix contract every device-requesting
                # pod sits below pg, so the whole block runs on [pg]
                # rows (non-device rows beyond are vacuously accepted).
                choice_pg = choice_eff[:pg]
                shared = g_count == 1
                multi = g_count > 1
                # with NUMA modeling off, the zone constraint is dropped
                # (not tightened against a sentinel mask); rows padded
                # past the numa width carry no policy (all-open mask)
                if enable_numa:
                    zone_mask_dev = _fit_rows(affinity, pg, True)
                    dev_engaged = _fit_rows(engaged, pg, False)
                else:
                    zone_mask_dev = jnp.ones((pg, 1), bool)
                    dev_engaged = jnp.zeros((pg,), bool)
                inst, inst_ok = deviceshare.choose_gpu_instance(
                    gpu_free, devices_x, choice_pg, g_per, shared,
                    zone_mask_dev, dev_engaged, device_strategy)
                acc_pg = accept[:pg]
                acc_pg &= ~shared | inst_ok
                gseg = jnp.where(acc_pg & shared,
                                 choice_pg * n_inst + inst,
                                 n_gpu_rows * n_inst)
                greq = g_per * (acc_pg & shared)[:, None]
                gpu_free_flat = gpu_free.reshape(-1, NUM_DEV_DIMS)
                acc_pg &= segment_prefix_ok(
                    gseg, earlier[:pg, :pg], greq,
                    jnp.zeros_like(gpu_free_flat),
                    gpu_free_flat, n_gpu_rows * n_inst)
                took_shared = acc_pg & shared
                # multi-GPU (whole instances): one winner per node per inner
                # step keeps lowest-index instance identity unambiguous;
                # contenders fall through to the next step/round. Instances
                # tentatively taken by this step's shared pods are excluded
                # (shared-before-multi intra-step order; exact order is
                # recovered at chunk size 1).
                shared_taken_now = jnp.zeros(
                    (n_gpu_rows * n_inst + 1,), bool).at[
                        jnp.where(took_shared, choice_pg * n_inst + inst,
                                  n_gpu_rows * n_inst)].set(True)[:-1]
                nc = jnp.clip(choice_pg, 0, n_gpu_rows - 1)
                take, enough = deviceshare.full_fit_instances(
                    gpu_free, devices_x, choice_pg, g_per, g_count,
                    zone_mask_dev, dev_engaged,
                    exclude=shared_taken_now.reshape(n_gpu_rows,
                                                     n_inst)[nc])
                same_node = choice_pg[:, None] == choice_pg[None, :]
                multi_cand = multi & acc_pg
                first_multi = ~jnp.any(earlier[:pg, :pg] & same_node
                                       & multi_cand[None, :], axis=-1)
                acc_pg = jnp.where(multi, acc_pg & first_multi & enough,
                                   acc_pg)
                accept = jnp.concatenate([acc_pg, accept[pg:]], axis=0)

            if use_aux:
                # --- aux (rdma/fpga) VF gates (default device handler) ---
                aux_free_flat = aux_free.reshape(-1, 1)
                aux_insts = []
                for t in range(NUM_AUX_TYPES):
                    a_req = pods.requests[:, deviceshare.AUX_KINDS[t]]
                    has = a_req > 0
                    a_inst, a_ok = deviceshare.choose_aux_instance(
                        aux_free, devices0, choice_eff, t, a_req,
                        device_strategy)
                    accept &= ~has | a_ok
                    base = (choice_eff * NUM_AUX_TYPES + t) * n_aux
                    aseg = jnp.where(accept & has, base + a_inst,
                                     n_nodes * NUM_AUX_TYPES * n_aux)
                    areq = (a_req * (accept & has))[:, None]
                    accept &= segment_prefix_ok(
                        aseg, earlier, areq, jnp.zeros_like(aux_free_flat),
                        aux_free_flat, n_nodes * NUM_AUX_TYPES * n_aux)
                    aux_insts.append(a_inst)

            if n_slots:
                # AllocateOnce: single consumer per slot — among this
                # step's accepted consumers, only the first in priority
                # order wins (plugin.go:509-510), then the slot closes.
                once_here = accept & on_slot & is_once[slot_of]
                same_slot = choice_eff[:, None] == choice_eff[None, :]
                first = ~jnp.any(earlier & same_slot & once_here[None, :],
                                 axis=-1)
                accept = jnp.where(once_here, accept & first, accept)
                once_win = accept & on_slot & is_once[slot_of]
                once_taken = once_taken.at[
                    jnp.where(once_win, slot_of, n_slots)].set(
                        True, mode="drop")

            # scatter-commit (assume; scheduler_adapter assume/forget) —
            # accept is final from here on; the NUMA/GPU commits read
            # and write only their prefix rows (engaged and device pods
            # live there by contract)
            if enable_numa:
                took_z = accept[:pn] & engaged
                numa_used = numa_used.at[
                    jnp.where(took_z, choice_pn, n_numa_rows)].add(
                        numa_take * took_z[:, None, None], mode="drop")
                out_take = jnp.concatenate(
                    [jnp.where(took_z[:, None, None], numa_take,
                               out_take[:pn]), out_take[pn:]], axis=0)
                # reported zone: the single zone for CPU-bind pods (feeds
                # the resource-status annotation)
                zone1 = jnp.argmax(affinity, axis=-1).astype(jnp.int32)
                out_zone = jnp.concatenate(
                    [jnp.where(took_z & pods.numa_single[:pn], zone1,
                               out_zone[:pn]), out_zone[pn:]], axis=0)
            if use_gpu:
                took_shared = accept[:pg] & shared
                gseg = jnp.where(took_shared, choice_pg * n_inst + inst,
                                 n_gpu_rows * n_inst)
                gpu_free = gpu_free.reshape(-1, NUM_DEV_DIMS).at[gseg].add(
                    -g_per * took_shared[:, None],
                    mode="drop").reshape(gpu_free.shape)
                took_multi = accept[:pg] & multi
                g_upd = (take[:, :, None] * g_per[:, None, :]
                         * took_multi[:, None, None])
                g_tgt = jnp.where(took_multi, choice_pg, n_gpu_rows)
                gpu_free = gpu_free.at[g_tgt].add(-g_upd, mode="drop")
                inst_onehot = (jnp.arange(n_inst, dtype=jnp.int32)[None, :]
                               == inst[:, None])
                out_gpu_take = jnp.concatenate(
                    [out_gpu_take[:pg]
                     | (inst_onehot & took_shared[:, None])
                     | (take & took_multi[:, None]),
                     out_gpu_take[pg:]], axis=0)
            if use_aux:
                aux_free_flat = aux_free.reshape(-1, 1)
                for t in range(NUM_AUX_TYPES):
                    a_req = pods.requests[:, deviceshare.AUX_KINDS[t]]
                    took_a = accept & (a_req > 0)
                    base = (choice_eff * NUM_AUX_TYPES + t) * n_aux
                    aseg = jnp.where(took_a, base + aux_insts[t],
                                     n_nodes * NUM_AUX_TYPES * n_aux)
                    aux_free_flat = aux_free_flat.at[aseg].add(
                        -(a_req * took_a)[:, None], mode="drop")
                    out_aux = out_aux.at[:, t].set(
                        jnp.where(took_a, aux_insts[t], out_aux[:, t]))
                aux_free = aux_free_flat.reshape(aux_free.shape)
            acc_req = pods.requests * accept[:, None]
            # node charge is amplified for CPU-bind pods; quota charges the
            # RAW request (quota admission is about the pod's own ask)
            acc_req_node = req_node * accept[:, None] \
                if enable_amplification else acc_req
            requested = requested.at[choice_eff].add(acc_req_node,
                                                     mode="drop")
            for d in range(quota_depth):
                anc = jnp.where(accept, pod_anc[:, d], -1)
                quota_used = quota_used.at[
                    jnp.where(anc >= 0, anc, n_quotas)].add(acc_req,
                                                            mode="drop")
            placed = jnp.where(accept, choice, placed)
            out_score = jnp.where(accept, val, out_score)
            # a rejected pod's chosen node just filled up: fall through
            kptr = jnp.where(trying & ~accept, kptr + 1, kptr)
            return (requested, quota_used, numa_used, gpu_free, aux_free,
                    once_taken, placed, kptr, out_score, out_zone, out_take,
                    out_gpu_take, out_aux), None

        (requested, quota_used, numa_used, gpu_free, aux_free, once_taken,
         placed, _, out_score, out_zone, out_take, out_gpu_take,
         out_aux), _ = \
            jax.lax.scan(
                inner,
                (requested, quota_used, numa_used, gpu_free, aux_free,
                 once_taken, placed, jnp.zeros((p,), jnp.int32), out_score,
                 out_zone, out_take, out_gpu_take, out_aux),
                None, length=k)

        # register newly placed pods' estimates for the next round's scores
        # (podAssignCache tracks reservation consumers on the REAL node too)
        new = (placed >= 0) & active
        tgt = jnp.where(new, to_real(placed), n_nodes)
        est = pods.estimated * new[:, None]
        assigned_est = assigned_est.at[tgt].add(est, mode="drop")
        is_prod = pods.priority_class == 4  # PriorityClass.PROD
        prod_assigned_est = prod_assigned_est.at[tgt].add(
            est * is_prod[:, None], mode="drop")
        gang_placed = gang_placed.at[jnp.where(new & (pods.gang_id >= 0),
                                               pods.gang_id, n_gangs)].add(
            1, mode="drop")
        return (requested, quota_used, numa_used, gpu_free, aux_free,
                once_taken, assigned_est, prod_assigned_est, gang_placed,
                placed, out_score, out_zone, out_take, out_gpu_take,
                out_aux), None

    n_zones0 = nodes0.numa_cap.shape[1]
    init = (
        jnp.concatenate([nodes0.requested,
                         jnp.zeros_like(slot_alloc0)], axis=0),
        quotas0.used,
        numa_used0_x,
        devices_x.gpu_free,
        devices0.aux_free,
        jnp.zeros((n_slots,), bool),
        nodes0.assigned_estimated,
        nodes0.prod_assigned_estimated,
        jnp.zeros((n_gangs,), jnp.int32),
        jnp.full((p,), -1, jnp.int32),
        jnp.full((p,), -1.0, jnp.float32),
        jnp.full((p,), -1, jnp.int32),
        jnp.zeros((p, n_zones0, 2), jnp.float32),
        jnp.zeros((p, n_inst), bool),
        jnp.full((p, NUM_AUX_TYPES), -1, jnp.int32))
    (_, _, _, _, _, _, _, _, gang_placed, placed, out_score, out_zone,
     out_take, out_gpu_take, out_aux), _ = \
        jax.lax.scan(round_body, init, None, length=num_rounds)

    # --- gang all-or-nothing rollback (Permit barrier, core.go:311-341) ---
    # A strict gang below quorum rolls back ONLY when no members remain
    # outstanding (still to be attempted in a later chunk of the scan or a
    # retry pass). With members outstanding, the placed ones stay ASSUMED —
    # the Permit wait of the reference: pods sit at the barrier until the
    # gang completes. Without this, a gang spanning batch or chunk boundaries
    # could never form: each chunk would see a partial count and revoke
    # its own members. Reclaim of a waiting gang that never completes is
    # two-tier, as in the reference: `gang_failed` in the result flags
    # gangs PROVEN short this batch so the host can forget/un-assume their
    # earlier members immediately, and gangs whose failed members simply
    # never reappear (provable by no one device-side) fall to the Permit
    # timeout — GangDirectory.expire_waits + the store's forget path.
    gid = jnp.maximum(pods.gang_id, 0)
    attempted = jnp.zeros((n_gangs,), jnp.int32).at[
        jnp.where(pods.valid & (pods.gang_id >= 0), gid, n_gangs)].add(
        1, mode="drop")
    outstanding = jnp.maximum(
        gangs0.member_count - gangs0.assumed - attempted, 0)
    gang_total = gangs0.assumed + gang_placed
    # satisfied gangs are never group-rejected (core.go:286 PostFilter skips
    # the strict-mode gang rejection once the match policy latched)
    gang_fail = (gangs0.valid & gangs0.strict & ~gangs0.satisfied
                 & (gang_total < gangs0.min_member)
                 & (outstanding == 0))
    revoke = (placed >= 0) & (pods.gang_id >= 0) & gang_fail[gid]
    placed = jnp.where(revoke, -1, placed)

    # --- rebuild post-commit state from the final assignment --------------
    ok = placed >= 0
    res_slot = jnp.where(placed >= n_nodes, placed - n_nodes, -1)
    placed_real = jnp.where(ok, to_real(jnp.maximum(placed, 0)), -1)
    tgt = jnp.where(ok, placed_real, n_nodes)
    fin_req = pods.requests * ok[:, None]
    fin_est = pods.estimated * ok[:, None]
    is_prod = pods.priority_class == 4
    # reservation consumers don't grow node requested (covered capacity was
    # already charged by the reserve pod, plugin.go:521-613)
    node_req = fin_req * (res_slot < 0)[:, None]
    if enable_amplification:
        f_fin = jnp.where(
            ok & pods.numa_single,
            nodes0.cpu_amplification[jnp.clip(placed_real, 0,
                                              n_nodes - 1)], 1.0)
        node_req = node_req.at[:, ci].mul(f_fin)
    requested = nodes0.requested.at[tgt].add(node_req, mode="drop")
    assigned_est = nodes0.assigned_estimated.at[tgt].add(fin_est, mode="drop")
    prod_assigned_est = nodes0.prod_assigned_estimated.at[tgt].add(
        fin_est * is_prod[:, None], mode="drop")
    quota_used = quotas0.used
    for d in range(quota_depth):
        anc = jnp.where(ok, pod_anc[:, d], -1)
        quota_used = quota_used.at[jnp.where(anc >= 0, anc, n_quotas)].add(
            fin_req, mode="drop")
    gang_assumed = gangs0.assumed.at[jnp.where(ok & (pods.gang_id >= 0),
                                               pods.gang_id, n_gangs)].add(
        1, mode="drop")

    # NUMA zone usage from the surviving assignment (revoked gang members
    # give their takes back)
    numa_zone = jnp.where(ok & pods.numa_single, out_zone, -1)
    numa_free = nodes0.numa_free
    on_slot_fin = res_slot >= 0
    if enable_numa:
        # slot consumers drew from the reservation's hold, not the node's
        # open pool (the hold already left the node at snapshot build)
        node_numa_tgt = jnp.where(ok & ~on_slot_fin, tgt, n_nodes)
        numa_free = jnp.maximum(
            nodes0.numa_free.at[node_numa_tgt].add(
                -out_take * ok[:, None, None], mode="drop"), 0.0)

    # device pools from the surviving assignment (revoked gang members give
    # their instances back); per-instance requests are a pure function of
    # (pod, assigned node), so only the take masks carry through the scan
    new_devices = devices0
    gpu_take = out_gpu_take & ok[:, None]
    aux_inst = jnp.where(ok[:, None], out_aux, -1)
    per_f = None
    if use_gpu:
        _, per_f = deviceshare.per_instance_at(devices0, pods, placed_real)
        g_upd = gpu_take[:, :, None] * per_f[:, None, :]
        g_tgt = jnp.where(ok & ~on_slot_fin, placed_real, n_nodes)
        new_gpu_free = devices0.gpu_free.at[g_tgt].add(-g_upd, mode="drop")
        new_devices = new_devices.replace(
            gpu_free=jnp.maximum(new_gpu_free, 0.0))
    if use_aux:
        aux_flat = devices0.aux_free.reshape(-1, 1)
        for t in range(NUM_AUX_TYPES):
            a_req = pods.requests[:, deviceshare.AUX_KINDS[t]]
            took = ok & (a_req > 0) & (aux_inst[:, t] >= 0)
            base = (jnp.maximum(placed_real, 0) * NUM_AUX_TYPES + t) * n_aux
            aseg = jnp.where(took, base + aux_inst[:, t],
                             n_nodes * NUM_AUX_TYPES * n_aux)
            aux_flat = aux_flat.at[aseg].add(-(a_req * took)[:, None],
                                             mode="drop")
        new_devices = new_devices.replace(
            aux_free=jnp.maximum(
                aux_flat.reshape(devices0.aux_free.shape), 0.0))

    # slot rows outscore any node sum for strict preference; report those
    # capped at MaxNodeScore (node-placed NUMA/device pods legitimately
    # exceed 100 — plugin scores sum — and keep their real value)
    chosen_score = jnp.where(
        ok, jnp.where(res_slot >= 0,
                      jnp.minimum(out_score, MAX_NODE_SCORE), out_score),
        -1.0)
    new_snap = snap.replace(
        nodes=nodes0.replace(requested=requested,
                             assigned_estimated=assigned_est,
                             prod_assigned_estimated=prod_assigned_est,
                             numa_free=numa_free),
        quotas=quotas0.replace(used=quota_used),
        gangs=gangs0.replace(assumed=gang_assumed),
        reservations=rebuild_reservations(
            snap.reservations, pods, res_slot, ok,
            numa_take=out_take if enable_numa else None,
            gpu_take=gpu_take if use_gpu else None, gpu_per_inst=per_f),
        devices=new_devices,
        version=snap.version + 1,
    )
    return ScheduleResult(assignment=placed_real, chosen_score=chosen_score,
                          numa_zone=numa_zone,
                          numa_take=out_take * ok[:, None, None],
                          gpu_take=gpu_take,
                          aux_inst=aux_inst, res_slot=res_slot,
                          gang_failed=gang_fail,
                          snapshot=new_snap,
                          amplified=enable_amplification)


def overcommit_arrays_ok(requested, allocatable, num_nodes: int = None,
                         tol: float = 1.0) -> bool:
    """Array form of `overcommit_ok` for callers holding the capacity
    columns without the snapshot."""
    req = np.asarray(requested)
    alloc = np.asarray(allocatable)
    if num_nodes is not None:
        if req[num_nodes:].any():
            return False  # a pad row was charged: provably a bug
        req, alloc = req[:num_nodes], alloc[:num_nodes]
    return bool((req <= alloc + tol).all())


def overcommit_ok(snap: ClusterSnapshot, num_nodes: int = None,
                  tol: float = 1.0) -> bool:
    """The no-overcommit invariant, host-side: requested <= allocatable
    + tol on the REAL node rows [0, num_nodes). THE one implementation
    the dryrun, the mesh smoke, and the conformance tests assert —
    `num_nodes` excludes the zero-capacity pad rows appended by
    parallel.pad_nodes_to_mesh (provably unschedulable, so they can
    never be charged; checking them would be vacuous, and a caller
    accidentally including a charged pad row must fail loudly here,
    not by tolerance). None checks every row (no padding)."""
    return overcommit_arrays_ok(snap.nodes.requested,
                                snap.nodes.allocatable, num_nodes, tol)


# the (count field, domain field, member field) triples of the
# cross-batch count rule — THE one place the pairing is encoded;
# the service's chunked rung, the dryrun and the tests consume it
COUNT_FIELDS = ("spread_count0", "anti_count0", "anti_carrier_count0",
                "aff_count0")
_COUNT_RULE = (("spread_count0", "spread_domain", "spread_member"),
               ("anti_count0", "anti_domain", "anti_member"),
               ("anti_carrier_count0", "anti_domain", "anti_carrier"),
               ("aff_count0", "aff_domain", "aff_member"))


def charge_all_counts(counts: tuple, batch, assignment) -> tuple:
    """Thread a batch's placements into the carried (spread, anti,
    anti-carrier, affinity) counts — the cross-batch analogue of the
    builder recomputing count0 from running + assumed pods. `counts`
    is ordered per COUNT_FIELDS; callers chunking one logical workload
    replace the next chunk's count0 fields with the result."""
    return tuple(
        charge_domain_counts(c, getattr(batch, dom), getattr(batch, mem),
                             assignment)
        for c, (_, dom, mem) in zip(counts, _COUNT_RULE))


@shape_contract(
    count0="f32[SG,DM~pad:zero]", dom_matrix="i32[SG,N~pad:-1]",
    member="bool[P~pad:false,SG]",
    assignment="i32[P~pad:-1]", _returns="f32[SG,DM~pad:zero]",
    _pad="unplaced rows (assignment -1), non-members, and keyless "
         "nodes (domain -1) all charge the drop row; the SG symbol "
         "stands for any of the three constraint families")
def charge_domain_counts(count0: jnp.ndarray, dom_matrix: jnp.ndarray,
                         member: jnp.ndarray,
                         assignment: jnp.ndarray) -> jnp.ndarray:
    """Post-batch (group x domain) count update — the cross-batch
    analogue of the builder recomputing spread/anti/aff count0 from
    running + assumed pods. Callers chunking one logical workload
    through repeated schedule_batch calls thread the returned counts
    into the next chunk's count0 so each chunk sees the previous
    chunks' assumes (the same rule the domain_machinery docstring
    states for the informer flow).

    `assignment` must be NODE-level indices (< N; map reservation-slot
    placements to their node first). Same segment-sum as the in-batch
    counts closure: every placed member of group g charges g's domain
    for its node; non-members and unplaced rows drop out.
    """
    n_g, n_d = count0.shape
    pl = jnp.maximum(assignment, 0)
    dom_pg = dom_matrix.T[pl]                              # [P, G]
    ok = member & (assignment >= 0)[:, None]
    dom_pg = jnp.where(ok, dom_pg, -1)
    g_idx = jnp.arange(n_g, dtype=jnp.int32)[None, :]
    seg = jnp.where(dom_pg >= 0, g_idx * n_d + dom_pg,
                    n_g * n_d).reshape(-1)
    return count0.reshape(-1).at[seg].add(
        1.0, mode="drop").reshape(n_g, n_d)


# --- device-resident straggler tail -------------------------------------
# After a chunked sweep some pods remain unplaced (conflict losers,
# constraint-tight rows). The tail packs them into fixed-width retry
# batches and re-schedules them with a heavier program (more rounds /
# fall-through choices). `tail_pass` is ONE such pass; the host may
# orchestrate passes itself (one straggler-count readback per adaptive
# decision — the conformance oracle in tests/test_cascade.py), or run
# `tail_compaction_loop`, which drives the same pass inside a
# lax.while_loop so the whole adaptive tail — gather, compact, retry,
# repeat — stays on device and the host reads back ONE stats vector at
# the end regardless of straggler count.


@shape_contract(
    pods="PodBatch", assign="i32[P~pad:-1]", tried="bool[P~pad:false]",
    _returns=("i32[TC]", "bool[TC]"),
    _static={"tail_chunk": "TC"},
    _pad="requires tail_chunk <= P (the window gathers batch rows); "
         "rows of idx beyond the straggler pool are padding; attempt "
         "marks the true leftovers this pass may retry")
def tail_select(pods: PodBatch, assign: jnp.ndarray, tried: jnp.ndarray,
                tail_chunk: int, topo_prefix: int = None,
                topo_mask: jnp.ndarray = None):
    """Pick up to `tail_chunk` stragglers for one retry pass.

    Returns (idx i32[tail_chunk], attempt bool[tail_chunk]): the batch
    rows to gather and which of them are true leftovers this pass may
    retry (the rest are padding — marked invalid by the caller).

    Selection prefers NEVER-RETRIED leftovers over already-retried
    ones, so retry capacity is genuinely exhausted: without the `tried`
    mask, a pass that placed nothing would re-select the same window
    and silently starve the rest.

    Full-gate (`topo_prefix` set, `topo_mask` bool[P] in the batch's
    packed order): at most topo_prefix constrained stragglers (untried
    first) sort to the FRONT of the window — inside the scheduler's
    packing prefix — and the remaining slots go to unconstrained
    stragglers. Constrained overflow is excluded from the pass AND left
    unmarked in `tried`, so it stays in the never-retried pool and an
    adaptive caller keeps running until it drains; the in-prefix mask
    below is the safety net for the degenerate few-stragglers case.
    """
    with obs.phase(obs_phases.PHASE_TAIL_SELECT):
        return _tail_select_body(pods, assign, tried, tail_chunk,
                                 topo_prefix, topo_mask)


def _tail_select_body(pods, assign, tried, tail_chunk, topo_prefix,
                      topo_mask):
    bad = pods.valid & (assign < 0)
    if topo_prefix is None:
        key = jnp.where(bad & ~tried, 0, jnp.where(bad, 1, 2))
    else:
        # budgeted constrained selection: rank constrained stragglers
        # untried-first and admit only the first topo_prefix of them to
        # this pass — the REST of the window goes to unconstrained
        # stragglers (untried first), so constrained overflow occupies
        # no dead slots and can never starve unconstrained retries
        cb = bad & topo_mask
        ckey = jnp.where(cb & ~tried, 0, jnp.where(cb, 1, 2))
        adm = cb & (stable_rank(ckey) < topo_prefix)
        # untried pods of EITHER class outrank every tried pod
        # (admitted-constrained tried included), so no untried straggler
        # can be starved by retry loops of failing pods; admitted-tried
        # rows displaced beyond the prefix are caught by the in_prefix
        # mask
        key = jnp.where(
            adm & ~tried, 0,
            jnp.where(bad & ~topo_mask & ~tried, 1,
                      jnp.where(adm, 2,
                                jnp.where(bad & ~topo_mask, 3,
                                          jnp.where(bad, 4, 5)))))
    order = jnp.argsort(key, stable=True)
    idx = order[:tail_chunk]
    attempt = bad[idx]
    if topo_prefix is not None:
        in_prefix = jnp.arange(tail_chunk) < topo_prefix
        attempt &= ~topo_mask[idx] | in_prefix
    return idx, attempt


@shape_contract(
    snap="ClusterSnapshot",
    counts=("f32[SG,DM~pad:zero]", "f32[AG,DM~pad:zero]",
            "f32[AG,DM~pad:zero]", "f32[FG,DM~pad:zero]"),
    assign="i32[P~pad:-1]", tried="bool[P~pad:false]", pods="PodBatch",
    cfg="LoadAwareConfig",
    _returns=("ClusterSnapshot",
              ("f32[SG,DM~pad:zero]", "f32[AG,DM~pad:zero]",
               "f32[AG,DM~pad:zero]", "f32[FG,DM~pad:zero]"),
              "i32[P~pad:-1]", "bool[P~pad:false]"),
    _static={"tail_chunk": "TC"},
    _callable={"step_fn": "koordinator_tpu.scheduler.core.schedule_batch"},
    _pad="counts ride COUNT_FIELDS order; a pass with nothing left "
         "gathers an all-invalid retry batch and no-ops the snapshot")
def tail_pass(step_fn, snap: ClusterSnapshot, counts: tuple,
              assign: jnp.ndarray, tried: jnp.ndarray, pods: PodBatch,
              cfg, *, tail_chunk: int, charge_counts: bool = True,
              topo_prefix: int = None, topo_mask: jnp.ndarray = None):
    """One retry pass: gather the selected stragglers into a compact
    [tail_chunk] batch, re-schedule via `step_fn(snap, retry, cfg)`,
    and scatter placements back. Returns (snap, counts, assign, tried).

    The gathered retry batch marks only true leftovers valid, so a pass
    with nothing left is a no-op on the snapshot. `counts` is the
    carried (group x domain) topology-count tuple (COUNT_FIELDS order);
    `charge_counts=False` skips the cross-batch charge for workloads
    without topology terms.
    """
    idx, attempt = tail_select(pods, assign, tried, tail_chunk,
                               topo_prefix, topo_mask)
    with obs.phase(obs_phases.PHASE_TAIL_PASS):
        retry = pods.replace(
            **{f: getattr(pods, f)[idx]
               for f in PER_POD_FIELDS if f != "valid"},
            valid=attempt)
        retry = retry.replace(**dict(zip(COUNT_FIELDS, counts)))
        tried = tried.at[idx].set(tried[idx] | attempt)
        res = step_fn(snap, retry, cfg)
        if charge_counts:
            counts = charge_all_counts(counts, retry, res.assignment)
        got = attempt & (res.assignment >= 0)
        assign = assign.at[idx].set(
            jnp.where(got, res.assignment, assign[idx]))
        return res.snapshot, counts, assign, tried


@shape_contract(
    snap="ClusterSnapshot",
    counts=("f32[SG,DM~pad:zero]", "f32[AG,DM~pad:zero]",
            "f32[AG,DM~pad:zero]", "f32[FG,DM~pad:zero]"),
    assign="i32[P~pad:-1]", pods="PodBatch", cfg="LoadAwareConfig",
    _returns=("ClusterSnapshot",
              ("f32[SG,DM~pad:zero]", "f32[AG,DM~pad:zero]",
               "f32[AG,DM~pad:zero]", "f32[FG,DM~pad:zero]"),
              "i32[P~pad:-1]", "i32[4]"),
    _static={"tail_chunk": "TC", "min_passes": 1, "max_passes": 2},
    _callable={"step_fn": "koordinator_tpu.scheduler.core.schedule_batch"},
    _pad="stats = [after_sweep, final, never_retried, passes]; only "
         "the max_passes cap can leave never_retried > 0")
def tail_compaction_loop(step_fn, snap: ClusterSnapshot, counts: tuple,
                         assign: jnp.ndarray, pods: PodBatch, cfg, *,
                         tail_chunk: int, min_passes: int, max_passes: int,
                         charge_counts: bool = True,
                         topo_prefix: int = None,
                         topo_mask: jnp.ndarray = None):
    """The device-resident adaptive tail: run `tail_pass` inside a
    lax.while_loop until the stragglers drain or the retry budget is
    spent, entirely on device.

    Returns (snap, counts, assign, stats) with stats i32[4] =
    [stragglers_after_sweep, stragglers_final, never_retried, passes] —
    a host that wants the numbers pays exactly ONE readback, after the
    loop, instead of one blocking straggler-count transfer per adaptive
    decision (each cost a full device round trip; the 10-pass
    full-gate tail paid up to 10 of them).

    Retry-budget semantics (mirrors the host-driven oracle pass for
    pass, so placements are bit-identical — tests/test_cascade.py):
    - `min(min_passes, max_passes)` passes always run, even with zero
      stragglers (the warm-path contract callers rely on);
    - further passes run while stragglers remain AND (the count
      improved over the previous pass OR never-retried stragglers
      remain — a pass that placed nothing must not strand disjoint
      windows that were never tried), up to `max_passes`;
    - only the max_passes cap can leave never_retried > 0 (the caller
      should surface that loudly).
    """
    p = pods.valid.shape[0]
    min_eff = min(int(min_passes), int(max_passes))

    def left_count(assign):
        return jnp.sum(pods.valid & (assign < 0)).astype(jnp.int32)

    left0 = left_count(assign)

    def cond(carry):
        _, _, _, _, passes, left, improved, never_retried = carry
        forced = passes < min_eff
        adaptive = ((passes < max_passes) & (left > 0)
                    & (improved | (never_retried > 0)))
        return forced | adaptive

    def body(carry):
        snap, counts, assign, tried, passes, left, _, _ = carry
        snap, counts, assign, tried = tail_pass(
            step_fn, snap, counts, assign, tried, pods, cfg,
            tail_chunk=tail_chunk, charge_counts=charge_counts,
            topo_prefix=topo_prefix, topo_mask=topo_mask)
        bad = pods.valid & (assign < 0)
        new_left = jnp.sum(bad).astype(jnp.int32)
        never_retried = jnp.sum(bad & ~tried).astype(jnp.int32)
        return (snap, counts, assign, tried, passes + jnp.int32(1),
                new_left, new_left < left, never_retried)

    init = (snap, counts, assign, jnp.zeros((p,), bool), jnp.int32(0),
            left0, jnp.asarray(False), left0)
    with obs.phase(obs_phases.PHASE_TAIL_LOOP):
        (snap, counts, assign, _, passes, left, _, never_retried) = \
            jax.lax.while_loop(cond, body, init)
    stats = jnp.stack([left0, left, never_retried, passes])
    return snap, counts, assign, stats
