"""ClusterSnapshot: the device-resident columnar cluster state.

Design (TPU-first, SURVEY.md 2.9):
- Every per-node / per-pod / per-quota map in the reference becomes a fixed-
  shape array column; XLA needs static shapes, so capacities (N nodes, P pods,
  Q quotas, G gangs, Z NUMA zones, V reservations) are padded to the next
  bucket and masked with validity columns.
- All "informer caches" the scheduler hot loop reads (NodeInfo requested/
  allocatable, NodeMetric usage + percentiles, quota tree, gang state,
  reservation state, NUMA free) are materialized here, so one jitted program
  can filter+score+commit a pod batch with zero host round-trips.
- float32 everywhere on the resource axis (canonical units: millicores / MiB)
  — exact-equality semantics of the Go int64 math are preserved by comparing
  with a tolerance chosen so the golden tests match bit-for-bit at realistic
  magnitudes.

Reference parity: NodeInfo snapshot + SLO/NodeMetric/NodeResourceTopology /
quota/gang/reservation caches (SURVEY.md 1, 2.1).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Mapping, Optional, Tuple, Union

import flax.struct
import jax.numpy as jnp

from koordinator_tpu.api.extension import NUM_RESOURCES

# Aggregation rows in NodeState.agg_usage, in order.
AGG_TYPES = ("avg", "p50", "p90", "p95", "p99")
NUM_AGG = len(AGG_TYPES)

# Static max depth of the elastic-quota tree (root at depth 0).
MAX_QUOTA_DEPTH = 6

# Device-instance resource dims (DeviceState.gpu_free last axis), mirroring
# the GPU resources of apis/extension/device_share.go:44-46.
DEV_CORE = 0    # gpu-core percent (100 == one full GPU)
DEV_MEM = 1     # gpu-memory MiB
DEV_RATIO = 2   # gpu-memory-ratio percent
NUM_DEV_DIMS = 3

# Aux device pools (DeviceState.aux_free axis 1): percent units per instance
# (an RDMA/FPGA virtual function is allocated from one instance).
AUX_RDMA = 0
AUX_FPGA = 1
NUM_AUX_TYPES = 2

Array = Any  # jnp.ndarray (host numpy allowed pre-upload)


@flax.struct.dataclass
class NodeState:
    """Per-node columns. Shapes: [N, ...] with R = NUM_RESOURCES.

    Mirrors: k8s NodeInfo (allocatable/requested), slo NodeMetric status
    (node_usage, prod_usage, aggregated percentiles, freshness), NUMA zones
    from NodeResourceTopology.
    """

    allocatable: Array      # f32[N, R] node allocatable (estimator-adjusted)
    requested: Array        # f32[N, R] sum of requests of assigned pods
    usage: Array            # f32[N, R] NodeMetric nodeUsage
    prod_usage: Array       # f32[N, R] sum of prod-tier pod usages
    agg_usage: Array        # f32[N, NUM_AGG, R] percentile node usage
    assigned_estimated: Array  # f32[N, R] Σ max(estimator(pod), reported
                               # usage) for recently-assigned pods
                               # (podAssignCache / estimatedAssignedPodUsed,
                               # load_aware.go:260-267, 340-378)
    assigned_correction: Array  # f32[N, R] Σ reported usage of those
                                # estimated pods — subtracted from the node
                                # usage source at score time with the >=
                                # guard (load_aware.go:300-315)
    prod_assigned_estimated: Array   # f32[N, R] prod-only variant
    prod_assigned_correction: Array  # f32[N, R] prod-only variant
    metric_fresh: Array     # bool[N] NodeMetric exists and is not expired
    has_agg: Array          # bool[N] aggregated percentiles available
    schedulable: Array      # bool[N] node exists, not cordoned
    label_group: Array      # i32[N] node-label equivalence class (selector gate)
    taint_group: Array      # i32[N] node-taint equivalence class (the
                            # TaintToleration gate rides [T, TG] matrices
                            # exactly like the selector gate)
    # NUMA (Z zones): cpu/mem capacity and free per zone
    numa_cap: Array         # f32[N, Z, 2] (cpu milli, mem MiB)
    numa_free: Array        # f32[N, Z, 2]
    numa_valid: Array       # bool[N, Z]
    numa_policy: Array      # i32[N] topology-manager policy code
                            # (scheduler/topologymanager.py POLICY_*;
                            # apis/extension numa-topology-policy label)
    cpu_amplification: Array  # f32[N] node CPU amplification ratio (>= 1;
                            # resource-amplification-ratio annotation). The
                            # webhook publishes AMPLIFIED allocatable; a
                            # CPU-bind pod's exclusive cores cost
                            # request x ratio against it
                            # (nodenumaresource filterAmplifiedCPUs)

    @property
    def num_nodes(self) -> int:
        return self.allocatable.shape[0]


@flax.struct.dataclass
class PodBatch:
    """The pending-pod batch being scheduled. Shapes: [P, ...].

    `requests` are already translated to the priority tier's extended
    resources (api.extension.translate_resource_by_priority);
    `estimated` is the LoadAware estimator output
    (estimator/default_estimator.go:62-110).
    """

    requests: Array         # f32[P, R]
    estimated: Array        # f32[P, R]
    qos: Array              # i8[P] QoSClass
    priority_class: Array   # i8[P] PriorityClass
    priority: Array         # i32[P] numeric priority (bands, tie-break)
    gang_id: Array          # i32[P] index into GangState, -1 = none
    quota_id: Array         # i32[P] index into QuotaState, -1 = none
    selector_id: Array      # i32[P] row into selector_match, -1 = match all
    selector_match: Array   # bool[S, L] selector s matches node-label-group l
                            # (distinct pod selectors x distinct node label
                            # sets — the nodeSelector gate without a P x N
                            # host-side matrix)
    reservation_owner: Array  # i32[P] owner-match group for reservations, -1
    gpu_ratio: Array        # f32[P] explicit gpu-memory-ratio request (0 =
                            # unspecified; requests[GPU_MEMORY] > 0 wins and
                            # the ratio is derived per node from the node's
                            # GPU memory, devicehandler_gpu.go:68-90)
    numa_single: Array      # bool[P] requires single-NUMA-node placement
    daemonset: Array        # bool[P] DaemonSet pods bypass LoadAware filter
                            # (load_aware.go isDaemonSetPod)
    toleration_id: Array    # i32[P] row into the toleration matrices
                            # (row 0 = the empty toleration set)
    tol_forbid: Array       # bool[T, TG] toleration set t leaves an
                            # untolerated NoSchedule/NoExecute taint on
                            # node-taint-group g (TaintToleration filter)
    tol_prefer: Array       # f32[T, TG] count of untolerated
                            # PreferNoSchedule taints (score penalty,
                            # upstream tainttoleration scoring)
    # PodTopologySpread (upstream hard constraints), batched: pods with
    # an identical (namespace, key, skew, selector) constraint share a
    # spread group; [1, 1]-shaped matrices mean no spread modeling and
    # the gate compiles out. Gating runs at ROUND granularity — exact at
    # chunk size 1 like every other commit gate. A pod carrying SEVERAL
    # constraints (zone + hostname is the upstream default profile) is
    # gated by each via the carrier MATRIX, the same shape as anti.
    spread_id: Array        # i32[P] FIRST carried group (diagnostics;
                            # gating rides spread_carrier), -1 = none
    spread_carrier: Array   # bool[P, Sg] pod carries group's constraint
    spread_member: Array    # bool[P, Sg] pod matches group's selector
                            # (charges the domain count when placed, even
                            # without carrying the constraint itself)
    spread_max_skew: Array  # f32[Sg]
    spread_domain: Array    # i32[Sg, N] node's domain for the group's
                            # topology key, -1 = node lacks the label
                            # (hard constraints reject such nodes)
    spread_count0: Array    # f32[Sg, D] matching running pods per domain
    spread_dvalid: Array    # bool[Sg, D] domain exists in the cluster
    # inter-pod affinity/anti-affinity (upstream required terms), the
    # same (group, domain) machinery: anti groups admit a domain only at
    # count 0 (nodes LACKING the topology key pass — no pair can exist);
    # affinity groups require count > 0, with a bootstrap when nothing
    # matches anywhere and the pod matches its own selector. The
    # per-(pod, group) member matrices mark which BATCH pods charge a
    # group's domain counts when placed — membership is by selector
    # match, so a matching pod that doesn't carry the term still counts
    # (upstream counts all matching pods, not just constrained ones).
    # Anti-affinity is enforced in BOTH directions with separate count
    # surfaces per group (one per distinct required term):
    # (a) a pod CARRYING a term avoids domains holding selector-
    #     matching pods (the anti_carrier MATRIX gates against
    #     anti_count0 + placed anti_member charges — a pod carrying
    #     SEVERAL terms is gated by each);
    # (b) a pod MATCHING the selector avoids domains holding term
    #     CARRIERS (anti_member gates against anti_carrier_count0 +
    #     placed anti_carrier charges) — satisfyExistingPodsAntiAffinity
    #     generalized to same-batch carriers.
    anti_id: Array          # i32[P] FIRST carried group (diagnostics;
                            # gating rides anti_carrier), -1 = none
    anti_member: Array      # bool[P, Ag] pod matches group's selector
    anti_carrier: Array     # bool[P, Ag] pod carries group's term
    anti_domain: Array      # i32[Ag, N]
    anti_count0: Array      # f32[Ag, D] matching running/assumed pods
    anti_carrier_count0: Array  # f32[Ag, D] carrier running/assumed pods
    # affinity: a pod carrying several required terms must satisfy each
    # (carrier matrix, like anti/spread)
    aff_id: Array           # i32[P] FIRST carried group (diagnostics;
                            # gating rides aff_carrier), -1 = none
    aff_carrier: Array      # bool[P, Fg] pod carries group's term
    aff_member: Array       # bool[P, Fg]
    aff_domain: Array       # i32[Fg, N]
    aff_count0: Array       # f32[Fg, D]
    valid: Array            # bool[P]
    # STATIC gate switches (aux data, not arrays): whether the batch
    # models each constraint family. Shape-based sentinels are ambiguous
    # — a legitimate 1-group/1-domain config collides with the [1, 1]
    # degenerate — so the builder sets these explicitly and the
    # scheduler compiles each gate in/out on them.
    has_taints: bool = flax.struct.field(pytree_node=False, default=False)
    has_spread: bool = flax.struct.field(pytree_node=False, default=False)
    has_anti: bool = flax.struct.field(pytree_node=False, default=False)
    has_aff: bool = flax.struct.field(pytree_node=False, default=False)

    @property
    def num_pods(self) -> int:
        return self.requests.shape[0]


# The [P]-leading PodBatch columns — the fields a per-pod gather/reorder
# (chunk slicing, prefix packing, straggler-tail compaction) must
# permute together; batch-global matrices (selector_match, the
# (group x domain) tables, count0 surfaces) stay put. THE one list:
# synthetic.slice_batch, the prefix packers and the device-resident
# tail (scheduler/core.tail_pass) all consume it.
PER_POD_FIELDS = ("requests", "estimated", "qos", "priority_class",
                  "priority", "gang_id", "quota_id", "selector_id",
                  "reservation_owner", "gpu_ratio", "numa_single",
                  "daemonset", "toleration_id", "spread_id",
                  "spread_carrier", "spread_member", "anti_id",
                  "anti_member", "anti_carrier", "aff_id", "aff_carrier",
                  "aff_member", "valid")


@flax.struct.dataclass
class QuotaState:
    """Hierarchical elastic-quota tree, flattened. Shapes: [Q, ...].

    `ancestors[q, a]` is True when quota `a` is `q` or an ancestor of `q` —
    the device-side equivalent of walking parentInfos
    (elasticquota/plugin.go:211-257). Runtime is recomputed by the
    water-filling kernel (ops/waterfill.py).
    """

    min: Array              # f32[Q, R] guaranteed
    max: Array              # f32[Q, R] cap (inf if unlimited)
    shared_weight: Array    # f32[Q, R] fair-share weight (default = max)
    parent: Array           # i32[Q] parent index, -1 = root's parent
    ancestors: Array        # bool[Q, Q]
    depth_ancestor: Array   # i32[Q, D] ancestor at depth d (self included),
                            # -1 past the leaf — lets the commit kernel do an
                            # exact per-level prefix gate without a Q x Q
                            # matmul per pod (D = MAX_QUOTA_DEPTH)
    used: Array             # f32[Q, R] admitted usage
    demand: Array           # f32[Q, R] DIRECT pod demand charged to the
                            # pod's own quota only; ops.waterfill propagates
                            # it bottom-up with the per-level min/max clamp
                            # into limitedRequest (quota_info.go
                            # getLimitRequestNoLock + group_quota_manager.go
                            # recursiveUpdateGroupTreeWithDeltaRequest)
    allow_lent: Array       # bool[Q] allowLentResource: lend unused min
    runtime: Array          # f32[Q, R] water-filled entitlement
    valid: Array            # bool[Q]


@flax.struct.dataclass
class GangState:
    """Coscheduling gang/PodGroup state. Shapes: [G, ...].

    Mirrors core/gang.go:43-83 state machine inputs: minMember quorum and
    the count already assumed/bound.
    """

    min_member: Array       # i32[G]
    member_count: Array     # i32[G] total members seen (quorum check)
    assumed: Array          # i32[G] members already assumed/bound
    strict: Array           # bool[G] strict mode
    satisfied: Array        # bool[G] match-policy satisfied latch: members
    #   pass the gang gates individually and are exempt from all-or-nothing
    #   rollback (core.go:236,286 — a once-satisfied gang short-circuits
    #   PreFilter and is never group-rejected in PostFilter)
    valid: Array            # bool[G]


@flax.struct.dataclass
class DeviceState:
    """Per-node device instances (DeviceShare nodeDeviceCache, SURVEY.md 2.1
    plugins/deviceshare: Device CRs mirrored as device columns).

    GPU pool: I instances per node, each with (core %, memory MiB, memory-
    ratio %) free. A node carries one GPU model, so per-instance totals are a
    single [N, 3] row (devicehandler_gpu.go:82 "a node can only contain one
    type of GPU"). Aux pools (RDMA/FPGA) are percent-unit instances; a
    request is served from a single instance (default device handler
    semantics: desiredCount 1).
    """

    gpu_total: Array        # f32[N, 3] per-INSTANCE totals (core=100 when
                            # present, memory MiB, ratio=100)
    gpu_free: Array         # f32[N, I, 3]
    gpu_valid: Array        # bool[N, I] instance exists and is healthy
    gpu_numa: Array         # i32[N, I] NUMA node of the instance, -1 unknown
    gpu_pcie: Array         # i32[N, I] PCIe root id, -1 unknown (host bind
                            # uses it for joint-allocate minor preference)
    aux_free: Array         # f32[N, A, J] percent free per aux instance
    aux_valid: Array        # bool[N, A, J]

    @property
    def num_instances(self) -> int:
        return self.gpu_free.shape[1]


@flax.struct.dataclass
class ReservationState:
    """Available reservations as device columns. Shapes: [V, ...].

    A reservation is reserved capacity *already counted* in node `requested`;
    a matching pod first consumes reservation free capacity (restore
    semantics, reservation/transformer.go:240-291). Reservations holding
    GPU instances or a NUMA cpuset carry those as per-slot pools the
    scheduler hands to consumers (the deviceshare / nodenumaresource
    ReservationRestorePlugin state): instance columns are indexed by the
    UNDERLYING NODE's minors/zones, so a consumer's grant is directly a
    node-level allocation.
    """

    node: Array             # i32[V] node index the reservation landed on
    free: Array             # f32[V, R] remaining reserved capacity
    owner_group: Array      # i32[V] owner-match group id
    allocate_once: Array    # bool[V]
    valid: Array            # bool[V]
    # reserved device instances (remaining per-instance capacity; zero
    # rows for minors the reservation does not hold)
    gpu_free: Array         # f32[V, I, NUM_DEV_DIMS]
    gpu_valid: Array        # bool[V, I] reserved minors
    # reserved NUMA zone capacity remaining (cpu milli, mem MiB)
    numa_free: Array        # f32[V, Z, 2]
    numa_valid: Array       # bool[V, Z] reserved zones


@flax.struct.dataclass
class ClusterSnapshot:
    """The complete device-resident cluster state (one version)."""

    nodes: NodeState
    quotas: QuotaState
    gangs: GangState
    reservations: ReservationState
    devices: DeviceState
    version: Array          # i32[] monotonically increasing

    @property
    def num_nodes(self) -> int:
        return self.nodes.num_nodes


def zeros_devices(num_nodes: int, num_gpu_inst: int = 0,
                  num_aux_inst: int = 0) -> DeviceState:
    """An all-empty device pool with the given static instance capacities."""
    n, i, j = num_nodes, num_gpu_inst, num_aux_inst
    f32 = jnp.float32
    return DeviceState(
        gpu_total=jnp.zeros((n, NUM_DEV_DIMS), f32),
        gpu_free=jnp.zeros((n, i, NUM_DEV_DIMS), f32),
        gpu_valid=jnp.zeros((n, i), bool),
        gpu_numa=jnp.full((n, i), -1, jnp.int32),
        gpu_pcie=jnp.full((n, i), -1, jnp.int32),
        aux_free=jnp.zeros((n, NUM_AUX_TYPES, j), f32),
        aux_valid=jnp.zeros((n, NUM_AUX_TYPES, j), bool),
    )


def zeros_snapshot(num_nodes: int, num_quotas: int = 1, num_gangs: int = 1,
                   num_reservations: int = 1, num_zones: int = 4,
                   num_gpu_inst: int = 0,
                   num_aux_inst: int = 0) -> ClusterSnapshot:
    """An all-empty snapshot with the given static capacities."""
    n, q, g, v, z, r = (num_nodes, num_quotas, num_gangs, num_reservations,
                        num_zones, NUM_RESOURCES)
    f32 = jnp.float32
    nodes = NodeState(
        allocatable=jnp.zeros((n, r), f32),
        requested=jnp.zeros((n, r), f32),
        usage=jnp.zeros((n, r), f32),
        prod_usage=jnp.zeros((n, r), f32),
        agg_usage=jnp.zeros((n, NUM_AGG, r), f32),
        assigned_estimated=jnp.zeros((n, r), f32),
        assigned_correction=jnp.zeros((n, r), f32),
        prod_assigned_estimated=jnp.zeros((n, r), f32),
        prod_assigned_correction=jnp.zeros((n, r), f32),
        metric_fresh=jnp.zeros((n,), bool),
        has_agg=jnp.zeros((n,), bool),
        schedulable=jnp.zeros((n,), bool),
        label_group=jnp.zeros((n,), jnp.int32),
        numa_cap=jnp.zeros((n, z, 2), f32),
        numa_free=jnp.zeros((n, z, 2), f32),
        numa_valid=jnp.zeros((n, z), bool),
        numa_policy=jnp.zeros((n,), jnp.int32),
        cpu_amplification=jnp.ones((n,), f32),
        taint_group=jnp.zeros((n,), jnp.int32),
    )
    quotas = QuotaState(
        min=jnp.zeros((q, r), f32),
        max=jnp.full((q, r), jnp.inf, f32),
        shared_weight=jnp.zeros((q, r), f32),
        parent=jnp.full((q,), -1, jnp.int32),
        ancestors=jnp.zeros((q, q), bool),
        depth_ancestor=jnp.full((q, MAX_QUOTA_DEPTH), -1, jnp.int32),
        used=jnp.zeros((q, r), f32),
        demand=jnp.zeros((q, r), f32),
        allow_lent=jnp.ones((q,), bool),
        runtime=jnp.full((q, r), jnp.inf, f32),
        valid=jnp.zeros((q,), bool),
    )
    gangs = GangState(
        min_member=jnp.ones((g,), jnp.int32),
        member_count=jnp.zeros((g,), jnp.int32),
        assumed=jnp.zeros((g,), jnp.int32),
        strict=jnp.ones((g,), bool),
        satisfied=jnp.zeros((g,), bool),
        valid=jnp.zeros((g,), bool),
    )
    reservations = ReservationState(
        node=jnp.full((v,), -1, jnp.int32),
        free=jnp.zeros((v, r), f32),
        owner_group=jnp.full((v,), -1, jnp.int32),
        allocate_once=jnp.ones((v,), bool),
        valid=jnp.zeros((v,), bool),
        gpu_free=jnp.zeros((v, num_gpu_inst, NUM_DEV_DIMS), f32),
        gpu_valid=jnp.zeros((v, num_gpu_inst), bool),
        numa_free=jnp.zeros((v, z, 2), f32),
        numa_valid=jnp.zeros((v, z), bool),
    )
    return ClusterSnapshot(nodes=nodes, quotas=quotas, gangs=gangs,
                           reservations=reservations,
                           devices=zeros_devices(n, num_gpu_inst,
                                                 num_aux_inst),
                           version=jnp.zeros((), jnp.int32))


# --- kernel shape contracts ------------------------------------------------
#
# Every jitted entry point (and the kernel helpers it composes) declares a
# machine-checked contract over the named-dimension vocabulary below:
# which dims each argument/output carries, its dtype, and the pad
# semantics callers rely on. Two independent checkers consume the
# registry:
#   Tier A (static, stdlib-only): koordlint's `shape-contract` pass reads
#     the decorator calls straight from the AST (tools/lint/shapes) and
#     abstractly interprets kernel bodies against the declared dims.
#   Tier B (device-free dynamic): tools/shapecheck.py imports this
#     registry and drives jax.eval_shape over every contract with
#     symbolic-sized ShapeDtypeStructs — no device, no compile.
# The decorator itself is a pure registration: zero tracing or runtime
# cost, and every spec is a literal string so the AST tier never has to
# execute anything.
#
# Spec grammar (tools/lint/shapes/spec.py is the single parser):
#   "f32[P,N]"   leaf array: dtype in {f32, i32, i8, u32, bool},
#                dims = named symbols, fixed symbols, or int literals
#   "f32[]"      scalar array
#   "?f32[P,N]"  optional: the value may be None (e.g. compiled-out gates)
#   "PodBatch"   a registered struct (register_struct below)
#   "N"          a bare dim symbol marks a symbolic-int PROPERTY of a
#                struct (documentation for the AST tier; never built)
#   "f32[N~pad:zero,R]"  a PADDED dim declares its pad predicate (the
#                koordpad tier): what the pad region along that dim
#                contains. Three checkers consume the predicates:
#                koordlint's pad-soundness pass (PS001-PS005, static
#                mask-provenance dataflow), tools/padcheck.py (concrete
#                differential runs under two paddings), and
#                parallel/mesh.py's pad fills. PAD_VOCAB below names
#                the predicates; PADDED_DIMS names the dims that must
#                carry one.

# the named-dimension vocabulary — THE shared meaning of every symbol;
# tools/lint/shapes/spec.py carries the same table for the stdlib-only
# tier and tests/test_shape_contract.py pins the two in sync
DIM_VOCAB = {
    "P": "pending pods in the batch",
    "N": "node columns (padded capacity)",
    "I": "GPU instances per node",
    "Z": "NUMA zones per node",
    "G": "gangs (PodGroups)",
    "Q": "elastic-quota tree nodes",
    "V": "reservation slots",
    "R": "resource dims (NUM_RESOURCES; padded like any capacity)",
    "S": "distinct pod node-selectors",
    "L": "node label-equivalence groups",
    "T": "distinct pod toleration sets",
    "TG": "node taint-equivalence groups",
    "SG": "pod-topology-spread groups",
    "AG": "inter-pod anti-affinity groups",
    "FG": "inter-pod affinity groups",
    "DM": "topology domains per constraint group",
    "J": "aux (RDMA/FPGA) VF instances per pool",
    "K": "delta rows per ingest tick",
    "KC": "gathered per-shard top-k candidates (k x node shards)",
    "TC": "tail retry-chunk width",
    "RD": "descheduler threshold resource dims",
    "NS": "descheduler namespace rows (padded)",
}

# dims pinned to module constants rather than free sizes
FIXED_DIMS = {
    "AGG": NUM_AGG,          # aggregation percentile rows
    "DEV": NUM_DEV_DIMS,     # GPU instance resource dims (core/mem/ratio)
    "AX": NUM_AUX_TYPES,     # aux device pools (rdma, fpga)
    "QD": MAX_QUOTA_DEPTH,   # quota-tree depth
}

# the pad-predicate vocabulary (the koordpad tier) — what a `~pad:` token
# on a padded dim promises about the pad region along that dim;
# tools/lint/shapes/spec.py carries the same table for the stdlib-only
# tier and tests/test_pad_soundness.py pins the two in sync
PAD_VOCAB = {
    "zero": "pad entries are 0 (False for bool)",
    "one": "pad entries are 1 (True for bool)",
    "false": "pad entries are False (bool columns only)",
    "-1": "pad entries carry the -1 'none' sentinel",
    "inf": "pad entries are +inf (never gate; f32 only)",
    "unschedulable": "zero-filled node rows additionally killed by the "
                     "schedulable=False guard (pad_nodes_to_mesh rows)",
    "invalid": "content unspecified; masked by the carrying struct's "
               "validity column (valid/gpu_valid/numa_valid/...)",
    "any": "content unspecified; every consumer must guard it "
           "explicitly (no inertness is asserted)",
}

# dims that take padded capacity and therefore MUST declare a pad
# predicate wherever they appear in a struct field or contract spec
# (the PS004 totality check). Deliberately NOT here:
#   R          fixed resource axis — NUM_RESOURCES is exact, never padded
#   S/L/T/TG/  exact equivalence-class tables sized by distinct values,
#   SG/AG/FG     not bucketed capacities
#   TC         static tail retry-chunk width (a tuning constant; varying
#                it changes tail-loop iteration stats, not padding)
#   KC/RD      derived widths (k x shards, threshold rows) — exact
PADDED_DIMS = frozenset({
    "P", "N", "Q", "G", "V", "Z", "I", "J", "DM", "K", "NS",
})

# pad predicate -> the concrete fill value tools/padcheck.py and the
# mesh padder materialize for it (None: no single canonical fill — the
# predicate is a masking promise, not a value)
PAD_FILL_VALUES = {
    "zero": 0,
    "one": 1,
    "false": 0,
    "-1": -1,
    "inf": float("inf"),
    "unschedulable": 0,
    "invalid": None,
    "any": None,
}

FieldSpec = Union[str, Tuple[str, ...]]


class ShapeContract:
    """One kernel's declared tensor contract (a plain record; the
    checkers interpret it — nothing here touches jax)."""

    __slots__ = ("name", "module", "fn", "args", "returns", "static",
                 "callables", "pad")

    def __init__(self, name: str, module: str, fn: Callable,
                 args: Dict[str, FieldSpec], returns: FieldSpec,
                 static: Dict[str, Any], callables: Dict[str, str],
                 pad: str):
        self.name = name
        self.module = module
        self.fn = fn
        self.args = args
        self.returns = returns
        self.static = static
        self.callables = callables
        self.pad = pad

    @property
    def key(self) -> str:
        return f"{self.module}.{self.name}"


# key: "module.function" -> contract (import the defining modules to
# populate; tools/shapecheck.py owns the canonical import list)
SHAPE_CONTRACTS: Dict[str, ShapeContract] = {}
# struct name -> {field: spec}; bare-symbol entries are symbolic-int
# properties (num_nodes = "N"), never constructor fields
STRUCT_SPECS: Dict[str, Dict[str, FieldSpec]] = {}
# struct name -> class, for Tier B instance construction
STRUCT_CLASSES: Dict[str, type] = {}


def register_struct(cls: type, fields: Dict[str, FieldSpec]) -> type:
    """Declare the per-field shape specs of a pytree struct. Static
    (pytree_node=False) fields are omitted — they keep their defaults
    when Tier B builds abstract instances."""
    name = cls.__name__
    prior = STRUCT_SPECS.get(name)
    if prior is not None and prior != fields:
        raise ValueError(f"struct {name!r} re-registered with a "
                         f"different spec")
    STRUCT_SPECS[name] = dict(fields)
    STRUCT_CLASSES[name] = cls
    return cls


def shape_contract(_returns: FieldSpec = None,
                   _static: Optional[Mapping[str, Any]] = None,
                   _callable: Optional[Mapping[str, str]] = None,
                   _pad: str = "",
                   **arg_specs: FieldSpec) -> Callable:
    """Decorator: register the function's kernel shape contract.

    `arg_specs` maps TRACED argument names to specs; static arguments
    the checker must supply go in `_static` (a value that names a dim
    symbol, e.g. "TC", resolves to that dim's assigned size). `_callable`
    maps higher-order arguments to the dotted path of another contracted
    function Tier B substitutes. Apply ABOVE jax.jit so the registered
    callable is the jitted wrapper (eval_shape traces it abstractly).
    """

    def deco(fn: Callable) -> Callable:
        name = getattr(fn, "__name__", None)
        module = getattr(fn, "__module__", None)
        if not name or not module:
            raise ValueError("shape_contract target has no name/module")
        c = ShapeContract(name=name, module=module, fn=fn,
                          args=dict(arg_specs), returns=_returns,
                          static=dict(_static or {}),
                          callables=dict(_callable or {}), pad=_pad)
        if c.key in SHAPE_CONTRACTS:
            raise ValueError(f"duplicate shape contract {c.key}")
        SHAPE_CONTRACTS[c.key] = c
        return fn

    return deco


register_struct(NodeState, {
    "allocatable": "f32[N~pad:unschedulable,R]",
    "requested": "f32[N~pad:unschedulable,R]",
    "usage": "f32[N~pad:unschedulable,R]",
    "prod_usage": "f32[N~pad:unschedulable,R]",
    "agg_usage": "f32[N~pad:unschedulable,AGG,R]",
    "assigned_estimated": "f32[N~pad:unschedulable,R]",
    "assigned_correction": "f32[N~pad:unschedulable,R]",
    "prod_assigned_estimated": "f32[N~pad:unschedulable,R]",
    "prod_assigned_correction": "f32[N~pad:unschedulable,R]",
    "metric_fresh": "bool[N~pad:false]",
    "has_agg": "bool[N~pad:false]",
    "schedulable": "bool[N~pad:false]",
    "label_group": "i32[N~pad:zero]",
    "taint_group": "i32[N~pad:zero]",
    "numa_cap": "f32[N~pad:unschedulable,Z~pad:zero,2]",
    "numa_free": "f32[N~pad:unschedulable,Z~pad:zero,2]",
    "numa_valid": "bool[N~pad:false,Z~pad:false]",
    "numa_policy": "i32[N~pad:zero]",
    "cpu_amplification": "f32[N~pad:one]",
    "num_nodes": "N",
})

register_struct(PodBatch, {
    "requests": "f32[P~pad:zero,R]",
    "estimated": "f32[P~pad:zero,R]",
    "qos": "i8[P~pad:zero]",
    "priority_class": "i8[P~pad:zero]",
    "priority": "i32[P~pad:zero]",
    "gang_id": "i32[P~pad:-1]",
    "quota_id": "i32[P~pad:-1]",
    "selector_id": "i32[P~pad:-1]",
    "selector_match": "bool[S,L]",
    "reservation_owner": "i32[P~pad:-1]",
    "gpu_ratio": "f32[P~pad:zero]",
    "numa_single": "bool[P~pad:false]",
    "daemonset": "bool[P~pad:false]",
    "toleration_id": "i32[P~pad:zero]",
    "tol_forbid": "bool[T,TG]",
    "tol_prefer": "f32[T,TG]",
    "spread_id": "i32[P~pad:-1]",
    "spread_carrier": "bool[P~pad:false,SG]",
    "spread_member": "bool[P~pad:false,SG]",
    "spread_max_skew": "f32[SG]",
    "spread_domain": "i32[SG,N~pad:-1]",
    "spread_count0": "f32[SG,DM~pad:zero]",
    "spread_dvalid": "bool[SG,DM~pad:false]",
    "anti_id": "i32[P~pad:-1]",
    "anti_member": "bool[P~pad:false,AG]",
    "anti_carrier": "bool[P~pad:false,AG]",
    "anti_domain": "i32[AG,N~pad:-1]",
    "anti_count0": "f32[AG,DM~pad:zero]",
    "anti_carrier_count0": "f32[AG,DM~pad:zero]",
    "aff_id": "i32[P~pad:-1]",
    "aff_carrier": "bool[P~pad:false,FG]",
    "aff_member": "bool[P~pad:false,FG]",
    "aff_domain": "i32[FG,N~pad:-1]",
    "aff_count0": "f32[FG,DM~pad:zero]",
    "valid": "bool[P~pad:false]",
    "num_pods": "P",
})

register_struct(QuotaState, {
    "min": "f32[Q~pad:zero,R]",
    "max": "f32[Q~pad:inf,R]",
    "shared_weight": "f32[Q~pad:zero,R]",
    "parent": "i32[Q~pad:-1]",
    "ancestors": "bool[Q~pad:false,Q~pad:false]",
    "depth_ancestor": "i32[Q~pad:-1,QD]",
    "used": "f32[Q~pad:zero,R]",
    "demand": "f32[Q~pad:zero,R]",
    "allow_lent": "bool[Q~pad:one]",
    "runtime": "f32[Q~pad:inf,R]",
    "valid": "bool[Q~pad:false]",
})

register_struct(GangState, {
    "min_member": "i32[G~pad:one]",
    "member_count": "i32[G~pad:zero]",
    "assumed": "i32[G~pad:zero]",
    "strict": "bool[G~pad:one]",
    "satisfied": "bool[G~pad:false]",
    "valid": "bool[G~pad:false]",
})

register_struct(DeviceState, {
    "gpu_total": "f32[N~pad:zero,DEV]",
    "gpu_free": "f32[N~pad:zero,I~pad:zero,DEV]",
    "gpu_valid": "bool[N~pad:false,I~pad:false]",
    "gpu_numa": "i32[N~pad:-1,I~pad:-1]",
    "gpu_pcie": "i32[N~pad:-1,I~pad:-1]",
    "aux_free": "f32[N~pad:zero,AX,J~pad:zero]",
    "aux_valid": "bool[N~pad:false,AX,J~pad:false]",
    "num_instances": "I",
})

register_struct(ReservationState, {
    "node": "i32[V~pad:-1]",
    "free": "f32[V~pad:zero,R]",
    "owner_group": "i32[V~pad:-1]",
    "allocate_once": "bool[V~pad:one]",
    "valid": "bool[V~pad:false]",
    "gpu_free": "f32[V~pad:zero,I~pad:zero,DEV]",
    "gpu_valid": "bool[V~pad:false,I~pad:false]",
    "numa_free": "f32[V~pad:zero,Z~pad:zero,2]",
    "numa_valid": "bool[V~pad:false,Z~pad:false]",
})

register_struct(ClusterSnapshot, {
    "nodes": "NodeState",
    "quotas": "QuotaState",
    "gangs": "GangState",
    "reservations": "ReservationState",
    "devices": "DeviceState",
    "version": "i32[]",
    "num_nodes": "N",
})
