"""Vectorized synthetic cluster/workload generation for benchmarks and
scale tests.

The typed-object path (SnapshotBuilder) is the production ingest; at 100k
pods a per-object Python loop would dominate the benchmark, so this module
builds the columnar pytrees directly with numpy. Semantics match the
builder (same estimator math, same columns) — cross-checked by tests.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from koordinator_tpu.api.extension import NUM_RESOURCES, PriorityClass, QoSClass, ResourceKind
from koordinator_tpu.snapshot.schema import (
    ClusterSnapshot,
    DeviceState,
    GangState,
    MAX_QUOTA_DEPTH,
    NodeState,
    NUM_AGG,
    NUM_AUX_TYPES,
    NUM_DEV_DIMS,
    PER_POD_FIELDS,
    PodBatch,
    QuotaState,
    ReservationState,
    zeros_devices,
)

R = NUM_RESOURCES
CPU, MEM = int(ResourceKind.CPU), int(ResourceKind.MEMORY)

# live reservation slot hold (synthetic_cluster num_reservations > 0);
# module-level so full_gate_pods can sample owners that actually FIT
RESV_SLOT_CPU, RESV_SLOT_MEM = 4000.0, 8192.0
BCPU, BMEM = int(ResourceKind.BATCH_CPU), int(ResourceKind.BATCH_MEMORY)


def estimate_vectorized(requests: np.ndarray, limits: np.ndarray,
                        priority_class: np.ndarray,
                        cpu_factor: float = 85.0,
                        mem_factor: float = 70.0) -> np.ndarray:
    """Vectorized DefaultEstimator (estimator/default_estimator.go:62-110)
    over [P, R] request/limit columns for the cpu/memory weight dims."""
    p = requests.shape[0]
    out = np.zeros((p, R), np.float32)
    is_batch = priority_class == int(PriorityClass.BATCH)
    is_mid = priority_class == int(PriorityClass.MID)
    for kind, factor, default in ((CPU, cpu_factor, 250.0),
                                  (MEM, mem_factor, 200.0)):
        tier_dim = np.where(
            is_batch, kind + 2, np.where(is_mid, kind + 4, kind))
        req = np.take_along_axis(requests, tier_dim[:, None], 1)[:, 0]
        lim = np.take_along_axis(limits, tier_dim[:, None], 1)[:, 0]
        use_lim = lim > req
        qty = np.where(use_lim, lim, req)
        f = np.where(use_lim, 100.0, factor)
        est = np.floor(qty.astype(np.float64) * f / 100.0 + 0.5)
        est = np.where(lim > 0, np.minimum(est, lim), est)
        est = np.where(qty == 0, default, est)
        out[:, kind] = est.astype(np.float32)
    return out


def synthetic_cluster(num_nodes: int, seed: int = 0,
                      max_quotas: int = 64, max_gangs: int = 64,
                      num_quotas: int = 0, num_gangs: int = 0,
                      gang_min_member: int = 8,
                      batch_overcommit_ratio: float = 0.5,
                      usage_cpu_frac: Tuple[float, float] = (0.0, 0.6),
                      gpu_node_frac: float = 0.0,
                      gpus_per_node: int = 8,
                      gpu_memory_mib: float = 81920.0,
                      num_reservations: int = 0,
                      now_version: int = 0) -> ClusterSnapshot:
    """A realistic colocation cluster: heterogeneous nodes, fresh
    NodeMetrics, batch-tier overcommit resources, a two-level quota tree,
    and gangs. All arrays are host numpy; upload via SnapshotStore."""
    rng = np.random.default_rng(seed)
    n = num_nodes
    f32 = np.float32

    cpu_alloc = rng.choice([32000, 64000, 96000], n).astype(f32)
    mem_alloc = (rng.choice([128, 256, 384], n) * 1024).astype(f32)
    alloc = np.zeros((n, R), f32)
    alloc[:, CPU] = cpu_alloc
    alloc[:, MEM] = mem_alloc
    # slo-controller batch overcommit: Batch = Total - Reserved - Used
    usage = np.zeros((n, R), f32)
    usage[:, CPU] = (rng.uniform(*usage_cpu_frac, n) * cpu_alloc).astype(f32)
    usage[:, MEM] = (rng.uniform(0.1, 0.7, n) * mem_alloc).astype(f32)
    alloc[:, BCPU] = np.maximum(
        (cpu_alloc - usage[:, CPU]) * batch_overcommit_ratio, 0)
    alloc[:, BMEM] = np.maximum(
        (mem_alloc - usage[:, MEM]) * batch_overcommit_ratio, 0)

    agg = np.zeros((n, NUM_AGG, R), f32)
    agg[:] = usage[:, None, :]
    agg[:, 2:] *= 1.15  # p90+ slightly above avg

    nodes = NodeState(
        allocatable=alloc,
        requested=np.zeros((n, R), f32),
        usage=usage,
        prod_usage=usage * 0.8,
        agg_usage=agg,
        assigned_estimated=np.zeros((n, R), f32),
        assigned_correction=np.zeros((n, R), f32),
        prod_assigned_estimated=np.zeros((n, R), f32),
        prod_assigned_correction=np.zeros((n, R), f32),
        metric_fresh=np.ones((n,), bool),
        has_agg=np.ones((n,), bool),
        schedulable=np.ones((n,), bool),
        label_group=np.zeros((n,), np.int32),
        numa_cap=np.zeros((n, 4, 2), f32),
        numa_free=np.zeros((n, 4, 2), f32),
        numa_valid=np.zeros((n, 4), bool),
        numa_policy=np.zeros((n,), np.int32),
        cpu_amplification=np.ones((n,), f32),
        taint_group=np.zeros((n,), np.int32),
    )

    q = max_quotas
    quota_min = np.zeros((q, R), f32)
    quota_max = np.full((q, R), np.inf, f32)
    weight = np.zeros((q, R), f32)
    parent = np.full((q,), -1, np.int32)
    ancestors = np.zeros((q, q), bool)
    depth_anc = np.full((q, MAX_QUOTA_DEPTH), -1, np.int32)
    qvalid = np.zeros((q,), bool)
    if num_quotas > 0:
        # quota 0 = root; 1..num_quotas-1 children sharing the cluster
        total_cpu = float(cpu_alloc.sum())
        total_mem = float(mem_alloc.sum())
        qvalid[:num_quotas] = True
        quota_max[0, CPU], quota_max[0, MEM] = total_cpu, total_mem
        ancestors[0, 0] = True
        depth_anc[0, 0] = 0
        for i in range(1, num_quotas):
            share = rng.uniform(0.05, 0.3)
            quota_max[i, CPU] = total_cpu * share
            quota_max[i, MEM] = total_mem * share
            quota_min[i, CPU] = total_cpu * share * 0.2
            quota_min[i, MEM] = total_mem * share * 0.2
            parent[i] = 0
            ancestors[i, i] = True
            ancestors[i, 0] = True
            depth_anc[i, 0] = 0
            depth_anc[i, 1] = i
        weight = np.where(np.isinf(quota_max), 1.0, quota_max).astype(f32)
    quotas = QuotaState(
        min=quota_min, max=quota_max, shared_weight=weight, parent=parent,
        ancestors=ancestors, depth_ancestor=depth_anc,
        used=np.zeros((q, R), f32), demand=np.zeros((q, R), f32),
        allow_lent=np.ones((q,), bool),
        runtime=quota_max.copy(), valid=qvalid)

    g = max_gangs
    gangs = GangState(
        min_member=np.full((g,), gang_min_member, np.int32),
        member_count=np.full((g,), gang_min_member, np.int32),
        assumed=np.zeros((g,), np.int32),
        strict=np.ones((g,), bool),
        satisfied=np.zeros((g,), bool),
        valid=np.arange(g) < num_gangs,
    )
    n_inst = gpus_per_node if gpu_node_frac > 0 else 0
    # Reservation slots: 0 by default — the slim workloads never
    # consume reservations, and a ZERO-length slot axis compiles the
    # virtual-node columns and the AllocateOnce [P, P] ordering
    # machinery OUT of their programs (the previous fixed 8 invalid
    # slots cost a full-width inner-step op for nothing). The FULL-gate
    # cluster requests LIVE slots instead (num_reservations > 0):
    # valid, node-hosted, owner-restricted holds whose capacity is
    # charged on the hosting node (restore semantics — consumers draw
    # from the slot, not the node's open pool), so the flagship
    # exercises the reservation gate semantically, not as dead weight.
    v = int(num_reservations)
    if v > n:
        raise ValueError(f"num_reservations={v} needs at least that many "
                         f"nodes; got {n}")
    r_nodes = np.full((v,), -1, np.int32)
    r_free = np.zeros((v, R), f32)
    if v:
        rrng = np.random.default_rng(seed + 41)
        r_nodes = rrng.choice(n, v, replace=False).astype(np.int32)
        r_free[:, CPU] = RESV_SLOT_CPU
        r_free[:, MEM] = RESV_SLOT_MEM
        req = nodes.requested.copy()
        req[r_nodes, CPU] += RESV_SLOT_CPU
        req[r_nodes, MEM] += RESV_SLOT_MEM
        nodes = nodes.replace(requested=req)
    reservations = ReservationState(
        node=r_nodes,
        free=r_free,
        owner_group=np.arange(v, dtype=np.int32),
        allocate_once=(np.arange(v) % 2 == 0),
        valid=np.ones((v,), bool),
        gpu_free=np.zeros((v, n_inst, NUM_DEV_DIMS), f32),
        gpu_valid=np.zeros((v, n_inst), bool),
        numa_free=np.zeros((v, 4, 2), f32),
        numa_valid=np.zeros((v, 4), bool),
    )
    if gpu_node_frac > 0:
        i = gpus_per_node
        is_gpu_node = rng.uniform(size=n) < gpu_node_frac
        gpu_total = np.zeros((n, NUM_DEV_DIMS), f32)
        gpu_total[is_gpu_node] = (100.0, gpu_memory_mib, 100.0)
        # aggregate device capacity rides node allocatable too (the device
        # plugin reports extended resources), feeding the cheap node-level
        # fit gate before the exact per-instance gates
        alloc = nodes.allocatable
        alloc[is_gpu_node, int(ResourceKind.GPU_CORE)] = i * 100.0
        alloc[is_gpu_node, int(ResourceKind.GPU_MEMORY)] = i * gpu_memory_mib
        nodes = nodes.replace(allocatable=alloc)
        gpu_free = np.broadcast_to(gpu_total[:, None, :],
                                   (n, i, NUM_DEV_DIMS)).copy()
        gpu_valid = np.broadcast_to(is_gpu_node[:, None], (n, i)).copy()
        # GPUs split across 2 NUMA nodes, 2 per PCIe root (A100-like)
        inst = np.arange(i)
        gpu_numa = np.broadcast_to((inst * 2 // max(i, 1))[None, :],
                                   (n, i)).astype(np.int32).copy()
        gpu_pcie = np.broadcast_to((inst // 2)[None, :],
                                   (n, i)).astype(np.int32).copy()
        gpu_numa[~is_gpu_node] = -1
        gpu_pcie[~is_gpu_node] = -1
        devices = DeviceState(
            gpu_total=gpu_total, gpu_free=gpu_free, gpu_valid=gpu_valid,
            gpu_numa=gpu_numa, gpu_pcie=gpu_pcie,
            aux_free=np.zeros((n, NUM_AUX_TYPES, 0), f32),
            aux_valid=np.zeros((n, NUM_AUX_TYPES, 0), bool),
        )
    else:
        devices = zeros_devices(n)
    return ClusterSnapshot(nodes=nodes, quotas=quotas, gangs=gangs,
                           reservations=reservations, devices=devices,
                           version=np.int32(now_version))


def synthetic_pods(num_pods: int, seed: int = 1,
                   prod_frac: float = 0.6,
                   num_quotas: int = 0, num_gangs: int = 0,
                   gang_min_member: int = 8,
                   gpu_pod_frac: float = 0.0) -> PodBatch:
    """A pending-pod batch: prod pods request native cpu/mem, batch pods
    request batch-tier resources (webhook translation, SURVEY.md 2.3)."""
    rng = np.random.default_rng(seed)
    p = num_pods
    f32 = np.float32
    is_prod = rng.uniform(size=p) < prod_frac
    prio_class = np.where(is_prod, int(PriorityClass.PROD),
                          int(PriorityClass.BATCH)).astype(np.int8)
    priority = np.where(is_prod, 9000, 5000).astype(np.int32) + \
        rng.integers(0, 999, p).astype(np.int32)

    cpu_req = (rng.integers(1, 16, p) * 500).astype(f32)
    mem_req = (rng.integers(1, 32, p) * 512).astype(f32)
    requests = np.zeros((p, R), f32)
    requests[is_prod, CPU] = cpu_req[is_prod]
    requests[is_prod, MEM] = mem_req[is_prod]
    requests[~is_prod, BCPU] = cpu_req[~is_prod]
    requests[~is_prod, BMEM] = mem_req[~is_prod]
    limits = np.zeros((p, R), f32)

    gpu_ratio = np.zeros((p,), f32)
    if gpu_pod_frac > 0:
        # mix of shared (half-GPU), full single, and multi-GPU trainers
        is_gpu = rng.uniform(size=p) < gpu_pod_frac
        shape = rng.choice([50, 100, 200, 400], p,
                           p=[0.4, 0.3, 0.2, 0.1]).astype(f32)
        gpu_ratio = np.where(is_gpu, shape, 0.0).astype(f32)
        requests[:, int(ResourceKind.GPU_CORE)] = np.where(
            is_gpu, shape, 0.0)

    estimated = estimate_vectorized(requests, limits, prio_class)

    gang_id = np.full((p,), -1, np.int32)
    if num_gangs > 0:
        members = num_gangs * gang_min_member
        gang_id[:members] = np.repeat(np.arange(num_gangs, dtype=np.int32),
                                      gang_min_member)
    quota_id = np.full((p,), -1, np.int32)
    if num_quotas > 1:
        quota_id = rng.integers(1, num_quotas, p).astype(np.int32)

    return PodBatch(
        requests=requests, estimated=estimated,
        qos=np.where(is_prod, int(QoSClass.LS), int(QoSClass.BE)).astype(np.int8),
        priority_class=prio_class, priority=priority,
        gang_id=gang_id, quota_id=quota_id,
        selector_id=np.full((p,), -1, np.int32),
        selector_match=np.zeros((8, 64), bool),
        reservation_owner=np.full((p,), -1, np.int32),
        gpu_ratio=gpu_ratio,
        numa_single=np.zeros((p,), bool),
        daemonset=np.zeros((p,), bool),
        toleration_id=np.zeros((p,), np.int32),
        tol_forbid=np.zeros((1, 1), bool),
        tol_prefer=np.zeros((1, 1), f32),
        spread_id=np.full((p,), -1, np.int32),
        spread_carrier=np.zeros((p, 1), bool),
        spread_member=np.zeros((p, 1), bool),
        spread_max_skew=np.ones((1,), f32),
        spread_domain=np.full((1, 1), -1, np.int32),
        spread_count0=np.zeros((1, 1), f32),
        spread_dvalid=np.zeros((1, 1), bool),
        anti_id=np.full((p,), -1, np.int32),
        anti_member=np.zeros((p, 1), bool),
        anti_carrier=np.zeros((p, 1), bool),
        anti_domain=np.full((1, 1), -1, np.int32),
        anti_count0=np.zeros((1, 1), f32),
        anti_carrier_count0=np.zeros((1, 1), f32),
        aff_id=np.full((p,), -1, np.int32),
        aff_carrier=np.zeros((p, 1), bool),
        aff_member=np.zeros((p, 1), bool),
        aff_domain=np.full((1, 1), -1, np.int32),
        aff_count0=np.zeros((1, 1), f32),
        valid=np.ones((p,), bool),
    )


def with_two_numa_zones(snap: ClusterSnapshot) -> ClusterSnapshot:
    """Populate every node with two NUMA zones at half capacity each
    (the dual-socket shape; shared by the full-gate flagship workload
    and BASELINE config 2 so the zone model cannot drift). The zone
    AXIS is compacted to exactly 2: every [.., Z, 2] intermediate in
    the zone kernels ([P, N, Z, 2] score/fit tensors) halves versus the
    4-slot default, and the reservation zone columns are sliced to
    match (the extended-pool concat requires one Z)."""
    nodes = snap.nodes
    alloc = np.asarray(nodes.allocatable)
    n = alloc.shape[0]
    z = 2
    resv_valid = np.asarray(snap.reservations.numa_valid)
    if resv_valid.shape[1] < z:
        raise ValueError(
            "with_two_numa_zones needs >= 2 reservation zone slots to "
            "keep the node/reservation zone axes consistent")
    if resv_valid[:, z:].any():
        raise ValueError(
            "with_two_numa_zones would silently drop reservation NUMA "
            "holds in zones >= 2; this helper is for dual-socket "
            "workloads only")
    numa_cap = np.zeros((n, z, 2), np.float32)
    numa_cap[:, 0, 0] = alloc[:, CPU] / 2
    numa_cap[:, 1, 0] = alloc[:, CPU] / 2
    numa_cap[:, 0, 1] = alloc[:, MEM] / 2
    numa_cap[:, 1, 1] = alloc[:, MEM] / 2
    numa_valid = np.ones((n, z), bool)
    resv = snap.reservations
    return snap.replace(
        nodes=nodes.replace(
            numa_cap=numa_cap, numa_free=numa_cap.copy(),
            numa_valid=numa_valid),
        reservations=resv.replace(
            numa_free=np.asarray(resv.numa_free)[:, :z],
            numa_valid=np.asarray(resv.numa_valid)[:, :z]))


def full_gate_reservations(num_nodes: int) -> int:
    """Live-slot count shared by full_gate_cluster and full_gate_pods
    (owner ids must line up with slot owner_groups)."""
    return min(64, num_nodes // 2)


def full_gate_cluster(num_nodes: int, seed: int = 0,
                      num_quotas: int = 32, max_quotas: int = 64,
                      num_gangs: int = 64, max_gangs: int = 64,
                      gpu_node_frac: float = 0.25,
                      gpus_per_node: int = 8,
                      num_reservations: int = None) -> ClusterSnapshot:
    """The FULL-gate flagship cluster: everything the slim bench cluster
    has, plus two populated NUMA zones per node, GPU nodes with
    per-instance pools, and a 3-class taint landscape (none/dedicated/
    gpu-exclusive). The reference's hot loop runs every registered
    plugin for every pod (framework_extender.go:204-259); this workload
    makes the batched program compile every gate in."""
    if num_reservations is None:
        num_reservations = full_gate_reservations(num_nodes)
    snap = synthetic_cluster(num_nodes, seed=seed, num_quotas=num_quotas,
                             max_quotas=max_quotas, num_gangs=num_gangs,
                             max_gangs=max_gangs,
                             gpu_node_frac=gpu_node_frac,
                             gpus_per_node=gpus_per_node,
                             num_reservations=num_reservations)
    snap = with_two_numa_zones(snap)
    rng = np.random.default_rng(seed + 17)
    # taint classes: 0 = untainted, 1 = dedicated, 2 = gpu-exclusive
    taint_group = rng.choice(3, num_nodes,
                             p=[0.8, 0.15, 0.05]).astype(np.int32)
    return snap.replace(nodes=snap.nodes.replace(taint_group=taint_group))


def full_gate_pods(num_pods: int, num_nodes: int, seed: int = 1,
                   num_quotas: int = 32, num_gangs: int = 64,
                   gang_min_member: int = 8, num_zones: int = 16,
                   gpu_pod_frac: float = 0.1,
                   numa_bind_frac: float = 0.33,
                   n_spread_groups: int = 8, spread_frac: float = 0.15,
                   max_skew: float = 64.0,
                   n_anti_groups: int = 16, anti_members: int = 64,
                   n_aff_groups: int = 8, aff_members: int = 48,
                   num_reservations: int = None) -> PodBatch:
    """The FULL-gate flagship workload: quota + gang pods plus NUMA-bound
    prod pods, GPU pods, three toleration classes, PodTopologySpread
    groups over zone domains, required anti-affinity over hostname
    domains, and affinity groups co-locating over zones. Every static
    gate switch is on, so schedule_batch compiles the complete plugin
    chain — the faithful analogue of the reference running all plugins
    per pod."""
    pods = synthetic_pods(num_pods, seed=seed, num_quotas=num_quotas,
                          num_gangs=num_gangs,
                          gang_min_member=gang_min_member,
                          gpu_pod_frac=gpu_pod_frac)
    rng = np.random.default_rng(seed + 29)
    p = num_pods
    f32 = np.float32

    # a third of prod (native-CPU) pods are single-NUMA bound (the
    # resource-spec annotation + LSR path, bench config 2 semantics)
    is_prod = np.asarray(pods.priority_class) == int(PriorityClass.PROD)
    numa_single = is_prod & (rng.uniform(size=p) < numa_bind_frac)

    # tolerations: set 0 tolerates nothing, set 1 tolerates dedicated,
    # set 2 tolerates both taint classes
    toleration_id = rng.choice(3, p, p=[0.7, 0.2, 0.1]).astype(np.int32)
    tol_forbid = np.array([[False, True, True],
                           [False, False, True],
                           [False, False, False]])
    # dedicated nodes carry one PreferNoSchedule taint for the
    # non-tolerating set (engages the taint score penalty too)
    tol_prefer = np.array([[0.0, 1.0, 1.0],
                           [0.0, 0.0, 1.0],
                           [0.0, 0.0, 0.0]], f32)

    # MULTI-CONSTRAINT spread, the upstream default profile: every
    # spread pod carries a ZONE constraint (group g) AND a HOSTNAME
    # constraint (companion group g + n_spread_groups) together — the
    # carrier matrix gates it by both. Zone groups spread over
    # num_zones domains; hostname groups spread over per-node domains
    # with a loose skew (the kube-scheduler zone+hostname pair).
    zone_of_node = (np.arange(num_nodes) % num_zones).astype(np.int32)
    host_of_node = np.arange(num_nodes, dtype=np.int32)
    n_sg_total = 2 * n_spread_groups
    d_cap = max(num_zones, num_nodes)
    spread_domain = np.empty((n_sg_total, num_nodes), np.int32)
    spread_domain[:n_spread_groups] = zone_of_node
    spread_domain[n_spread_groups:] = host_of_node
    in_spread = rng.uniform(size=p) < spread_frac
    sgrp = rng.integers(0, n_spread_groups, p).astype(np.int32)
    spread_id = np.where(in_spread, sgrp, -1).astype(np.int32)
    spread_member = np.zeros((p, n_sg_total), bool)
    spread_carrier = np.zeros((p, n_sg_total), bool)
    rows = np.flatnonzero(in_spread)
    spread_member[rows, sgrp[in_spread]] = True
    spread_member[rows, sgrp[in_spread] + n_spread_groups] = True
    spread_carrier[rows, sgrp[in_spread]] = True
    spread_carrier[rows, sgrp[in_spread] + n_spread_groups] = True
    spread_count0 = np.zeros((n_sg_total, d_cap), f32)
    spread_dvalid = np.zeros((n_sg_total, d_cap), bool)
    spread_dvalid[:n_spread_groups, :num_zones] = True
    spread_dvalid[n_spread_groups:, :num_nodes] = True
    # hostname skew stays loose relative to members-per-group so the
    # workload remains schedulable while the per-node cap still gates
    host_skew = max(float(np.ceil(p * spread_frac / n_spread_groups
                                  / max(num_nodes, 1))) + 3.0, 4.0)
    spread_max_skew = np.concatenate([
        np.full((n_spread_groups,), max_skew, f32),
        np.full((n_spread_groups,), host_skew, f32)])

    # group memberships scale DOWN with small batches (the constrained
    # pods stay <= ~half the batch) instead of crashing an undersized
    # run with an opaque sampling error
    anti_members = max(min(anti_members, p // (4 * n_anti_groups)), 1)
    aff_members = max(min(aff_members, p // (4 * n_aff_groups)), 1)
    total_anti = n_anti_groups * anti_members
    total_aff = n_aff_groups * aff_members
    if total_anti + total_aff > p:
        raise ValueError(
            f"full_gate_pods needs at least {n_anti_groups + n_aff_groups}"
            f" pods for {n_anti_groups} anti + {n_aff_groups} affinity "
            f"groups; got {p}")

    # required anti-affinity over HOSTNAME domains: each group's
    # carriers must land on distinct nodes (the kv-service shape)
    host_domain = np.arange(num_nodes, dtype=np.int32)
    anti_domain = np.broadcast_to(
        host_domain, (n_anti_groups, num_nodes)).copy()
    anti_id = np.full((p,), -1, np.int32)
    anti_member = np.zeros((p, n_anti_groups), bool)
    anti_carrier = np.zeros((p, n_anti_groups), bool)
    a_idx = rng.choice(p, total_anti, replace=False)
    a_grp = np.repeat(np.arange(n_anti_groups, dtype=np.int32),
                      anti_members)
    anti_id[a_idx] = a_grp
    anti_member[a_idx, a_grp] = True
    anti_carrier[a_idx, a_grp] = True
    anti_count0 = np.zeros((n_anti_groups, num_nodes), f32)
    anti_carrier_count0 = np.zeros((n_anti_groups, num_nodes), f32)

    # affinity groups co-locating over zones (self-bootstrap opens the
    # first domain, the rest must follow); groups come in PAIRS — every
    # odd group's member ALSO carries the even partner's term
    # (multi-term pods: both groups must hold where they land). All
    # members are dual so the pair CONVERGES: a partial overlap would
    # let the two groups bootstrap different zones and strand the
    # multi-term pods with an empty intersection — a workload bug, not
    # a scheduler property.
    aff_domain = np.broadcast_to(
        zone_of_node, (n_aff_groups, num_nodes)).copy()
    aff_id = np.full((p,), -1, np.int32)
    aff_member = np.zeros((p, n_aff_groups), bool)
    aff_carrier = np.zeros((p, n_aff_groups), bool)
    # disjoint from the anti pods so one pod never carries both terms
    remaining = np.setdiff1d(np.arange(p), a_idx, assume_unique=False)
    f_idx = rng.choice(remaining, total_aff, replace=False)
    f_grp = np.repeat(np.arange(n_aff_groups, dtype=np.int32),
                      aff_members)
    aff_id[f_idx] = f_grp
    aff_member[f_idx, f_grp] = True
    aff_carrier[f_idx, f_grp] = True
    for g in range(1, n_aff_groups, 2):
        dual = f_idx[(f_grp == g)]
        aff_member[dual, g - 1] = True
        aff_carrier[dual, g - 1] = True
    aff_count0 = np.zeros((n_aff_groups, num_zones), f32)

    # reservation owners: two pods compete for each live slot of the
    # full-gate cluster (num_reservations defaults to the shared
    # full_gate_reservations formula so owner ids line up with slot
    # owner_groups) — the AllocateOnce single-winner ordering and the
    # slot virtual-node columns run against real consumers, not dead
    # weight. Owners are sampled from pods that can actually FIT the
    # slot hold: requests within (RESV_SLOT_CPU, RESV_SLOT_MEM) on the
    # prod dims and zero elsewhere (excludes batch-tier, device and
    # CPU-bind pods — the slots carry no zone/instance holds).
    from koordinator_tpu.scheduler.plugins import deviceshare
    v = full_gate_reservations(num_nodes) if num_reservations is None \
        else int(num_reservations)
    resv_owner = np.full((p,), -1, np.int32)
    if v:
        reqs = np.asarray(pods.requests)
        slot_free = np.zeros((reqs.shape[1],), np.float32)
        slot_free[CPU], slot_free[MEM] = RESV_SLOT_CPU, RESV_SLOT_MEM
        fits_slot = (reqs <= slot_free[None, :]).all(axis=1)
        plain = np.flatnonzero(
            fits_slot & ~np.asarray(deviceshare.has_device_request(pods))
            & ~numa_single)
        owners = rng.choice(plain, min(2 * v, plain.size),
                            replace=False)
        resv_owner[owners] = (np.arange(owners.size) % v).astype(
            np.int32)

    return pods.replace(
        numa_single=numa_single,
        reservation_owner=resv_owner,
        toleration_id=toleration_id, tol_forbid=tol_forbid,
        tol_prefer=tol_prefer,
        spread_id=spread_id, spread_carrier=spread_carrier,
        spread_member=spread_member,
        spread_max_skew=spread_max_skew,
        spread_domain=spread_domain, spread_count0=spread_count0,
        spread_dvalid=spread_dvalid,
        anti_id=anti_id, anti_member=anti_member,
        anti_carrier=anti_carrier, anti_domain=anti_domain,
        anti_count0=anti_count0,
        anti_carrier_count0=anti_carrier_count0,
        aff_id=aff_id, aff_carrier=aff_carrier, aff_member=aff_member,
        aff_domain=aff_domain, aff_count0=aff_count0,
        has_taints=True, has_spread=True, has_anti=True, has_aff=True)


def dom_classes(pods: PodBatch) -> tuple:
    """Static domain-class partition for core.schedule_batch: groups
    whose domain-matrix rows are byte-identical (the upstream
    topologyKey determines the row, so zone-keyed groups share one row
    shape and hostname-keyed groups another) share an in-step
    same-domain mask. Derived from the ACTUAL rows, so the contract
    (identical rows within a class) holds by construction."""
    def classes(dom):
        dom = np.asarray(dom)
        seen = {}
        for g in range(dom.shape[0]):
            seen.setdefault(dom[g].tobytes(), []).append(g)
        return tuple(tuple(v) for v in seen.values())
    return (classes(pods.spread_domain), classes(pods.anti_domain),
            classes(pods.aff_domain))


def topo_constrained_mask(pods: PodBatch) -> np.ndarray:
    """bool[P]: pods carrying or matching ANY spread/anti/aff term —
    the rows core.schedule_batch's `topo_prefix` contract requires at
    the front of each chunk."""
    p = pods.valid.shape[0]
    constrained = np.zeros((p,), bool)
    for f in ("spread_member", "spread_carrier", "anti_member",
              "anti_carrier", "aff_member", "aff_carrier"):
        m = np.asarray(getattr(pods, f))
        if m.shape[0] == p:
            constrained |= m.any(axis=1)
    return constrained


def pack_topo_prefix(pods: PodBatch, chunk: int,
                     align: int = 128) -> tuple:
    """Topology-class view of pack_gate_prefixes (one packing
    mechanism, one contract implementation): returns
    `(packed_pods, topo_prefix, constrained_mask)` satisfying
    core.schedule_batch's topo_prefix packing contract.

    On constraint-sparse workloads (the upstream norm: most pods carry
    no inter-pod term) this shrinks the scheduler's in-step same-domain
    [P, P] machinery to [prefix, prefix] — quadratic savings for the
    price of a stable in-chunk reorder. Queue semantics are unaffected:
    schedule_batch ranks by (priority desc, index asc), so the reorder
    only permutes tie-breaks among equal-priority pods, exactly like
    any other arrival order of the same queue. The returned mask is in
    PACKED order (core.tail_select takes it to keep retry batches
    inside the contract)."""
    packed, prefixes, masks = pack_gate_prefixes(pods, chunk,
                                                 align=align)
    return packed, prefixes["topo"], masks["topo"]


def pack_gate_prefixes(pods: PodBatch, chunk: int,
                       align: int = 128) -> tuple:
    """Pack THREE gate classes into nested chunk prefixes and return
    `(packed_pods, prefixes, masks)` with `prefixes`/`masks` dicts
    keyed "topo" / "numa" / "gpu" satisfying the corresponding
    schedule_batch packing contracts (topo_prefix / numa_prefix /
    gpu_prefix).

    Pods sort within each chunk by (topo, numa, gpu) descending
    membership (stable), giving segment order [T..][N..][G..][rest]:
    every topo pod precedes every non-topo pod, every numa pod every
    (non-topo, non-numa) pod, and so on — so the three prefixes nest
    (topo <= numa <= gpu) and each class is fully covered by its own
    prefix. Classes: topo = any spread/anti/aff term (the
    topo_constrained_mask), numa = CPU-bind (numa_single), gpu = any
    device request (deviceshare.has_device_request). The numa_prefix
    contract ALSO requires a policy-free snapshot — that part is the
    caller's to assert (SchedulerService's auto-pack does), since the
    packer never sees nodes."""
    from koordinator_tpu.scheduler.plugins import deviceshare

    p = pods.valid.shape[0]
    if p % chunk:
        raise ValueError(f"{p} pods not divisible by chunk {chunk}")
    topo = topo_constrained_mask(pods)
    numa = np.asarray(pods.numa_single, bool)
    gpu = np.asarray(deviceshare.has_device_request(pods), bool)
    perm = np.empty((p,), np.int64)
    worst = {"topo": 0, "numa": 0, "gpu": 0}
    for s in range(0, p, chunk):
        t = topo[s:s + chunk]
        n = t | numa[s:s + chunk]
        g = n | gpu[s:s + chunk]
        # lexsort: last key is primary; stable within equal keys
        perm[s:s + chunk] = s + np.lexsort((~g, ~n, ~t))
        worst["topo"] = max(worst["topo"], int(t.sum()))
        worst["numa"] = max(worst["numa"], int(n.sum()))
        worst["gpu"] = max(worst["gpu"], int(g.sum()))
    prefixes = {k: min(-(-v // align) * align, chunk)
                for k, v in worst.items()}
    packed = pods.replace(**{f: np.asarray(getattr(pods, f))[perm]
                             for f in PER_POD_FIELDS})
    masks = {"topo": topo[perm], "numa": numa[perm], "gpu": gpu[perm],
             # the applied permutation: packed[i] == pods[perm[i]], so
             # original_row = perm[packed_row]; callers mapping per-pod
             # RESULTS back to the caller's order index with the
             # INVERSE permutation (inv[perm] = arange; the service
             # path does exactly this)
             "perm": perm}
    # the contracts the scheduler relies on (real raises: silent
    # miscomputation on violation, so -O must not strip these)
    for key in ("topo", "numa", "gpu"):
        m, pref = masks[key], prefixes[key]
        for s in range(0, p, chunk):
            if m[s + pref:s + chunk].any():
                raise ValueError(
                    f"pack_gate_prefixes: {key} pod escaped its prefix")
    return packed, prefixes, masks


def slice_batch(batch: PodBatch, start: int, size: int) -> PodBatch:
    """Static-size pod-chunk view (selector_match is batch-global)."""
    return batch.replace(**{f: getattr(batch, f)[start:start + size]
                            for f in PER_POD_FIELDS})
