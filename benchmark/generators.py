"""The benchmark's own traffic and cluster generators: numpy only.

Copied from `koordinator_tpu/utils/synthetic.py` at PR 21 (the four
generators the cells use, with the helpers they need) so that a later PR
cannot move the yardstick by editing the program's generator. They
return plain dicts of numpy arrays keyed by the program's schema field
names; `run.py` turns them into the program's pytrees, and
`reference.py` reads the same dicts. `digests.json` pins every column
at fixed seeds (tests/benchmark/test_bench_generators.py).

Constants below are the program's enum values at PR 21, copied as
numbers; `run.py` checks them against the program before a run.
"""

import numpy as np

# ResourceKind (api/extension.py)
R = 11
CPU, MEM, BCPU, BMEM = 0, 1, 2, 3
GPU_CORE, GPU_MEMORY, RDMA, FPGA = 6, 7, 9, 10
# PriorityClass / QoSClass
PRIO_BATCH, PRIO_MID, PRIO_PROD = 2, 3, 4
QOS_LS, QOS_BE = 4, 5
# snapshot/schema.py
NUM_AGG = 5
MAX_QUOTA_DEPTH = 6
NUM_DEV_DIMS = 3
NUM_AUX_TYPES = 2
# live reservation slot hold
RESV_SLOT_CPU, RESV_SLOT_MEM = 4000.0, 8192.0

F32 = np.float32


def estimate_vectorized(requests, limits, priority_class,
                        cpu_factor=85.0, mem_factor=70.0):
    """DefaultEstimator over [P, R] request/limit columns."""
    out = np.zeros((requests.shape[0], R), F32)
    is_batch = priority_class == PRIO_BATCH
    is_mid = priority_class == PRIO_MID
    for kind, factor, default in ((CPU, cpu_factor, 250.0),
                                  (MEM, mem_factor, 200.0)):
        tier_dim = np.where(is_batch, kind + 2,
                            np.where(is_mid, kind + 4, kind))
        req = np.take_along_axis(requests, tier_dim[:, None], 1)[:, 0]
        lim = np.take_along_axis(limits, tier_dim[:, None], 1)[:, 0]
        use_lim = lim > req
        qty = np.where(use_lim, lim, req)
        f = np.where(use_lim, 100.0, factor)
        est = np.floor(qty.astype(np.float64) * f / 100.0 + 0.5)
        est = np.where(lim > 0, np.minimum(est, lim), est)
        est = np.where(qty == 0, default, est)
        out[:, kind] = est.astype(F32)
    return out


def _zeros_devices(n):
    return dict(
        gpu_total=np.zeros((n, NUM_DEV_DIMS), F32),
        gpu_free=np.zeros((n, 0, NUM_DEV_DIMS), F32),
        gpu_valid=np.zeros((n, 0), bool),
        gpu_numa=np.full((n, 0), -1, np.int32),
        gpu_pcie=np.full((n, 0), -1, np.int32),
        aux_free=np.zeros((n, NUM_AUX_TYPES, 0), F32),
        aux_valid=np.zeros((n, NUM_AUX_TYPES, 0), bool))


def synthetic_cluster(num_nodes, seed=0, max_quotas=64, max_gangs=64,
                      num_quotas=0, num_gangs=0, gang_min_member=8,
                      batch_overcommit_ratio=0.5,
                      usage_cpu_frac=(0.0, 0.6), gpu_node_frac=0.0,
                      gpus_per_node=8, gpu_memory_mib=81920.0,
                      num_reservations=0):
    """A colocation cluster: heterogeneous nodes, fresh NodeMetrics,
    batch-tier overcommit, a two-level quota tree and gangs."""
    rng = np.random.default_rng(seed)
    n = num_nodes
    cpu_alloc = rng.choice([32000, 64000, 96000], n).astype(F32)
    mem_alloc = (rng.choice([128, 256, 384], n) * 1024).astype(F32)
    alloc = np.zeros((n, R), F32)
    alloc[:, CPU] = cpu_alloc
    alloc[:, MEM] = mem_alloc
    usage = np.zeros((n, R), F32)
    usage[:, CPU] = (rng.uniform(*usage_cpu_frac, n) * cpu_alloc).astype(F32)
    usage[:, MEM] = (rng.uniform(0.1, 0.7, n) * mem_alloc).astype(F32)
    alloc[:, BCPU] = np.maximum(
        (cpu_alloc - usage[:, CPU]) * batch_overcommit_ratio, 0)
    alloc[:, BMEM] = np.maximum(
        (mem_alloc - usage[:, MEM]) * batch_overcommit_ratio, 0)
    agg = np.zeros((n, NUM_AGG, R), F32)
    agg[:] = usage[:, None, :]
    agg[:, 2:] *= 1.15
    nodes = dict(
        allocatable=alloc, requested=np.zeros((n, R), F32), usage=usage,
        prod_usage=usage * 0.8, agg_usage=agg,
        assigned_estimated=np.zeros((n, R), F32),
        assigned_correction=np.zeros((n, R), F32),
        prod_assigned_estimated=np.zeros((n, R), F32),
        prod_assigned_correction=np.zeros((n, R), F32),
        metric_fresh=np.ones((n,), bool), has_agg=np.ones((n,), bool),
        schedulable=np.ones((n,), bool),
        label_group=np.zeros((n,), np.int32),
        numa_cap=np.zeros((n, 4, 2), F32),
        numa_free=np.zeros((n, 4, 2), F32),
        numa_valid=np.zeros((n, 4), bool),
        numa_policy=np.zeros((n,), np.int32),
        cpu_amplification=np.ones((n,), F32),
        taint_group=np.zeros((n,), np.int32))

    q = max_quotas
    quota_min = np.zeros((q, R), F32)
    quota_max = np.full((q, R), np.inf, F32)
    weight = np.zeros((q, R), F32)
    parent = np.full((q,), -1, np.int32)
    ancestors = np.zeros((q, q), bool)
    depth_anc = np.full((q, MAX_QUOTA_DEPTH), -1, np.int32)
    qvalid = np.zeros((q,), bool)
    if num_quotas > 0:
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
        weight = np.where(np.isinf(quota_max), 1.0, quota_max).astype(F32)
    quotas = dict(
        min=quota_min, max=quota_max, shared_weight=weight, parent=parent,
        ancestors=ancestors, depth_ancestor=depth_anc,
        used=np.zeros((q, R), F32), demand=np.zeros((q, R), F32),
        allow_lent=np.ones((q,), bool), runtime=quota_max.copy(),
        valid=qvalid)

    g = max_gangs
    gangs = dict(
        min_member=np.full((g,), gang_min_member, np.int32),
        member_count=np.full((g,), gang_min_member, np.int32),
        assumed=np.zeros((g,), np.int32), strict=np.ones((g,), bool),
        satisfied=np.zeros((g,), bool), valid=np.arange(g) < num_gangs)

    n_inst = gpus_per_node if gpu_node_frac > 0 else 0
    v = int(num_reservations)
    if v > n:
        raise ValueError(f"num_reservations={v} needs at least that many "
                         f"nodes; got {n}")
    r_nodes = np.full((v,), -1, np.int32)
    r_free = np.zeros((v, R), F32)
    if v:
        rrng = np.random.default_rng(seed + 41)
        r_nodes = rrng.choice(n, v, replace=False).astype(np.int32)
        r_free[:, CPU] = RESV_SLOT_CPU
        r_free[:, MEM] = RESV_SLOT_MEM
        nodes["requested"][r_nodes, CPU] += RESV_SLOT_CPU
        nodes["requested"][r_nodes, MEM] += RESV_SLOT_MEM
    reservations = dict(
        node=r_nodes, free=r_free,
        owner_group=np.arange(v, dtype=np.int32),
        allocate_once=(np.arange(v) % 2 == 0), valid=np.ones((v,), bool),
        gpu_free=np.zeros((v, n_inst, NUM_DEV_DIMS), F32),
        gpu_valid=np.zeros((v, n_inst), bool),
        numa_free=np.zeros((v, 4, 2), F32),
        numa_valid=np.zeros((v, 4), bool))
    if gpu_node_frac > 0:
        i = gpus_per_node
        is_gpu_node = rng.uniform(size=n) < gpu_node_frac
        gpu_total = np.zeros((n, NUM_DEV_DIMS), F32)
        gpu_total[is_gpu_node] = (100.0, gpu_memory_mib, 100.0)
        alloc[is_gpu_node, GPU_CORE] = i * 100.0
        alloc[is_gpu_node, GPU_MEMORY] = i * gpu_memory_mib
        inst = np.arange(i)
        gpu_numa = np.broadcast_to((inst * 2 // max(i, 1))[None, :],
                                   (n, i)).astype(np.int32).copy()
        gpu_pcie = np.broadcast_to((inst // 2)[None, :],
                                   (n, i)).astype(np.int32).copy()
        gpu_numa[~is_gpu_node] = -1
        gpu_pcie[~is_gpu_node] = -1
        devices = dict(
            gpu_total=gpu_total,
            gpu_free=np.broadcast_to(gpu_total[:, None, :],
                                     (n, i, NUM_DEV_DIMS)).copy(),
            gpu_valid=np.broadcast_to(is_gpu_node[:, None], (n, i)).copy(),
            gpu_numa=gpu_numa, gpu_pcie=gpu_pcie,
            aux_free=np.zeros((n, NUM_AUX_TYPES, 0), F32),
            aux_valid=np.zeros((n, NUM_AUX_TYPES, 0), bool))
    else:
        devices = _zeros_devices(n)
    return dict(nodes=nodes, quotas=quotas, gangs=gangs,
                reservations=reservations, devices=devices,
                version=np.int32(0))


def with_two_numa_zones(snap):
    """Every node gets two NUMA zones at half its cpu/memory each; the
    zone axis is compacted to 2 (reservation zone columns too)."""
    nodes, resv = snap["nodes"], snap["reservations"]
    alloc = nodes["allocatable"]
    n, z = alloc.shape[0], 2
    if resv["numa_valid"][:, z:].any():
        raise ValueError("reservation NUMA holds in zones >= 2")
    numa_cap = np.zeros((n, z, 2), F32)
    numa_cap[:, :, 0] = (alloc[:, CPU] / 2)[:, None]
    numa_cap[:, :, 1] = (alloc[:, MEM] / 2)[:, None]
    nodes.update(numa_cap=numa_cap, numa_free=numa_cap.copy(),
                 numa_valid=np.ones((n, z), bool))
    resv.update(numa_free=resv["numa_free"][:, :z],
                numa_valid=resv["numa_valid"][:, :z])
    return snap


def full_gate_reservations(num_nodes):
    return min(64, num_nodes // 2)


def full_gate_cluster(num_nodes, seed=0, num_quotas=32, max_quotas=64,
                      num_gangs=64, max_gangs=64, gpu_node_frac=0.25,
                      gpus_per_node=8, num_reservations=None):
    """The full-gate cluster: the colocation cluster plus two NUMA zones
    per node, GPU nodes with per-instance pools, live reservation slots
    and three taint classes (none / dedicated / gpu-exclusive)."""
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
    snap["nodes"]["taint_group"] = rng.choice(
        3, num_nodes, p=[0.8, 0.15, 0.05]).astype(np.int32)
    return snap


def synthetic_pods(num_pods, seed=1, prod_frac=0.6, num_quotas=0,
                   num_gangs=0, gang_min_member=8, gpu_pod_frac=0.0):
    """Plain LS/BE pods: prod pods request native cpu/memory, batch pods
    the batch tier."""
    rng = np.random.default_rng(seed)
    p = num_pods
    is_prod = rng.uniform(size=p) < prod_frac
    prio_class = np.where(is_prod, PRIO_PROD, PRIO_BATCH).astype(np.int8)
    priority = np.where(is_prod, 9000, 5000).astype(np.int32) + \
        rng.integers(0, 999, p).astype(np.int32)
    cpu_req = (rng.integers(1, 16, p) * 500).astype(F32)
    mem_req = (rng.integers(1, 32, p) * 512).astype(F32)
    requests = np.zeros((p, R), F32)
    requests[is_prod, CPU] = cpu_req[is_prod]
    requests[is_prod, MEM] = mem_req[is_prod]
    requests[~is_prod, BCPU] = cpu_req[~is_prod]
    requests[~is_prod, BMEM] = mem_req[~is_prod]
    limits = np.zeros((p, R), F32)
    gpu_ratio = np.zeros((p,), F32)
    if gpu_pod_frac > 0:
        is_gpu = rng.uniform(size=p) < gpu_pod_frac
        shape = rng.choice([50, 100, 200, 400], p,
                           p=[0.4, 0.3, 0.2, 0.1]).astype(F32)
        gpu_ratio = np.where(is_gpu, shape, 0.0).astype(F32)
        requests[:, GPU_CORE] = np.where(is_gpu, shape, 0.0)
    estimated = estimate_vectorized(requests, limits, prio_class)
    gang_id = np.full((p,), -1, np.int32)
    if num_gangs > 0:
        members = num_gangs * gang_min_member
        gang_id[:members] = np.repeat(np.arange(num_gangs, dtype=np.int32),
                                      gang_min_member)
    quota_id = np.full((p,), -1, np.int32)
    if num_quotas > 1:
        quota_id = rng.integers(1, num_quotas, p).astype(np.int32)
    return dict(
        requests=requests, estimated=estimated,
        qos=np.where(is_prod, QOS_LS, QOS_BE).astype(np.int8),
        priority_class=prio_class, priority=priority,
        gang_id=gang_id, quota_id=quota_id,
        selector_id=np.full((p,), -1, np.int32),
        selector_match=np.zeros((8, 64), bool),
        reservation_owner=np.full((p,), -1, np.int32),
        gpu_ratio=gpu_ratio, numa_single=np.zeros((p,), bool),
        daemonset=np.zeros((p,), bool),
        toleration_id=np.zeros((p,), np.int32),
        tol_forbid=np.zeros((1, 1), bool),
        tol_prefer=np.zeros((1, 1), F32),
        spread_id=np.full((p,), -1, np.int32),
        spread_carrier=np.zeros((p, 1), bool),
        spread_member=np.zeros((p, 1), bool),
        spread_max_skew=np.ones((1,), F32),
        spread_domain=np.full((1, 1), -1, np.int32),
        spread_count0=np.zeros((1, 1), F32),
        spread_dvalid=np.zeros((1, 1), bool),
        anti_id=np.full((p,), -1, np.int32),
        anti_member=np.zeros((p, 1), bool),
        anti_carrier=np.zeros((p, 1), bool),
        anti_domain=np.full((1, 1), -1, np.int32),
        anti_count0=np.zeros((1, 1), F32),
        anti_carrier_count0=np.zeros((1, 1), F32),
        aff_id=np.full((p,), -1, np.int32),
        aff_carrier=np.zeros((p, 1), bool),
        aff_member=np.zeros((p, 1), bool),
        aff_domain=np.full((1, 1), -1, np.int32),
        aff_count0=np.zeros((1, 1), F32),
        valid=np.ones((p,), bool),
        has_taints=False, has_spread=False, has_anti=False, has_aff=False)


def has_device_request(requests, gpu_ratio):
    return ((requests[:, GPU_CORE] > 0) | (requests[:, GPU_MEMORY] > 0)
            | (gpu_ratio > 0) | (requests[:, RDMA] > 0)
            | (requests[:, FPGA] > 0))


def full_gate_pods(num_pods, num_nodes, seed=1, num_quotas=32, num_gangs=64,
                   gang_min_member=8, num_zones=16, gpu_pod_frac=0.1,
                   numa_bind_frac=0.33, n_spread_groups=8, spread_frac=0.15,
                   max_skew=64.0, n_anti_groups=16, anti_members=64,
                   n_aff_groups=8, aff_members=48, num_reservations=None):
    """Full-gate pods: quota and gang pods plus CPU-bind (NUMA) prod
    pods, GPU pods, three toleration sets, zone+hostname spread pairs,
    hostname anti-affinity groups, paired zone affinity groups and
    reservation owners."""
    pods = synthetic_pods(num_pods, seed=seed, num_quotas=num_quotas,
                          num_gangs=num_gangs,
                          gang_min_member=gang_min_member,
                          gpu_pod_frac=gpu_pod_frac)
    rng = np.random.default_rng(seed + 29)
    p = num_pods
    is_prod = pods["priority_class"] == PRIO_PROD
    numa_single = is_prod & (rng.uniform(size=p) < numa_bind_frac)
    toleration_id = rng.choice(3, p, p=[0.7, 0.2, 0.1]).astype(np.int32)
    tol_forbid = np.array([[False, True, True],
                           [False, False, True],
                           [False, False, False]])
    tol_prefer = np.array([[0.0, 1.0, 1.0],
                           [0.0, 0.0, 1.0],
                           [0.0, 0.0, 0.0]], F32)

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
    rows = np.flatnonzero(in_spread)
    spread_member[rows, sgrp[in_spread]] = True
    spread_member[rows, sgrp[in_spread] + n_spread_groups] = True
    spread_carrier = spread_member.copy()
    spread_count0 = np.zeros((n_sg_total, d_cap), F32)
    spread_dvalid = np.zeros((n_sg_total, d_cap), bool)
    spread_dvalid[:n_spread_groups, :num_zones] = True
    spread_dvalid[n_spread_groups:, :num_nodes] = True
    host_skew = max(float(np.ceil(p * spread_frac / n_spread_groups
                                  / max(num_nodes, 1))) + 3.0, 4.0)
    spread_max_skew = np.concatenate([
        np.full((n_spread_groups,), max_skew, F32),
        np.full((n_spread_groups,), host_skew, F32)])

    anti_members = max(min(anti_members, p // (4 * n_anti_groups)), 1)
    aff_members = max(min(aff_members, p // (4 * n_aff_groups)), 1)
    total_anti = n_anti_groups * anti_members
    total_aff = n_aff_groups * aff_members
    if total_anti + total_aff > p:
        raise ValueError(f"full_gate_pods needs at least "
                         f"{n_anti_groups + n_aff_groups} pods; got {p}")
    anti_domain = np.broadcast_to(host_of_node,
                                  (n_anti_groups, num_nodes)).copy()
    anti_id = np.full((p,), -1, np.int32)
    anti_member = np.zeros((p, n_anti_groups), bool)
    a_idx = rng.choice(p, total_anti, replace=False)
    a_grp = np.repeat(np.arange(n_anti_groups, dtype=np.int32),
                      anti_members)
    anti_id[a_idx] = a_grp
    anti_member[a_idx, a_grp] = True
    anti_carrier = anti_member.copy()

    aff_domain = np.broadcast_to(zone_of_node,
                                 (n_aff_groups, num_nodes)).copy()
    aff_id = np.full((p,), -1, np.int32)
    aff_member = np.zeros((p, n_aff_groups), bool)
    remaining = np.setdiff1d(np.arange(p), a_idx, assume_unique=False)
    f_idx = rng.choice(remaining, total_aff, replace=False)
    f_grp = np.repeat(np.arange(n_aff_groups, dtype=np.int32), aff_members)
    aff_id[f_idx] = f_grp
    aff_member[f_idx, f_grp] = True
    for g in range(1, n_aff_groups, 2):
        aff_member[f_idx[f_grp == g], g - 1] = True
    aff_carrier = aff_member.copy()

    v = full_gate_reservations(num_nodes) if num_reservations is None \
        else int(num_reservations)
    resv_owner = np.full((p,), -1, np.int32)
    if v:
        reqs = pods["requests"]
        slot_free = np.zeros((R,), F32)
        slot_free[CPU], slot_free[MEM] = RESV_SLOT_CPU, RESV_SLOT_MEM
        fits_slot = (reqs <= slot_free[None, :]).all(axis=1)
        plain = np.flatnonzero(
            fits_slot & ~has_device_request(reqs, pods["gpu_ratio"])
            & ~numa_single)
        owners = rng.choice(plain, min(2 * v, plain.size), replace=False)
        resv_owner[owners] = (np.arange(owners.size) % v).astype(np.int32)

    pods.update(
        numa_single=numa_single, reservation_owner=resv_owner,
        toleration_id=toleration_id, tol_forbid=tol_forbid,
        tol_prefer=tol_prefer,
        spread_id=spread_id, spread_carrier=spread_carrier,
        spread_member=spread_member, spread_max_skew=spread_max_skew,
        spread_domain=spread_domain, spread_count0=spread_count0,
        spread_dvalid=spread_dvalid,
        anti_id=anti_id, anti_member=anti_member, anti_carrier=anti_carrier,
        anti_domain=anti_domain,
        anti_count0=np.zeros((n_anti_groups, num_nodes), F32),
        anti_carrier_count0=np.zeros((n_anti_groups, num_nodes), F32),
        aff_id=aff_id, aff_carrier=aff_carrier, aff_member=aff_member,
        aff_domain=aff_domain, aff_count0=np.zeros((n_aff_groups, num_zones),
                                                   F32),
        has_taints=True, has_spread=True, has_anti=True, has_aff=True)
    return pods


CLUSTERS = {"synthetic_cluster": synthetic_cluster,
            "full_gate_cluster": full_gate_cluster}
PODS = {"synthetic_pods": synthetic_pods, "full_gate_pods": full_gate_pods}

# per-pod columns (axis 0 is the pod); everything else is batch-global
PER_POD_FIELDS = ("requests", "estimated", "qos", "priority_class",
                  "priority", "gang_id", "quota_id", "selector_id",
                  "reservation_owner", "gpu_ratio", "numa_single",
                  "daemonset", "toleration_id", "spread_id",
                  "spread_carrier", "spread_member", "anti_id",
                  "anti_member", "anti_carrier", "aff_id", "aff_carrier",
                  "aff_member", "valid")


def make_cluster(config, seed):
    """The seeded cluster a configuration file describes."""
    gen = config["cluster"]
    return CLUSTERS[gen["generator"]](seed=seed, **gen["params"])


def make_backlog(traffic, cluster_params, seed):
    """The seeded backlog a traffic file describes. Cluster facts the
    pod generator needs (nodes, quotas, gangs) come from the
    configuration, so one mix can run against several clusters."""
    gen = traffic["pods"]
    kw = dict(gen["params"])
    fn = PODS[gen["generator"]]
    for key in gen.get("from_cluster", ()):
        kw[key] = cluster_params[key]
    return fn(traffic["backlog"], seed=seed, **kw)
