"""The plain reference: what a drain's bindings must add up to, and the
guarantees each binding must keep. numpy only; it imports nothing of
the program and takes nothing the program made except its answers (the
bindings `schedule()` returned) and the committed state it published.

For one drain it rebuilds, from the seeded cluster, the seeded backlog
and the returned bindings alone, every column the commit writes: node
`requested`, LoadAware `assigned_estimated` (all and prod), ElasticQuota
`used` at every level, gang `assumed`, NUMA zone and GPU instance free
capacity, and reservation slots. It then reads:

- `state_gap`: the worst gap, column by column, between the committed
  snapshot and that rebuild, as a share of the column's largest
  reference value (a pod charged twice, charged nowhere, or charged on
  another node than the one returned all show here);
- `violations`: bindings that break a guarantee or a gate of the
  configuration (by kind in `kinds`);
- `unplaced` of `attempted`: pods the service left unbound.
"""

import numpy as np

CPU, MEM = 0, 1
GPU_CORE, GPU_MEMORY = 6, 7
DEV_MEM = 1
PRIO_PROD = 4
POD_KEYS = ("requests", "estimated", "priority_class", "quota_id",
            "gang_id", "numa_single", "gpu_ratio", "reservation_owner",
            "toleration_id", "selector_id", "daemonset", "valid",
            "spread_member", "spread_carrier", "anti_member",
            "anti_carrier", "aff_member", "aff_carrier")


def _gpu_per_instance(requests, gpu_ratio, total_mem):
    """(count, per-instance [P, 3]) of each GPU pod at its node: a
    ratio over 100 that divides by 100 is that many whole GPUs, split
    evenly (Koordinator's devicehandler_gpu.go), integer floors."""
    core = requests[:, GPU_CORE].astype(np.float64)
    mem = requests[:, GPU_MEMORY].astype(np.float64)
    total = np.maximum(total_mem.astype(np.float64), 1.0)
    spec = mem > 0
    ratio = np.where(spec, np.floor(mem / total * 100.0), gpu_ratio)
    mem_eff = np.where(spec, mem, np.floor(gpu_ratio * total / 100.0))
    multi = (ratio > 100.0) & (np.mod(ratio, 100.0) == 0.0)
    count = np.where(multi, ratio / 100.0, 1.0)
    per = np.stack([np.floor(core / count), np.floor(mem_eff / count),
                    np.floor(ratio / count)], axis=-1)
    return count.astype(np.int64), per


def _usage_ok(nodes, thresholds):
    """The LoadAware filter on the seeded NodeMetrics: a node whose
    rounded usage percent reaches a threshold takes no pod."""
    alloc = nodes["allocatable"].astype(np.float64)
    used = nodes["usage"].astype(np.float64)
    pct = np.where(alloc > 0,
                   np.floor(used / np.maximum(alloc, 1e-9) * 100 + 0.5), 0)
    over = (thresholds[None, :] > 0) & (alloc > 0) \
        & (pct >= thresholds[None, :])
    return ~over.any(axis=1) | ~nodes["metric_fresh"]


def _gap(got, want):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if want.size == 0:
        return 0.0
    scale = max(float(np.abs(want).max()), 1.0)
    return float(np.abs(got - want).max()) / scale


def node_columns(cluster, pod, a, slot, numa_take, gpu_take):
    """The node columns the commit writes, rebuilt from the seeded
    cluster and the bindings alone: node `requested` (a pod bound to a
    reservation slot draws from the slot's hold instead), LoadAware
    `assigned_estimated` (all and prod), NUMA zone and GPU instance free
    capacity, unclamped. Returns (columns, GPU count of each pod)."""
    nodes, devs = cluster["nodes"], cluster["devices"]
    n = nodes["allocatable"].shape[0]
    placed = (a >= 0) & (a < n) & np.asarray(pod["valid"], bool)
    at = np.where(placed, a, 0)
    on_node = placed & (slot < 0)
    req = pod["requests"].astype(np.float64)
    est = pod["estimated"].astype(np.float64)
    cols = {}
    requested = nodes["requested"].astype(np.float64).copy()
    np.add.at(requested, at[on_node], req[on_node])
    cols["requested"] = requested
    ae = nodes["assigned_estimated"].astype(np.float64).copy()
    np.add.at(ae, at[placed], est[placed])
    cols["assigned_estimated"] = ae
    prod = placed & (pod["priority_class"] == PRIO_PROD)
    pae = nodes["prod_assigned_estimated"].astype(np.float64).copy()
    np.add.at(pae, at[prod], est[prod])
    cols["prod_assigned_estimated"] = pae
    numa_free = nodes["numa_free"].astype(np.float64).copy()
    np.add.at(numa_free, at[on_node], -numa_take[on_node])
    cols["numa_free"] = numa_free
    gpu_free = devs["gpu_free"].astype(np.float64).copy()
    g_count = np.zeros(a.shape, np.int64)
    if gpu_free.shape[1]:
        g_count, g_per = _gpu_per_instance(
            pod["requests"], pod["gpu_ratio"].astype(np.float64),
            devs["gpu_total"][at, DEV_MEM])
        upd = gpu_take[:, :, None] * g_per[:, None, :]
        np.add.at(gpu_free, at[on_node], -upd[on_node])
    cols["gpu_free"] = gpu_free
    return cols, g_count


def state_before(cluster, backlog, batch, parts):
    """The node columns after the batches `parts` (index, assignment,
    numa_take, gpu_take, res_slot) of one drain, as `node_columns`."""
    if not parts:
        return node_columns(cluster, _pods(backlog, np.zeros(0, int)),
                            np.zeros(0, np.int64), np.zeros(0, np.int64),
                            np.zeros((0,) + cluster["nodes"]["numa_free"]
                                     .shape[1:]),
                            np.zeros((0, cluster["devices"]["gpu_free"]
                                      .shape[1]), bool))[0]
    rows = np.concatenate([np.arange(p[0] * batch, (p[0] + 1) * batch)
                           for p in parts])
    cat = lambda j: np.concatenate([p[j] for p in parts])  # noqa: E731
    return node_columns(cluster, _pods(backlog, rows),
                        cat(1).astype(np.int64), cat(4).astype(np.int64),
                        cat(2).astype(np.float64), cat(3).astype(bool))[0]


def _pods(backlog, rows):
    return {k: np.asarray(backlog[k])[rows] for k in POD_KEYS}


def check_drain(cluster, backlog, rows, answers, committed, guarantees):
    """Check one drain. `rows` are the backlog rows attempted in it,
    `answers` the bindings returned for them (assignment, numa_take,
    gpu_take, res_slot), `committed` the snapshot columns the service
    published after the drain's last cycle. Returns
    (gaps by column, violations by kind, unplaced, attempted)."""
    eps = float(guarantees["capacity_tolerance"])
    nodes, quotas, gangs = (cluster["nodes"], cluster["quotas"],
                            cluster["gangs"])
    resv, devs = cluster["reservations"], cluster["devices"]
    n = nodes["allocatable"].shape[0]
    pod = _pods(backlog, rows)
    a = answers["assignment"].astype(np.int64)
    slot = answers["res_slot"].astype(np.int64)
    numa_take = answers["numa_take"].astype(np.float64)
    gpu_take = answers["gpu_take"].astype(bool)
    attempted = pod["valid"]
    placed = (a >= 0) & attempted
    bad = {}

    def count(kind, mask):
        bad[kind] = bad.get(kind, 0) + int(np.count_nonzero(mask))

    count("bound_to_no_node", placed & (a >= n))
    placed &= a < n
    at = np.where(placed, a, 0)
    on_slot = placed & (slot >= 0)
    on_node = placed & ~on_slot
    req = pod["requests"].astype(np.float64)

    # --- rebuild the committed columns from the bindings alone ---
    cols, g_count = node_columns(cluster, pod, a, slot, numa_take, gpu_take)
    requested, numa_free, gpu_free = (cols["requested"], cols["numa_free"],
                                      cols["gpu_free"])
    want = {k: cols[k] for k in ("requested", "assigned_estimated",
                                 "prod_assigned_estimated")}
    want["numa_free"] = np.maximum(numa_free, 0.0)
    want["gpu_free"] = np.maximum(gpu_free, 0.0)
    n_inst = gpu_free.shape[1]
    used = quotas["used"].astype(np.float64).copy()
    qid = pod["quota_id"]
    for d in range(quotas["depth_ancestor"].shape[1]):
        anc = np.where(qid >= 0, quotas["depth_ancestor"][np.maximum(qid, 0),
                                                          d], -1)
        m = placed & (anc >= 0)
        np.add.at(used, anc[m], req[m])
    want["quota_used"] = used
    gid = pod["gang_id"]
    in_gang = placed & (gid >= 0)
    assumed = gangs["assumed"].astype(np.int64).copy()
    np.add.at(assumed, gid[in_gang], 1)
    want["gang_assumed"] = assumed
    v = resv["free"].shape[0]
    consumed = np.zeros((v, req.shape[1]), np.float64)
    took = np.zeros((v,), np.int64)
    if v:
        s = np.where(on_slot, slot, 0)
        np.add.at(consumed, s[on_slot], req[on_slot])
        np.add.at(took, s[on_slot], 1)
    r_free = resv["free"].astype(np.float64) - consumed
    want["reservation_free"] = np.maximum(r_free, 0.0)
    want["reservation_valid"] = resv["valid"] & ~(resv["allocate_once"]
                                                  & (took > 0))

    gaps = {}
    for key, ref in want.items():
        got = committed[key]
        if ref.dtype == bool:
            gaps[key] = float(np.count_nonzero(got != ref) > 0)
        else:
            valid = quotas["valid"] if key == "quota_used" else None
            gaps[key] = _gap(got[valid] if valid is not None else got,
                             ref[valid] if valid is not None else ref)

    # --- guarantees ---
    count("node_overcommitted",
          (requested > nodes["allocatable"] + eps).any(axis=1))
    count("quota_used_over_runtime",
          quotas["valid"] & (used > quotas["runtime"] + eps).any(axis=1))
    # strict gangs: all-or-nothing once no member is left to attempt
    seen = np.zeros_like(assumed)
    np.add.at(seen, gid[attempted & (gid >= 0)], 1)
    outstanding = gangs["member_count"] - gangs["assumed"] - seen
    total = assumed
    count("gang_partial", gangs["valid"] & gangs["strict"]
          & ~gangs["satisfied"] & (outstanding <= 0)
          & (total > gangs["assumed"]) & (total < gangs["min_member"]))
    count("reservation_overdrawn", (r_free < -eps).any(axis=1))
    count("allocate_once_reused", resv["allocate_once"] & (took > 1))

    # --- gates: every binding is one the filters admit ---
    count("node_unschedulable", placed & ~nodes["schedulable"][at])
    sel = pod["selector_id"]
    sel_ok = (sel < 0) | np.asarray(backlog["selector_match"], bool)[
        np.maximum(sel, 0), nodes["label_group"][at]]
    count("selector_mismatch", placed & ~sel_ok)
    la_ok = _usage_ok(nodes, np.asarray(guarantees["usage_thresholds"],
                                        np.float64))
    count("loadaware_filtered", placed & ~la_ok[at] & ~pod["daemonset"])
    if backlog["has_taints"]:
        forbid = backlog["tol_forbid"][np.maximum(pod["toleration_id"], 0),
                                       nodes["taint_group"][at]]
        count("taint_not_tolerated", placed & forbid)
    # NUMA: a CPU-bind pod takes its cpu and memory from one valid zone;
    # no other pod takes zone capacity; no zone goes below zero
    numa_pod = on_node & pod["numa_single"]
    taken = (numa_take != 0).any(axis=2)                      # [P, Z]
    zones = taken.sum(axis=1)
    z = np.argmax(taken, axis=1)
    zone_ok = nodes["numa_valid"][at, np.minimum(
        z, nodes["numa_valid"].shape[1] - 1)]
    amount_ok = (numa_take[np.arange(len(a)), z, 0] == req[:, CPU]) \
        & (numa_take[np.arange(len(a)), z, 1] == req[:, MEM])
    count("numa_take_wrong", numa_pod & ~((zones == 1) & zone_ok
                                         & amount_ok))
    count("numa_take_unbound_pod", placed & ~numa_pod & taken.any(axis=1))
    count("numa_zone_over", (numa_free < -eps).any(axis=2))
    # GPU: a GPU pod takes `count` valid instances; no other pod takes one
    if n_inst:
        gpu_pod = on_node & (g_count > 0) & (
            (pod["requests"][:, GPU_CORE] > 0)
            | (pod["requests"][:, GPU_MEMORY] > 0) | (pod["gpu_ratio"] > 0))
        n_taken = gpu_take.sum(axis=1)
        inst_ok = ~(gpu_take & ~devs["gpu_valid"][at]).any(axis=1)
        count("gpu_take_wrong", gpu_pod & ~((n_taken == g_count) & inst_ok))
        count("gpu_take_unbound_pod",
              placed & ~gpu_pod & gpu_take.any(axis=1))
        count("gpu_instance_over", (gpu_free < -eps).any(axis=2))
    # reservations: only the slot's owners consume it, on its node
    if v:
        s = np.where(on_slot, slot, 0)
        count("reservation_misuse", on_slot & (
            (slot >= v) | (resv["owner_group"][np.minimum(s, v - 1)]
                           != pod["reservation_owner"])
            | (resv["node"][np.minimum(s, v - 1)] != a)
            | ~resv["valid"][np.minimum(s, v - 1)]))
    if backlog["has_spread"]:
        count("spread_skew_over", _spread_over(backlog, pod, placed, at))
    if backlog["has_anti"]:
        count("anti_affinity_colocated", _anti_colocated(backlog, pod,
                                                         placed, at))
    if backlog["has_aff"]:
        count("affinity_split", _affinity_split(backlog, pod, placed, at))
    unplaced = int(np.count_nonzero(attempted & ~placed))
    return gaps, bad, unplaced, int(np.count_nonzero(attempted))


def _domain_counts(domain_row, nodes_of, weights, n_dom):
    d = domain_row[nodes_of]
    m = weights & (d >= 0)
    return np.bincount(d[m], minlength=n_dom)[:n_dom]


def _spread_over(backlog, pod, placed, at):
    """Per topology-spread group: a domain holding a placed carrier may
    not exceed the least-filled eligible domain by more than maxSkew
    (counts only grow within a drain, so the end state bounds every
    placement)."""
    dom = backlog["spread_domain"]
    dvalid = backlog["spread_dvalid"]
    skew = backlog["spread_max_skew"]
    over = np.zeros(len(at), bool)
    for g in range(dom.shape[0]):
        n_dom = dvalid.shape[1]
        cnt = backlog["spread_count0"][g].astype(np.float64) + \
            _domain_counts(dom[g], at, placed & pod["spread_member"][:, g],
                           n_dom)
        eligible = dvalid[g]
        low = cnt[eligible].min() if eligible.any() else 0.0
        carrier = placed & pod["spread_carrier"][:, g]
        d = dom[g][at]
        over |= carrier & (d >= 0) & (cnt[np.maximum(d, 0)] - low > skew[g])
    return over


def _anti_colocated(backlog, pod, placed, at):
    """Required anti-affinity: a placed carrier shares its domain with
    no other placed member, and a placed member with no other carrier."""
    dom = backlog["anti_domain"]
    bad = np.zeros(len(at), bool)
    for g in range(dom.shape[0]):
        n_dom = int(dom[g].max()) + 1
        if n_dom <= 0:
            continue
        mem = placed & pod["anti_member"][:, g]
        car = placed & pod["anti_carrier"][:, g]
        m_cnt = _domain_counts(dom[g], at, mem, n_dom)
        c_cnt = _domain_counts(dom[g], at, car, n_dom)
        m_cnt = m_cnt + backlog["anti_count0"][g][:n_dom]
        c_cnt = c_cnt + backlog["anti_carrier_count0"][g][:n_dom]
        keyed = dom[g][at] >= 0
        d = np.maximum(dom[g][at], 0)
        bad |= keyed & car & (m_cnt[d] - mem > 0)
        bad |= keyed & mem & (c_cnt[d] - car > 0)
    return bad


def _affinity_split(backlog, pod, placed, at):
    """Required affinity where every member carries the term: the first
    placement opens a domain and every later one must join a domain
    that holds a member, so a group's placed pods share one domain.
    Pods outside the group's most-filled domain are violations."""
    dom = backlog["aff_domain"]
    bad = np.zeros(len(at), bool)
    for g in range(dom.shape[0]):
        grp = placed & (pod["aff_member"][:, g] | pod["aff_carrier"][:, g])
        if not grp.any():
            continue
        d = dom[g][at]
        counts = np.bincount(d[grp & (d >= 0)], minlength=1)
        if backlog["aff_count0"][g].any():
            continue  # pre-existing members: not this mix
        bad |= grp & (d != int(np.argmax(counts)))
    return bad
