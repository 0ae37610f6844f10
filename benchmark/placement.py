"""The placement reference: an independent Filter and Score of the
plugins a configuration enables, numpy only. It imports nothing of the
program and reads nothing the program made except the bindings it
returned.

For one batch the service scheduled, against the committed state
before it (rebuilt by the reference from the seeded cluster and the
earlier bindings), it reads for each checked pod the gap by which the
score of the node it was bound to lies below the best score of a node
that admitted it and that the batch left untouched:

    gap(p) = max over n in C(p) of S(p, n) - S(p, chosen)

with S the reference's score at the state before the batch and C(p)
the nodes that pass every filter for p at that state and that received
no pod of the batch. Why a sound program reads no gap: the program
picks, round by round, a node of highest score among those that admit
the pod at the round's start. An untouched node keeps its state and its
score through every round, so it was open to p at a score of S(p, n).
The chosen node's score at that round is at most S(p, chosen), since
LoadAware only falls as a node fills and the NUMA, DeviceShare and
taint terms are fixed at the batch's start (scheduler/core.py computes
them once per batch). A gap therefore means the program ranked nodes
otherwise than the plugins' scores: a term dropped or coarsened, scores
or capacities computed in lower precision, another order of choice.

Checked pods are those the score of the configuration decides alone:
bound to a node (not a reservation slot), no topology-spread,
anti-affinity or affinity term or membership (their spread score and
in-batch domain gates depend on the other pods of the batch), no
reservation owner, no pod that is both CPU-bind and GPU (its zone and
instance choice are joint), no aux-device request.

The control (`Scorer.score(..., q=bf16)`) is this same reference
computed in bfloat16, put in the program's place: at each checked pod
it takes the node that the bfloat16 scores put first among C(p) and
the chosen node, and reads that node's gap by the float64 scores.
"""

import numpy as np

EPS = 0.5                    # the program's stated comparison tolerance
MAX_NODE_SCORE = 100.0
CPU, MEM = 0, 1
GPU_CORE, GPU_MEMORY, RDMA, FPGA = 6, 7, 9, 10
DEV_MEM = 1
CHUNK = 128                  # pods per block: bounds the [P, N, R] blocks
POD_KEYS = ("requests", "estimated", "selector_id", "toleration_id",
            "gang_id",
            "numa_single", "gpu_ratio", "reservation_owner",
            "spread_member", "spread_carrier", "anti_member",
            "anti_carrier", "aff_member", "aff_carrier")


def exact(x):
    return np.asarray(x, np.float64)


def bf16(x):
    """Round to bfloat16 (nearest even), returned as float64."""
    f = np.asarray(x, np.float32)
    bits = f.view(np.uint32).astype(np.uint64)
    rounded = ((bits + 0x7FFF + ((bits >> 16) & 1)) >> 16) << 16
    return rounded.astype(np.uint32).view(np.float32).astype(np.float64)


def gpu_per_instance(requests, gpu_ratio, total_mem):
    """(count, per-instance [..., 3]) of GPU pods against per-GPU memory
    `total_mem` (broadcast): a ratio over 100 that divides by 100 is that
    many whole GPUs, split evenly; integer floors (Koordinator's
    devicehandler_gpu.go). Pods without a GPU request get count 0."""
    core = exact(requests[..., GPU_CORE])
    mem = exact(requests[..., GPU_MEMORY])
    ratio_in = exact(gpu_ratio)
    total = exact(total_mem)
    spec = mem > 0
    ratio = np.where(spec, np.floor(mem / np.maximum(total, 1.0) * 100.0),
                     ratio_in)
    mem_eff = np.where(spec, mem, np.floor(ratio_in * total / 100.0))
    multi = (ratio > 100.0) & (np.mod(ratio, 100.0) == 0.0)
    count = np.where(multi, ratio / 100.0, 1.0)
    per = np.stack([np.floor(core / count), np.floor(mem_eff / count),
                    np.floor(ratio / count)], axis=-1)
    gpu = (core > 0) | (mem > 0) | (ratio_in > 0)
    count = np.where(gpu, count, 0.0)
    return count, per * gpu[..., None]


def usage_ok(nodes, thresholds):
    """The LoadAware filter on the seeded NodeMetrics: a node whose
    rounded usage percent reaches a threshold takes no pod; nodes
    without a fresh metric pass."""
    alloc = exact(nodes["allocatable"])
    used = exact(nodes["usage"])
    pct = np.where(alloc > 0,
                   np.floor(used / np.maximum(alloc, 1e-9) * 100 + 0.5), 0)
    over = (thresholds[None, :] > 0) & (alloc > 0) \
        & (pct >= thresholds[None, :])
    return ~over.any(axis=1) | ~nodes["metric_fresh"]


class Scorer:
    """Filter and Score of one configuration against one node state. The
    configuration file names the plugins the service enables
    (`service.schedule_kwargs`), LoadAware's filter thresholds
    (`guarantees.usage_thresholds`) and the score's weights and
    strategies (`guarantees.placement`)."""

    def __init__(self, cluster, backlog, state, config):
        nodes, devs = cluster["nodes"], cluster["devices"]
        score_cfg = config["guarantees"]["placement"]
        plugins = config["service"]["schedule_kwargs"]
        enable_numa = bool(plugins["enable_numa"])
        enable_devices = bool(plugins["enable_devices"])
        self.nodes, self.backlog = nodes, backlog
        self.alloc = exact(nodes["allocatable"])
        self.requested = state["requested"]
        self.weights = exact(score_cfg["loadaware_weights"])
        self.wdims = np.flatnonzero(self.weights > 0)
        usage = exact(nodes["usage"])
        corr = exact(nodes["assigned_correction"])
        # node_term = assigned estimate + usage less the correction
        # where the usage covers it (load_aware.go:303-309)
        self.node_term = state["assigned_estimated"] + \
            (usage - np.where(usage >= corr, corr, 0.0))
        self.fresh = np.asarray(nodes["metric_fresh"], bool)
        self.static = np.asarray(nodes["schedulable"], bool) & usage_ok(
            nodes, exact(config["guarantees"]["usage_thresholds"]))
        # a node past its capacity in a dim no pod asks for still fails
        self.over = self.requested > self.alloc + EPS             # [N, R]
        self.enable_numa = enable_numa
        if enable_numa:
            self.numa_cap = exact(nodes["numa_cap"])
            self.numa_free = state["numa_free"]
            self.numa_valid = np.asarray(nodes["numa_valid"], bool)
            self.policy_free = np.asarray(nodes["numa_policy"]) == 0
        self.n_inst = devs["gpu_free"].shape[1] if enable_devices else 0
        self.enable_devices = enable_devices
        if self.n_inst:
            self.gpu_total = exact(devs["gpu_total"])
            self.gpu_free = state["gpu_free"]
            self.gpu_valid = np.asarray(devs["gpu_valid"], bool)
        self.has_taints = bool(backlog["has_taints"])
        if self.has_taints:
            self.taint_group = np.asarray(nodes["taint_group"])
            self.tol_forbid = np.asarray(backlog["tol_forbid"], bool)
            prefer = exact(backlog["tol_prefer"])
            self.tol_penalty = prefer / max(prefer.max(), 1.0) \
                * MAX_NODE_SCORE
        self.numa_strategy = score_cfg["numa_strategy"]
        self.device_strategy = score_cfg["device_strategy"]

    # --- Filter ---------------------------------------------------------
    def feasible(self, pod, idx):
        """bool[len(idx), N]: the nodes that admit each pod at this
        state (every node gate; quota and gang quorum do not depend on
        the node)."""
        req = exact(pod["requests"][idx])                         # [B, R]
        ok = np.broadcast_to(self.static, (idx.size, self.static.size))
        sel = pod["selector_id"][idx]
        match = np.asarray(self.backlog["selector_match"], bool)
        lg = np.asarray(self.nodes["label_group"])
        ok = ok & ((sel[:, None] < 0)
                   | match[np.maximum(sel, 0)][:, lg])
        if self.has_taints:
            ok &= ~self.tol_forbid[np.maximum(pod["toleration_id"][idx], 0)
                                   ][:, self.taint_group]
        asked = np.flatnonzero((req > 0).any(axis=0))
        idle = np.setdiff1d(np.arange(req.shape[1]), asked)
        ok &= ~self.over[:, idle].any(axis=1)[None, :]
        for r in asked:
            ok &= req[:, r, None] + self.requested[None, :, r] \
                <= self.alloc[None, :, r] + EPS
        if self.enable_numa:
            single = pod["numa_single"][idx]
            if single.any():
                req2 = req[:, [CPU, MEM]]
                fits = np.all(self.numa_free[None] + EPS
                              >= req2[:, None, None, :], axis=-1)
                fits &= self.numa_valid[None]
                ok &= ~single[:, None] | fits.any(axis=-1)
            ok &= self.policy_free[None, :]
        if self.enable_devices:
            # a GPU pod needs `count` valid instances that each hold its
            # per-instance share; a node without GPUs holds none
            rows = np.flatnonzero((req[:, GPU_CORE] > 0)
                                  | (req[:, GPU_MEMORY] > 0)
                                  | (pod["gpu_ratio"][idx] > 0))
            if rows.size:
                ok = ok.copy()
                if self.n_inst:
                    count, per = gpu_per_instance(
                        req[rows][:, None, :],
                        pod["gpu_ratio"][idx][rows][:, None],
                        self.gpu_total[None, :, DEV_MEM])
                    fits = np.all(self.gpu_free[None] + EPS
                                  >= per[:, :, None, :], axis=-1)
                    n_fit = (fits & self.gpu_valid[None]).sum(axis=-1)
                    ok[rows] &= n_fit >= count
                else:
                    ok[rows] = False
        return ok

    # --- Score ----------------------------------------------------------
    def score(self, pod, idx, q=exact):
        """f64[len(idx), N]: the summed plugin scores, each operation
        rounded by `q` (identity, or bfloat16 for the control)."""
        d = self.wdims
        cap = q(self.alloc[:, d])[None]                           # [1,N,D]
        used = q(q(exact(pod["estimated"][idx][:, d])[:, None, :])
                 + q(self.node_term[:, d])[None])
        least = np.floor(q(q(q(cap - used) * MAX_NODE_SCORE)
                           / np.maximum(cap, 1e-9)))
        least = np.where((cap > 0) & (used <= cap), least, 0.0)
        w = q(self.weights[d])
        total = np.floor(q(q((least * w).sum(axis=-1)) / q(w.sum())))
        total = np.where(self.fresh[None], total, 0.0)
        if self.enable_numa:
            total = total + self._numa(pod, idx, q)
        if self.n_inst:
            total = total + self._device(pod, idx, q)
        if self.has_taints:
            pen = self.tol_penalty[np.maximum(pod["toleration_id"][idx],
                                              0)][:, self.taint_group]
            total = np.maximum(total - pen, 0.0)
        return total

    def _numa(self, pod, idx, q):
        """The zone score of a CPU-bind pod: the zone it would take, most
        (or least) allocated over cpu and memory (scoring.go)."""
        single = pod["numa_single"][idx]
        out = np.zeros((idx.size, self.alloc.shape[0]))
        if not single.any():
            return out
        rows = np.flatnonzero(single)
        req2 = q(exact(pod["requests"][idx[rows]][:, [CPU, MEM]]))
        cap = q(self.numa_cap)[None]
        free = q(self.numa_free)[None]
        fits = np.all(free + EPS >= req2[:, None, None, :], axis=-1) \
            & self.numa_valid[None]
        frac = q(q(q(cap - free) + req2[:, None, None, :])
                 / np.maximum(cap, 1e-9))
        if self.numa_strategy != "most":
            frac = q(1.0 - frac)
        zone = q(frac.sum(axis=-1) / 2.0)
        best = np.where(fits, zone, -1.0).max(axis=-1)
        out[rows] = q(np.clip(best, 0.0, 1.0) * MAX_NODE_SCORE)
        return out

    def _device(self, pod, idx, q):
        """The GPU pool score of a GPU pod after its allocation, least
        (or most) allocated over the dims it asks for (scoring.go)."""
        req = pod["requests"][idx]
        out = np.zeros((idx.size, self.alloc.shape[0]))
        rows = np.flatnonzero((req[:, GPU_CORE] > 0)
                              | (req[:, GPU_MEMORY] > 0)
                              | (pod["gpu_ratio"][idx] > 0))
        if not rows.size:
            return out
        count, per = gpu_per_instance(req[rows][:, None, :],
                                      pod["gpu_ratio"][idx][rows][:, None],
                                      self.gpu_total[None, :, DEV_MEM])
        valid_n = self.gpu_valid.sum(axis=-1)
        pool_total = q(self.gpu_total * valid_n[:, None])[None]   # [1,N,3]
        pool_free = q((self.gpu_free * self.gpu_valid[..., None])
                      .sum(axis=1))[None]
        alloc = q(per * count[..., None])
        frac = q(q(q(pool_total - pool_free) + alloc)
                 / np.maximum(pool_total, 1e-9))
        if self.device_strategy != "most":
            frac = q(1.0 - frac)
        w = (per > 0).astype(np.float64)
        s = q((frac * w).sum(axis=-1) / np.maximum(w.sum(axis=-1), 1.0))
        out[rows] = q(np.clip(s, 0.0, 1.0) * MAX_NODE_SCORE)
        return out


def checked_pods(pod, assignment, res_slot, n):
    """The placed pods whose node the configuration's score decides
    alone (see the module's docstring)."""
    placed = (assignment >= 0) & (assignment < n) & (res_slot < 0)
    req = pod["requests"]
    gpu = (req[:, GPU_CORE] > 0) | (req[:, GPU_MEMORY] > 0) \
        | (pod["gpu_ratio"] > 0)
    topo = np.zeros(placed.shape, bool)
    for key in ("spread_member", "spread_carrier", "anti_member",
                "anti_carrier", "aff_member", "aff_carrier"):
        topo |= np.asarray(pod[key], bool).any(axis=1)
    return placed & ~topo & (pod["reservation_owner"] < 0) \
        & ~(gpu & pod["numa_single"]) & (req[:, RDMA] <= 0) \
        & (req[:, FPGA] <= 0)


def batch_gaps(cluster, backlog, state, rows, assignment, res_slot, config,
               control=False):
    """Per checked pod of one batch: the gap of the program's choice and
    (with `control`) of the bfloat16 reference's choice, both by the
    float64 scores. `state` is the reference's rebuild of the committed
    columns before the batch."""
    pod = {k: np.asarray(backlog[k])[rows] for k in POD_KEYS}
    n = cluster["nodes"]["allocatable"].shape[0]
    a = np.asarray(assignment, np.int64)
    scorer = Scorer(cluster, backlog, state, config)
    touched = np.zeros(n, bool)
    placed = (a >= 0) & (a < n)
    touched[a[placed]] = True
    check = np.flatnonzero(checked_pods(pod, a, np.asarray(res_slot), n))
    if ((pod["gang_id"] >= 0) & (a < 0)).any():
        # a gang member left unbound: its gang was placed for some
        # rounds and rolled back, on nodes the answers do not show
        check = check[:0]
    gaps, ctrl = [], []
    for s in range(0, check.size, CHUNK):
        idx = check[s:s + CHUNK]
        cand = scorer.feasible(pod, idx) & ~touched[None, :]
        score = scorer.score(pod, idx)
        best = np.where(cand, score, -np.inf).max(axis=1)
        have = np.isfinite(best)
        chosen = a[idx]
        got = score[np.arange(idx.size), chosen]
        gaps.append(np.where(have, best - got, 0.0))
        if control:
            low = scorer.score(pod, idx, q=bf16)
            pool = cand.copy()
            pool[np.arange(idx.size), chosen] = True
            pick = np.argmax(np.where(pool, low, -np.inf), axis=1)
            got_c = score[np.arange(idx.size), pick]
            ctrl.append(np.where(have, best - got_c, 0.0))
    cat = lambda x: np.concatenate(x) if x else np.zeros(0)  # noqa: E731
    return cat(gaps), cat(ctrl)
