"""Device-resident LowNodeLoad plan (BASELINE config 5).

The host plugin (lownodeload.py) walks source nodes and their pods
sequentially — the faithful mirror of evictPodsFromSourceNodes
(/root/reference/pkg/descheduler/framework/plugins/loadaware/
low_node_load.go:232-305). That greedy is in fact PREFIX-STRUCTURED, so
the whole plan vectorizes with no per-pod loop at all:

- Within one source node, pods are evicted in sorted order while the
  node is still over its high threshold. Usage only decreases as pods
  leave, so "still over" is monotone: the evicted set is a PREFIX of
  the node's sorted removable pods — computable for every node at once
  with a segment exclusive-cumsum.
- Across nodes, the shared destination budget only decreases, and the
  reference stops as soon as any dimension is exhausted — so "budget
  still open" is ALSO monotone along the global eviction order: one
  exclusive cumsum over the would-be-evicted pods. Same for the
  per-cycle eviction cap.
- A pod is planned iff (node prefix holds) AND (budget prefix holds):
  two cumsums and a gather replace the reference's nested loop. This is
  the TPU-native shape of the "batched ILP relax" BASELINE.json names:
  the LP's greedy rounding collapses into prefix sums.

Classification (thresholds, deviation mode, freshness) and node_fit run
batched on device too. Host keeps only the typed->columnar flattening,
the anomaly counters (stateful across cycles), and offering the planned
pods to the evictor.

Per-node / per-namespace / per-cycle eviction caps (the
EvictionLimiter production configuration — migration arbitrator
blast-radius bounding, /root/reference/pkg/descheduler/controllers/
migration/arbitrator/filter.go) are ALSO modeled on device. Unlike the
uncapped plan they are not prefix-structured: the host loop SKIPS a
refused pod (no usage/budget subtraction) and continues, so acceptance
within a node is not a prefix of its sorted pods (ns-capped pods
interleave with accepted ones). The capped kernel therefore runs ONE
`lax.scan` along the global eviction order with a small carry (current
node's removed usage + count, global budget, total, per-namespace
counts) — still a single device program over the same columns, with
the classification/ordering prelude shared with the prefix kernel.

Narrowing (documented): the device plans predict the EvictionLimiter
exactly; a CUSTOM evictor that refuses arbitrary pods is honored by
filtering the returned selection on evict()'s result, but refusals do
not re-plan (the freed allowance is not re-offered to later pods until
the next cycle).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from koordinator_tpu.api import types as api
from koordinator_tpu.api.extension import NUM_RESOURCES, ResourceKind
from koordinator_tpu.descheduler.lownodeload import (
    LowNodeLoad,
    LowNodeLoadArgs,
)
from koordinator_tpu.snapshot.builder import resource_vec
from koordinator_tpu.snapshot.schema import shape_contract


def _plan_prelude(usage, capacity, fresh, source_mask,
                  pod_node, pod_usage_r, pod_req, pod_eligible,
                  low, high, weights, rdims_onehot,
                  use_deviation: bool, node_fit: bool, fit_dims: tuple):
    """Shared front half of both plan kernels: classification, budget,
    node_fit eligibility, and the global eviction order. Traced inside
    a jit, never called eagerly."""
    eps = 1e-9
    sel = lambda x: jnp.matmul(                           # [.., R]->[.., Rd]
        x, rdims_onehot.T, precision=jax.lax.Precision.HIGHEST)
    pct = 100.0 * sel(usage) / jnp.maximum(sel(capacity), eps)  # [N, Rd]
    if use_deviation:
        nf = jnp.maximum(fresh.sum(), 1)
        avg = jnp.where(fresh[:, None], pct, 0.0).sum(0) / nf
        low = jnp.clip(avg - low, 0.0, 100.0)
        high = jnp.clip(avg + high, 0.0, 100.0)
    low_mask = fresh & (pct < low[None, :]).all(1)        # [N]
    high_mask = fresh & (pct > high[None, :]).any(1)      # [N]
    high_abs = sel(capacity) * high[None, :] / 100.0      # [N, Rd]
    source = source_mask & high_mask                      # [N]

    # a -1 pod_node (pad rows, orphan pods) must not wrap to the last
    # node: clamp every gather through `pn` and gate on `on_node` so
    # such rows are never active and never charge a node
    on_node = pod_node >= 0                               # [P]
    pn = jnp.maximum(pod_node, 0)                         # [P]

    # budget: spare headroom under the HIGH threshold of destinations
    budget0 = jnp.where(low_mask[:, None],
                        high_abs - sel(usage), 0.0).sum(0)  # [Rd]

    # node_fit: pod must fit on >= 1 underutilized node, against
    # allocatable - Σ requests of that node's pods. `fit_dims` (static)
    # restricts the [P, N, R] comparison to dims ANY pod requests —
    # exact, because an unrequested dim compares 0 <= capacity + 0.5,
    # always true (the scheduler bench's fit_dims argument, same idea).
    if node_fit:
        node_req = jnp.zeros_like(capacity).at[pn].add(
            pod_req * on_node[:, None])
        dest_free = capacity - node_req                   # [N, R]
        fd = list(fit_dims) if fit_dims is not None else slice(None)
        fits_pn = (pod_req[:, None, fd] <= dest_free[None][:, :, fd]
                   + 0.5).all(-1)                         # [P, N]
        fits = (fits_pn & low_mask[None, :]).any(-1)      # [P]
        pod_eligible = pod_eligible & fits

    active = pod_eligible & on_node & source[pn]          # [P]

    # --- global eviction order: source nodes by weighted usage%% desc,
    # pods within a node by weighted usage desc (stable = list order) --
    node_w = (pct * weights[None, :]).sum(1)              # [N]
    n = usage.shape[0]
    src_rank = jnp.zeros((n,), jnp.int32).at[
        jnp.argsort(-jnp.where(source, node_w, -jnp.inf))].set(
        jnp.arange(n, dtype=jnp.int32))
    pod_w = (pod_usage_r * weights[None, :]).sum(1)       # [P]
    ord1 = jnp.argsort(-pod_w, stable=True)
    pod_rank = jnp.where(on_node, src_rank[pn], n)        # nodeless last
    order = ord1[jnp.argsort(pod_rank[ord1], stable=True)]
    return sel, active, order, budget0, high_abs


@shape_contract(
    usage="f32[N~pad:zero,R]", capacity="f32[N~pad:zero,R]",
    fresh="bool[N~pad:false]",
    source_mask="bool[N~pad:false]", pod_node="i32[P~pad:-1]",
    pod_usage_r="f32[P~pad:zero,RD]",
    pod_req="f32[P~pad:zero,R]", pod_eligible="bool[P~pad:false]",
    low="f32[RD]",
    high="f32[RD]", weights="f32[RD]", rdims_onehot="f32[RD,R]",
    max_evictions="i32[]",
    _returns=("bool[P~pad:false]", "i32[P~pad:any]"),
    _pad="pod_usage_r is pre-restricted to the RD threshold dims via "
         "rdims_onehot; ineligible pods are simply never taken")
@functools.partial(jax.jit, static_argnames=("use_deviation", "node_fit",
                                             "fit_dims"))
def plan_kernel(usage, capacity, fresh, source_mask,
                pod_node, pod_usage_r, pod_req, pod_eligible,
                low, high, weights, rdims_onehot,
                max_evictions,
                use_deviation: bool = False, node_fit: bool = True,
                fit_dims: tuple = None):
    """The full balance plan as one jitted program.

    Shapes: usage/capacity f32[N, R]; pod_* over P pods with
    pod_usage_r f32[P, Rd] already restricted to the threshold dims;
    rdims_onehot f32[Rd, R] selects those dims out of R columns;
    low/high/weights f32[Rd]. Returns (take bool[P], order i32[P]):
    take[p] marks planned pods, order is the global eviction order (the
    plan is `[int(i) for i in order if take[i]]`).
    """
    sel, active, order, budget0, high_abs = _plan_prelude(
        usage, capacity, fresh, source_mask, pod_node, pod_usage_r,
        pod_req, pod_eligible, low, high, weights, rdims_onehot,
        use_deviation, node_fit, fit_dims)

    ns = pod_node[order]                                  # sorted node ids
    x = jnp.where(active[order, None], pod_usage_r[order], 0.0)  # [P, Rd]

    # segment (per-node) EXCLUSIVE cumsum along the sorted order
    ex = jnp.cumsum(x, 0) - x
    p = x.shape[0]
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), ns[1:] != ns[:-1]])
    start_idx = lax_cummax(jnp.where(is_start,
                                     jnp.arange(p, dtype=jnp.int32), -1))
    seg_ex = ex - ex[jnp.maximum(start_idx, 0)]           # [P, Rd]

    # node prefix: evict while the node is STILL over before this pod
    still_over = ((sel(usage)[ns] - seg_ex) > high_abs[ns]).any(1)  # [P]
    take0 = active[order] & still_over

    # budget prefix (and per-cycle cap): both monotone along the order
    taken_x = jnp.where(take0[:, None], pod_usage_r[order], 0.0)
    cum_before = jnp.cumsum(taken_x, 0) - taken_x
    budget_ok = (budget0[None, :] - cum_before > 0.0).all(1)
    cnt_before = jnp.cumsum(take0.astype(jnp.int32)) - take0
    take_sorted = take0 & budget_ok & (cnt_before < max_evictions)

    take = jnp.zeros((p,), bool).at[order].set(take_sorted)
    return take, order


def lax_cummax(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.associative_scan(jnp.maximum, x)


@shape_contract(
    usage="f32[N~pad:zero,R]", capacity="f32[N~pad:zero,R]",
    fresh="bool[N~pad:false]",
    source_mask="bool[N~pad:false]", pod_node="i32[P~pad:-1]",
    pod_usage_r="f32[P~pad:zero,RD]",
    pod_req="f32[P~pad:zero,R]", pod_eligible="bool[P~pad:false]",
    low="f32[RD]",
    high="f32[RD]", weights="f32[RD]", rdims_onehot="f32[RD,R]",
    pod_ns="i32[P~pad:zero]", ns_counts0="i32[NS~pad:zero]",
    per_node0="i32[N~pad:zero]",
    max_evictions="i32[]", max_per_node="i32[]", max_per_ns="i32[]",
    _returns=("bool[P~pad:false]", "i32[P~pad:any]"),
    _pad="ns_counts0 is padded to a pow2 namespace table "
         "(columnarize_ns); unlimited caps ride _BIG sentinels")
@functools.partial(jax.jit, static_argnames=("use_deviation", "node_fit",
                                             "fit_dims"))
def plan_kernel_capped(usage, capacity, fresh, source_mask,
                       pod_node, pod_usage_r, pod_req, pod_eligible,
                       low, high, weights, rdims_onehot,
                       pod_ns, ns_counts0, per_node0,
                       max_evictions, max_per_node, max_per_ns,
                       use_deviation: bool = False, node_fit: bool = True,
                       fit_dims: tuple = None):
    """The balance plan under per-node / per-namespace / per-cycle caps.

    The host loop SKIPS a limiter-refused pod (no usage or budget
    subtraction) and keeps walking, so acceptance is not prefix-
    structured; this kernel replays that exact decision sequence as one
    `lax.scan` along the global eviction order. Carry: the CURRENT
    node's removed usage + eviction count (the order is node-contiguous,
    so one scalar pair suffices), the global budget/total, and the
    per-namespace counts (`ns_counts0`, padded — see columnarize_ns).
    `per_node0[n]` seeds node n's count from the limiter's existing
    state (mid-cycle reuse), as ns_counts0 does for namespaces.
    Returns (take bool[P], order i32[P]) like plan_kernel.
    """
    sel, active, order, budget0, high_abs = _plan_prelude(
        usage, capacity, fresh, source_mask, pod_node, pod_usage_r,
        pod_req, pod_eligible, low, high, weights, rdims_onehot,
        use_deviation, node_fit, fit_dims)

    ns = pod_node[order]
    usage_node = sel(usage)[ns]                           # [P, Rd]
    high_abs_s = high_abs[ns]                             # [P, Rd]
    pod_ns_s = pod_ns[order]                              # [P]
    u_s = pod_usage_r[order]                              # [P, Rd]
    active_s = active[order]
    p = u_s.shape[0]
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), ns[1:] != ns[:-1]])
    node_cnt0_s = per_node0[ns]                           # [P]

    def step(carry, xs):
        removed, node_cnt, budget, total, ns_counts = carry
        (start, u, un, ha, nsid, act, cnt0) = xs
        removed = jnp.where(start, jnp.zeros_like(removed), removed)
        node_cnt = jnp.where(start, cnt0, node_cnt)
        # host order: the still_over/budget break check runs BEFORE the
        # evict() limiter call; a limiter refusal subtracts nothing
        still_over = ((un - removed) > ha).any()
        budget_open = (budget > 0.0).all()
        want = act & still_over & budget_open
        allow = ((total < max_evictions)
                 & (node_cnt < max_per_node)
                 & (ns_counts[nsid] < max_per_ns))
        take = want & allow
        tf = take.astype(u.dtype)
        removed = removed + u * tf
        budget = budget - u * tf
        total = total + take.astype(total.dtype)
        node_cnt = node_cnt + take.astype(node_cnt.dtype)
        ns_counts = ns_counts.at[nsid].add(take.astype(ns_counts.dtype))
        return (removed, node_cnt, budget, total, ns_counts), take

    rd = u_s.shape[1]
    carry0 = (jnp.zeros((rd,), u_s.dtype), jnp.int32(0), budget0,
              jnp.int32(0), ns_counts0.astype(jnp.int32))
    _, take_sorted = jax.lax.scan(
        step, carry0,
        (is_start, u_s, usage_node, high_abs_s, pod_ns_s, active_s,
         node_cnt0_s))
    take = jnp.zeros((p,), bool).at[order].set(take_sorted)
    return take, order


def _pad_pow2(n: int, lo: int = 8) -> int:
    k = lo
    while k < n:
        k *= 2
    return k


def columnarize(nodes: Sequence[api.Node],
                metrics: Mapping[str, api.NodeMetric],
                pods_by_node: Mapping[str, Sequence[api.Pod]],
                args: LowNodeLoadArgs,
                usage: np.ndarray, capacity: np.ndarray,
                fresh: np.ndarray) -> Optional[dict]:
    """Typed host objects -> the kernel's POD columns (the node columns
    come in prebuilt from LowNodeLoad.node_columns, so flattening
    happens once). No per-pod decision logic here — that is the
    kernel's job. Pod usage is collected from EVERY NodeMetric,
    expired or not, matching the host plugin's pod_usage build (only
    node freshness gates classification)."""
    rdims = sorted({int(k) for k in args.high_thresholds})
    name_to_idx = {node.meta.name: i for i, node in enumerate(nodes)}
    pod_usage_map: Dict[str, np.ndarray] = {}
    for name in name_to_idx:
        m = metrics.get(name)
        if m is not None:
            for pm in m.pods_metric:
                pod_usage_map[pm.namespaced_name] = resource_vec(pm.usage)

    pods: List[api.Pod] = []
    pod_node_l: List[int] = []
    for name, plist in pods_by_node.items():
        i = name_to_idx.get(name)
        if i is None:
            continue
        for pod in plist:
            pods.append(pod)
            pod_node_l.append(i)
    p = len(pods)
    if p == 0:
        return None
    pod_node = np.asarray(pod_node_l, np.int32)
    pod_req = np.zeros((p, NUM_RESOURCES), np.float32)
    pod_usage_r = np.zeros((p, len(rdims)), np.float32)
    pod_eligible = np.zeros((p,), bool)
    for j, pod in enumerate(pods):
        pod_req[j] = resource_vec(pod.requests)
        u = pod_usage_map.get(pod.meta.namespaced_name)
        if u is None:
            u = pod_req[j]
        pod_usage_r[j] = u[rdims]
        pod_eligible[j] = not pod.is_daemonset and (
            args.pod_filter is None or args.pod_filter(pod))

    low = np.array([args.low_thresholds.get(ResourceKind(d), 0.0)
                    for d in rdims], np.float32)
    high = np.array([args.high_thresholds.get(ResourceKind(d), 100.0)
                     for d in rdims], np.float32)
    weights = np.array([args.resource_weights.get(ResourceKind(d), 0.0)
                        for d in rdims], np.float32)
    rdims_onehot = np.zeros((len(rdims), NUM_RESOURCES), np.float32)
    rdims_onehot[np.arange(len(rdims)), rdims] = 1.0
    fit_dims = tuple(int(d) for d in np.flatnonzero(pod_req.any(0)))
    return dict(usage=usage, capacity=capacity, fresh=fresh,
                pod_node=pod_node, pod_usage_r=pod_usage_r,
                pod_req=pod_req, pod_eligible=pod_eligible,
                low=low, high=high, weights=weights,
                rdims_onehot=rdims_onehot, pods=pods,
                fit_dims=fit_dims)


class DeviceLowNodeLoad(LowNodeLoad):
    """LowNodeLoad with the balance plan computed on device.

    Classification for the anomaly counters reuses the host classify()
    (cheap, stateful); the eviction selection — the O(N x P) part — is
    one jitted program. Per-cycle caps ride the prefix kernel; per-node
    / per-namespace caps (the production blast-radius configuration)
    switch to the scan kernel, which replays the limiter's exact
    skip-and-continue decisions. A custom evictor that refuses pods the
    limiter model did not predict is honored by filtering the returned
    selection on evict()'s result — refusals do not re-plan.
    """

    name = "LowNodeLoad"

    _BIG = 1 << 30

    def _limiter_caps(self):
        """(cycle_remaining, max_per_node, max_per_ns, limiter), with
        _BIG sentinels for unlimited dimensions."""
        limiter = getattr(self.evictor, "limiter", None)
        if limiter is None:
            return self._BIG, self._BIG, self._BIG, None
        cyc = (self._BIG if limiter.max_per_cycle is None
               else limiter.max_per_cycle - limiter._total)
        per_node = (self._BIG if limiter.max_per_node is None
                    else limiter.max_per_node)
        per_ns = (self._BIG if limiter.max_per_namespace is None
                  else limiter.max_per_namespace)
        return cyc, per_node, per_ns, limiter

    def balance_once(self, nodes, metrics, pods_by_node, now):
        args = self.args
        # the host plugin never consults the evictor in dry_run —
        # neither may the device caps (golden parity)
        if args.dry_run:
            cyc, per_node, per_ns, limiter = (self._BIG, self._BIG,
                                              self._BIG, None)
        else:
            cyc, per_node, per_ns, limiter = self._limiter_caps()
        if not nodes:
            return []
        # ONE flattening pass; anomaly gating stays host-side
        # (stateful across cycles)
        usage, capacity, fresh = self.node_columns(nodes, metrics, now)
        _, _, low_mask, high_mask, _ = self.classify_columns(
            usage, capacity, fresh)
        names = [nd.meta.name for nd in nodes]
        source_mask = self._gate_anomalies(names, high_mask)
        if not low_mask.any() or not source_mask.any():
            return []
        cols = columnarize(nodes, metrics, pods_by_node, args,
                           usage, capacity, fresh)
        if cols is None:
            return []
        pods = cols.pop("pods")
        pod_node = cols["pod_node"]
        if per_node < self._BIG or per_ns < self._BIG:
            # namespace ids + seeded limiter state (mid-cycle reuse)
            ns_names = sorted({p.meta.namespace for p in pods})
            ns_of = {s: j for j, s in enumerate(ns_names)}
            pod_ns = np.asarray([ns_of[p.meta.namespace] for p in pods],
                                np.int32)
            ns_counts0 = np.zeros((_pad_pow2(len(ns_names)),), np.int32)
            per_node0 = np.zeros((len(nodes),), np.int32)
            if limiter is not None:
                for s, j in ns_of.items():
                    ns_counts0[j] = limiter._per_ns.get(s, 0)
                for i, name in enumerate(names):
                    per_node0[i] = limiter._per_node.get(name, 0)
            take, order = plan_kernel_capped(
                source_mask=source_mask,
                pod_ns=pod_ns, ns_counts0=ns_counts0,
                per_node0=per_node0,
                max_evictions=np.int32(max(min(cyc, self._BIG), 0)),
                max_per_node=np.int32(min(per_node, self._BIG)),
                max_per_ns=np.int32(min(per_ns, self._BIG)),
                use_deviation=args.use_deviation_thresholds,
                node_fit=args.node_fit, **cols)
        else:
            take, order = plan_kernel(
                source_mask=source_mask,
                max_evictions=np.int32(max(min(cyc, self._BIG), 0)),
                use_deviation=args.use_deviation_thresholds,
                node_fit=args.node_fit, **cols)
        take = np.asarray(take)
        sel_idx = [int(i) for i in np.asarray(order) if take[int(i)]]
        if args.dry_run or self.evictor is None:
            return [pods[i] for i in sel_idx]
        selected = []
        for i in sel_idx:
            # honor the live verdict: a custom evictor may refuse pods
            # the limiter model did not predict (refused pods are NOT
            # re-planned — the host loop drops them the same way)
            if self.evictor.evict(
                    pods[i], f"node {names[int(pod_node[i])]} is "
                             f"overutilized"):
                selected.append(pods[i])
        return selected
