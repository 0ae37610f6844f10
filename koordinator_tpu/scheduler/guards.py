"""Device health guards: columnar defect scans fused into the batch
program, a packed health word, and per-node/per-pod quarantine masks.

Failure model (docs/DESIGN.md "Failure model & degradation ladder"): a
resident service ingesting arrivals from millions of users WILL see a
NaN metric column, a negative allocatable, an out-of-range domain index
— and one poisoned [P, N] matmul corrupts every placement in the batch
(NaN * 0 == NaN). Instead of trusting the edge, the batch program scans
its own inputs:

- `snapshot_health(snap)` scans the node columns: non-finite metric
  values, invalid (negative/non-finite) allocatable or requested,
  per-node overcommit (requested > allocatable + tol, the same invariant
  `core.overcommit_ok` asserts host-side), and inconsistent NUMA pools.
- `batch_health(snap, pods)` scans the pod batch: non-finite or
  negative requests/estimated, gang/quota/selector/toleration ids out
  of the snapshot's capacity range, and domain-matrix entries out of
  the count-surface range (which would mis-gate a whole constraint
  group through clipped gathers).
- `apply_quarantine` neutralizes what the scans found: bad nodes become
  `schedulable=False` with their float rows scrubbed (NaN/Inf -> 0,
  negatives clamped), bad pods become `valid=False` with their request
  rows scrubbed, and a domain row with out-of-range entries is scrubbed
  to -1 with its CARRIER pods quarantined (non-carriers are untouched
  by the group, so clean placements are preserved exactly).

`guarded_schedule_batch` composes all three with `core.schedule_batch`
in ONE jitted program — no new host sync; the service reads back a
single packed [word, bad_nodes, bad_pods] health vector (u32[3]) and
only touches the masks on the cold path, when the word is non-zero.
On healthy inputs every scrub is a `jnp.where` over an all-false mask,
so the scheduled columns are bit-identical to the unguarded program
(tools/chaos_smoke.py pins placements either way).

Word layout (u32; bit set = defect class present anywhere):
  bit 0  NODE_METRIC_NONFINITE   NaN/Inf in a metric-derived column
  bit 1  NODE_BAD_ALLOCATABLE    negative/non-finite allocatable
  bit 2  NODE_BAD_REQUESTED      negative/non-finite requested
  bit 3  NODE_OVERCOMMIT         requested > allocatable + tol
  bit 4  NODE_NUMA_INVALID       numa_free < 0 / > cap / non-finite
  bit 8  POD_NONFINITE           NaN/Inf in requests/estimated
  bit 9  POD_NEGATIVE            negative requests/estimated
  bit 10 POD_ID_RANGE            gang/quota/selector/toleration id OOB
  bit 11 POD_DOMAIN_RANGE        domain-matrix entry outside [-1, D)
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from koordinator_tpu.scheduler import core
from koordinator_tpu.scheduler.plugins import loadaware
from koordinator_tpu.snapshot import hostbatch
from koordinator_tpu.snapshot.schema import (
    ClusterSnapshot,
    MAX_QUOTA_DEPTH,
    PodBatch,
    shape_contract,
)

# matches core.overcommit_ok's default tolerance: the guard must agree
# with the host-side invariant, or a snapshot the dryrun calls healthy
# would quarantine on device (and vice versa)
OVERCOMMIT_TOL = 1.0

HEALTH_OK = 0
NODE_METRIC_NONFINITE = 1 << 0
NODE_BAD_ALLOCATABLE = 1 << 1
NODE_BAD_REQUESTED = 1 << 2
NODE_OVERCOMMIT = 1 << 3
NODE_NUMA_INVALID = 1 << 4
POD_NONFINITE = 1 << 8
POD_NEGATIVE = 1 << 9
POD_ID_RANGE = 1 << 10
POD_DOMAIN_RANGE = 1 << 11

# bit -> stable defect name (metric labels, chaos assertions, logs)
DEFECT_NAMES = {
    NODE_METRIC_NONFINITE: "node_metric_nonfinite",
    NODE_BAD_ALLOCATABLE: "node_bad_allocatable",
    NODE_BAD_REQUESTED: "node_bad_requested",
    NODE_OVERCOMMIT: "node_overcommit",
    NODE_NUMA_INVALID: "node_numa_invalid",
    POD_NONFINITE: "pod_nonfinite",
    POD_NEGATIVE: "pod_negative",
    POD_ID_RANGE: "pod_id_range",
    POD_DOMAIN_RANGE: "pod_domain_range",
}


def decode_health_word(word: int) -> Tuple[str, ...]:
    """Host-side: the defect-class names set in a packed health word."""
    return tuple(name for bit, name in sorted(DEFECT_NAMES.items())
                 if int(word) & bit)


def _pack(flag_bits) -> jnp.ndarray:
    """OR scalar-bool flags into one u32 word."""
    word = jnp.uint32(0)
    for flag, bit in flag_bits:
        word = word | jnp.where(flag, jnp.uint32(bit), jnp.uint32(0))
    return word


def _row_nonfinite(col: jnp.ndarray) -> jnp.ndarray:
    """bool[N]: any non-finite entry in the row's trailing axes."""
    bad = ~jnp.isfinite(col)
    return bad.reshape(bad.shape[0], -1).any(axis=1)


def _row_invalid(col: jnp.ndarray) -> jnp.ndarray:
    """bool[N]: any negative or non-finite entry per row."""
    bad = ~jnp.isfinite(col) | (col < 0.0)
    return bad.reshape(bad.shape[0], -1).any(axis=1)


def _node_defects(snap: ClusterSnapshot):
    """-> (word u32[], node_bad bool[N]). Pure columnar reductions."""
    nodes = snap.nodes
    metric_cols = (nodes.usage, nodes.prod_usage, nodes.agg_usage,
                   nodes.assigned_estimated, nodes.assigned_correction,
                   nodes.prod_assigned_estimated,
                   nodes.prod_assigned_correction)
    bad_metric = _row_nonfinite(metric_cols[0])
    for col in metric_cols[1:]:
        bad_metric = bad_metric | _row_nonfinite(col)
    bad_alloc = _row_invalid(nodes.allocatable)
    bad_req = _row_invalid(nodes.requested)
    # NaN comparisons are False, so a non-finite row cannot mask an
    # overcommit bit it doesn't deserve — it trips its own class instead
    over = (nodes.requested > nodes.allocatable + OVERCOMMIT_TOL).any(axis=1)
    numa_bad_elem = (~jnp.isfinite(nodes.numa_free)
                     | (nodes.numa_free < 0.0)
                     | (nodes.numa_free > nodes.numa_cap + OVERCOMMIT_TOL))
    numa_bad_elem = numa_bad_elem & nodes.numa_valid[:, :, None]
    bad_numa = numa_bad_elem.reshape(numa_bad_elem.shape[0], -1).any(axis=1)
    node_bad = bad_metric | bad_alloc | bad_req | over | bad_numa
    word = _pack(((bad_metric.any(), NODE_METRIC_NONFINITE),
                  (bad_alloc.any(), NODE_BAD_ALLOCATABLE),
                  (bad_req.any(), NODE_BAD_REQUESTED),
                  (over.any(), NODE_OVERCOMMIT),
                  (bad_numa.any(), NODE_NUMA_INVALID)))
    return word, node_bad


def _id_oob(ids: jnp.ndarray, cap: int) -> jnp.ndarray:
    """-1 is the legitimate 'none' sentinel everywhere; anything below
    it or at/past the snapshot capacity reads a clipped (wrong) row."""
    return (ids < -1) | (ids >= cap)


_DOMAIN_FAMILIES = (
    # (gate switch attr, domain matrix, count surface, carrier matrix)
    ("has_spread", "spread_domain", "spread_count0", "spread_carrier"),
    ("has_anti", "anti_domain", "anti_count0", "anti_carrier"),
    ("has_aff", "aff_domain", "aff_count0", "aff_carrier"),
)


def _bad_domain_groups(pods: PodBatch):
    """Per family: bool[Gf] groups whose domain row holds an entry
    outside [-1, D). Families whose gate is compiled out yield None."""
    out = {}
    for switch, dom_f, cnt_f, _carrier in _DOMAIN_FAMILIES:
        if not getattr(pods, switch):
            out[dom_f] = None
            continue
        dom = getattr(pods, dom_f)
        d = getattr(pods, cnt_f).shape[1]
        out[dom_f] = ((dom < -1) | (dom >= d)).any(axis=1)
    return out


def _batch_defects(snap: ClusterSnapshot, pods: PodBatch):
    """-> (word u32[], pod_bad bool[P]). Defects are detected on EVERY
    row (a NaN in an invalid pad row still poisons batch-global
    matmuls); the caller drains only valid rows through the error
    chain."""
    bad_nonfinite = (_row_nonfinite(pods.requests)
                     | _row_nonfinite(pods.estimated)
                     | ~jnp.isfinite(pods.gpu_ratio))
    bad_neg = ((pods.requests < 0.0).any(axis=1)
               | (pods.estimated < 0.0).any(axis=1)
               | (pods.gpu_ratio < 0.0))
    n_gangs = snap.gangs.min_member.shape[0]
    n_quotas = snap.quotas.parent.shape[0]
    n_sel = pods.selector_match.shape[0]
    n_tol = pods.tol_forbid.shape[0]
    bad_id = (_id_oob(pods.gang_id, n_gangs)
              | _id_oob(pods.quota_id, n_quotas)
              | _id_oob(pods.selector_id, n_sel)
              | _id_oob(pods.toleration_id, n_tol))
    bad_groups = _bad_domain_groups(pods)
    bad_domain_pod = jnp.zeros(pods.requests.shape[:1], bool)
    any_bad_group = jnp.asarray(False)
    for _switch, dom_f, _cnt_f, carrier_f in _DOMAIN_FAMILIES:
        bg = bad_groups[dom_f]
        if bg is None:
            continue
        carrier = getattr(pods, carrier_f)
        bad_domain_pod = bad_domain_pod | (carrier & bg[None, :]).any(axis=1)
        any_bad_group = any_bad_group | bg.any()
    pod_bad = bad_nonfinite | bad_neg | bad_id | bad_domain_pod
    word = _pack(((bad_nonfinite.any(), POD_NONFINITE),
                  (bad_neg.any(), POD_NEGATIVE),
                  (bad_id.any(), POD_ID_RANGE),
                  (any_bad_group, POD_DOMAIN_RANGE)))
    return word, pod_bad


def _scrub_rows(col: jnp.ndarray, bad: jnp.ndarray) -> jnp.ndarray:
    """Replace bad rows with their sanitized (finite, non-negative)
    values; healthy rows pass through bit-identically."""
    clean = jnp.maximum(
        jnp.nan_to_num(col, nan=0.0, posinf=0.0, neginf=0.0), 0.0)
    return jnp.where(bad.reshape(bad.shape + (1,) * (col.ndim - 1)),
                     clean, col)


def _quarantine(snap: ClusterSnapshot, pods: PodBatch,
                node_bad: jnp.ndarray, pod_bad: jnp.ndarray):
    nodes = snap.nodes
    alloc = _scrub_rows(nodes.allocatable, node_bad)
    # clamp the capacity-consistency defects too (requested within
    # allocatable, numa_free within cap): the scrubbed snapshot is what
    # gets COMMITTED, so a defect the scrub leaves in place would
    # re-trip the guard word — and re-count the same node on every
    # metric — each cycle until a full re-publish
    requested = _scrub_rows(nodes.requested, node_bad)
    requested = jnp.where(node_bad[:, None],
                          jnp.minimum(requested, alloc), requested)
    numa_free = _scrub_rows(nodes.numa_free, node_bad)
    numa_free = jnp.where(node_bad[:, None, None],
                          jnp.minimum(numa_free, nodes.numa_cap),
                          numa_free)
    nodes = nodes.replace(
        schedulable=nodes.schedulable & ~node_bad,
        allocatable=alloc,
        requested=requested,
        usage=_scrub_rows(nodes.usage, node_bad),
        prod_usage=_scrub_rows(nodes.prod_usage, node_bad),
        agg_usage=_scrub_rows(nodes.agg_usage, node_bad),
        assigned_estimated=_scrub_rows(nodes.assigned_estimated, node_bad),
        assigned_correction=_scrub_rows(nodes.assigned_correction,
                                        node_bad),
        prod_assigned_estimated=_scrub_rows(nodes.prod_assigned_estimated,
                                            node_bad),
        prod_assigned_correction=_scrub_rows(
            nodes.prod_assigned_correction, node_bad),
        numa_free=numa_free,
    )
    updates = dict(
        valid=pods.valid & ~pod_bad,
        requests=_scrub_rows(pods.requests, pod_bad),
        estimated=_scrub_rows(pods.estimated, pod_bad),
        gpu_ratio=_scrub_rows(pods.gpu_ratio, pod_bad),
    )
    # a domain row with out-of-range entries is scrubbed to -1 (node
    # lacks the label); its carriers are already in pod_bad, so no
    # clean pod is ever gated by the scrubbed group
    bad_groups = _bad_domain_groups(pods)
    for _switch, dom_f, _cnt_f, _carrier_f in _DOMAIN_FAMILIES:
        bg = bad_groups[dom_f]
        if bg is None:
            continue
        dom = getattr(pods, dom_f)
        updates[dom_f] = jnp.where(bg[:, None], -1, dom)
    return snap.replace(nodes=nodes), pods.replace(**updates)


@shape_contract(snap="ClusterSnapshot",
                _returns=("u32[]", "bool[N~pad:false]"),
                _pad="pad node rows are zero-capacity and scan healthy; "
                     "the word ORs defect-class bits over ALL rows")
@jax.jit
def snapshot_health(snap: ClusterSnapshot):
    """Scan the node columns; -> (packed health word, quarantine mask)."""
    return _node_defects(snap)


@shape_contract(snap="ClusterSnapshot", pods="PodBatch",
                _returns=("u32[]", "bool[P~pad:false]"),
                _pad="defects are detected on every row including "
                     "invalid pads (they still poison batch-global "
                     "matmuls); callers drain only valid rows")
@jax.jit
def batch_health(snap: ClusterSnapshot, pods: PodBatch):
    """Scan the pod batch; -> (packed health word, quarantine mask)."""
    return _batch_defects(snap, pods)


@shape_contract(snap="ClusterSnapshot", pods="PodBatch",
                node_bad="bool[N~pad:false]", pod_bad="bool[P~pad:false]",
                _returns=("ClusterSnapshot", "PodBatch"),
                _pad="all-false masks are a bit-identical pass-through")
@jax.jit
def apply_quarantine(snap: ClusterSnapshot, pods: PodBatch,
                     node_bad: jnp.ndarray, pod_bad: jnp.ndarray):
    """Neutralize flagged rows: bad nodes unschedulable + scrubbed, bad
    pods invalid + scrubbed, bad domain groups scrubbed to -1."""
    return _quarantine(snap, pods, node_bad, pod_bad)


@shape_contract(
    snap="ClusterSnapshot", pods="PodBatch", cfg="LoadAwareConfig",
    _returns=("ScheduleResult", "u32[3]", "bool[N~pad:false]",
              "bool[P~pad:false]"),
    _static={"num_rounds": 2, "k_choices": 2, "quota_depth": 2},
    _pad="quarantined rows behave exactly like schedulable=False nodes "
         "/ valid=False pods; health is [word, bad_nodes, bad_pods] "
         "packed for a single cold-path readback")
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
def guarded_schedule_batch(snap: ClusterSnapshot, pods: PodBatch,
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
                           cascade: bool = False):
    """Health guards + quarantine + `core.schedule_batch`, fused as ONE
    device program (same static knobs, same placement semantics on
    healthy inputs). Returns `(result, health, node_bad, pod_bad)` with
    `health = [packed word, quarantined nodes, quarantined pods]` as a
    single u32[3] vector — the service's one guard readback; the masks
    stay on device until the word says there is something to read.
    `pods` may also arrive as its one-buffer upload (a `HostBatch`),
    taken apart inside this program before the guards see it."""
    pods = hostbatch.unpack(pods)
    node_word, node_bad = _node_defects(snap)
    pod_word, pod_bad = _batch_defects(snap, pods)
    g_snap, g_pods = _quarantine(snap, pods, node_bad, pod_bad)
    result = core.schedule_batch(
        g_snap, g_pods, cfg, num_rounds=num_rounds, k_choices=k_choices,
        score_dims=score_dims, tie_break=tie_break,
        enable_numa=enable_numa,
        numa_strategy=numa_strategy, enable_devices=enable_devices,
        device_strategy=device_strategy, quota_depth=quota_depth,
        fit_dims=fit_dims, enable_amplification=enable_amplification,
        topo_prefix=topo_prefix, dom_classes=dom_classes,
        numa_prefix=numa_prefix, gpu_prefix=gpu_prefix, cascade=cascade)
    health = jnp.stack([node_word | pod_word,
                        node_bad.sum().astype(jnp.uint32),
                        pod_bad.sum().astype(jnp.uint32)])
    return result, health, node_bad, pod_bad
