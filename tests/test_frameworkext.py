"""Feature gates, typed config args, and the frameworkext seam (monitor,
debug tables, service endpoints, scheduler service) — SURVEY.md 2.1/2.7."""

import json
import urllib.request

import numpy as np
import pytest

from koordinator_tpu.api.extension import ResourceKind as RK
from koordinator_tpu.features import (
    DEFAULT_FEATURE_GATE,
    FeatureGate,
    FeatureSpec,
)
from koordinator_tpu.scheduler.config_args import (
    DeviceShareArgs,
    LoadAwareSchedulingArgs,
    MostAllocated,
    NodeNUMAResourceArgs,
    SchedulerProfile,
)
from koordinator_tpu.scheduler.frameworkext import (
    DebugFlags,
    SchedulerMonitor,
    SchedulerService,
    ServiceRegistry,
    ServicesServer,
    debug_score_table,
)
from koordinator_tpu.utils import synthetic


# --- feature gates ----------------------------------------------------------


def test_feature_gate_defaults_and_parse():
    gate = FeatureGate({"A": FeatureSpec(default=True),
                        "B": FeatureSpec(default=False),
                        "L": FeatureSpec(default=True,
                                         lock_to_default=True)})
    assert gate.enabled("A") and not gate.enabled("B")
    gate.parse("A=false, B=true")
    assert not gate.enabled("A") and gate.enabled("B")
    with pytest.raises(KeyError):
        gate.enabled("nope")
    with pytest.raises(ValueError):
        gate.parse("A=maybe")
    with pytest.raises(ValueError):
        gate.set("L", False)


def test_default_gate_catalog():
    assert DEFAULT_FEATURE_GATE.enabled("BECPUSuppress")
    assert not DEFAULT_FEATURE_GATE.enabled("Libpfm4")
    assert not DEFAULT_FEATURE_GATE.enabled("ResizePod")
    assert len(list(DEFAULT_FEATURE_GATE.known())) >= 35


# --- typed args -------------------------------------------------------------


def test_args_defaults_validate_clean():
    assert SchedulerProfile().validate() == []


def test_args_validation_rejects_bad_values():
    bad = SchedulerProfile(
        load_aware=LoadAwareSchedulingArgs(
            usage_thresholds={RK.CPU: 150.0},
            filter_agg_type="p42"),
        numa=NodeNUMAResourceArgs(default_cpu_bind_policy="Bogus"),
        device_share=DeviceShareArgs(scoring_strategy="Weird"))
    errs = bad.validate()
    assert len(errs) == 4
    with pytest.raises(ValueError):
        bad.schedule_options()


def test_profile_lowers_to_schedule_options():
    prof = SchedulerProfile(
        numa=NodeNUMAResourceArgs(numa_scoring_strategy=MostAllocated),
        device_share=DeviceShareArgs(scoring_strategy=MostAllocated))
    opts = prof.schedule_options()
    assert opts == {"numa_strategy": "most", "device_strategy": "most"}
    cfg = prof.load_aware_config()
    assert float(cfg.usage_thresholds[int(RK.CPU)]) == 65.0


# --- monitor ----------------------------------------------------------------


def test_monitor_flags_slow_cycles():
    mon = SchedulerMonitor(timeout_seconds=1.0)
    t = mon.start_cycle(now=0.0)
    assert mon.overdue(now=2.5) == [t]
    assert mon.complete_cycle(t, now=3.0) == (3.0, True)
    assert mon.timeouts == 1
    t2 = mon.start_cycle(now=10.0)
    mon.complete_cycle(t2, now=10.2)
    assert mon.timeouts == 1 and mon.overdue(now=10.5) == []


# --- scheduler service + endpoints ------------------------------------------


def test_scheduler_service_end_to_end():
    service = SchedulerService(num_rounds=2, k_choices=4)
    snap = synthetic.synthetic_cluster(32, num_quotas=4)
    service.publish(snap)
    pods = synthetic.synthetic_pods(64, num_quotas=4)
    res = service.schedule(pods)
    placed = int((np.asarray(res.assignment) >= 0).sum())
    assert placed > 0
    assert service.summary()["podsPlaced"] == placed
    assert service.store.version == 2  # publish + post-commit update
    # second batch schedules against the committed state
    res2 = service.schedule(synthetic.synthetic_pods(64, seed=9,
                                                     num_quotas=4))
    assert service.batches == 2


def test_amplification_derived_from_scheduled_snapshot():
    """Regression (ADVICE r3): the amplified-CPU auto-detection keys
    off the snapshot the batch actually READS — writers that bypass
    service.publish() and put snapshots straight into the shared store
    (SnapshotSyncer._rebuild, embedded compositions) still flip the
    gate on."""
    import numpy as np

    service = SchedulerService(num_rounds=1, k_choices=4)
    snap = synthetic.synthetic_cluster(16)
    amp = np.array(snap.nodes.cpu_amplification)
    amp[3] = 1.5
    snap_amp = snap.replace(nodes=snap.nodes.replace(
        cpu_amplification=amp))
    # bypass service.publish on purpose
    service.store.publish(snap_amp)
    service.schedule(synthetic.synthetic_pods(8))
    assert service.schedule_kwargs["enable_amplification"] is True
    # a ratio-1 snapshot published the same way turns it back off
    service.store.publish(synthetic.synthetic_cluster(16, seed=3))
    service.schedule(synthetic.synthetic_pods(8, seed=1))
    assert service.schedule_kwargs["enable_amplification"] is False
    # an explicit constructor kwarg always wins
    svc2 = SchedulerService(num_rounds=1, k_choices=4,
                            enable_amplification=False)
    svc2.store.publish(snap_amp)
    svc2.schedule(synthetic.synthetic_pods(8))
    assert svc2.schedule_kwargs["enable_amplification"] is False


def test_debug_score_table_renders():
    snap = synthetic.synthetic_cluster(8)
    pods = synthetic.synthetic_pods(3)
    from koordinator_tpu.scheduler.plugins.loadaware import LoadAwareConfig
    table = debug_score_table(snap, pods, LoadAwareConfig.make(), top_n=3,
                              pod_names=["a", "b", "c"])
    lines = table.splitlines()
    assert lines[0].startswith("pod")
    assert len(lines) == 5 and "node" in lines[2]


def test_services_http_endpoints():
    registry = ServiceRegistry()
    registry.register("gang", lambda: {"gangs": 3})
    flags = DebugFlags()
    server = ServicesServer(registry, flags)
    try:
        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(f"{base}/apis/v1/plugins") as r:
            assert json.load(r)["plugins"] == ["gang"]
        with urllib.request.urlopen(f"{base}/apis/v1/plugins/gang") as r:
            assert json.load(r) == {"gangs": 3}
        req = urllib.request.Request(f"{base}/debug/flags/s", data=b"5",
                                     method="PUT")
        with urllib.request.urlopen(req) as r:
            assert json.load(r)["scoreTopN"] == 5
        assert flags.score_top_n == 5
    finally:
        server.close()


def test_debug_filter_table_and_http_toggle():
    """The /debug/flags/f counterpart (DebugFiltersSetter): per-gate
    rejection counts per pod, toggled over HTTP."""
    from koordinator_tpu.scheduler.frameworkext import debug_filter_table
    from koordinator_tpu.scheduler.plugins.loadaware import LoadAwareConfig

    snap = synthetic.synthetic_cluster(8)
    pods = synthetic.synthetic_pods(3)
    table = debug_filter_table(snap, pods, LoadAwareConfig.make(),
                               pod_names=["a", "b", "c"])
    lines = table.splitlines()
    assert lines[0].startswith("pod") and len(lines) == 5
    assert all("fit:" in ln for ln in lines[2:])
    registry = ServiceRegistry()
    flags = DebugFlags()
    server = ServicesServer(registry, flags)
    try:
        base = f"http://127.0.0.1:{server.port}"
        req = urllib.request.Request(f"{base}/debug/flags/f", data=b"true",
                                     method="PUT")
        with urllib.request.urlopen(req) as r:
            assert json.load(r)["filterDump"] is True
        assert flags.filter_dump is True
    finally:
        server.close()


def test_debug_filter_table_covers_topology_gates():
    """The filter table mirrors the taint/spread/affinity gates too."""
    from koordinator_tpu.api.types import (
        Node, NodeMetric, ObjectMeta, Pod, PodAffinityTerm, Taint,
        TopologySpreadConstraint,
    )
    from koordinator_tpu.api.extension import ResourceKind as RK
    from koordinator_tpu.scheduler.frameworkext import debug_filter_table
    from koordinator_tpu.scheduler.plugins.loadaware import LoadAwareConfig
    from koordinator_tpu.snapshot import SnapshotBuilder

    b = SnapshotBuilder(max_nodes=3)
    for i in range(3):
        b.add_node(Node(
            meta=ObjectMeta(name=f"n{i}", labels={"zone": f"z{i % 2}"}),
            taints=[Taint(key="x", effect="NoSchedule")] if i == 2 else [],
            allocatable={RK.CPU: 8000.0, RK.MEMORY: 16384.0}))
        b.set_node_metric(NodeMetric(node_name=f"n{i}", update_time=1e9,
                                     node_usage={}))
    b.add_running_pod(Pod(meta=ObjectMeta(name="r", namespace="d",
                                          labels={"app": "x"}),
                          requests={RK.CPU: 100.0}, phase="Running",
                          node_name="n0"))
    snap, ctx = b.build(now=1e9)
    pods = [Pod(meta=ObjectMeta(name="p", namespace="d",
                                labels={"app": "x"}),
                priority=9000, requests={RK.CPU: 100.0},
                spread_constraints=[TopologySpreadConstraint(
                    topology_key="zone", label_selector={"app": "x"})],
                pod_affinity=[PodAffinityTerm(
                    topology_key="zone", label_selector={"app": "x"},
                    anti=True)])]
    table = debug_filter_table(snap, b.build_pod_batch(pods, ctx),
                               LoadAwareConfig.make(), pod_names=["p"])
    assert "TaintToleration:-1" in table
    assert "PodTopologySpread:-1" in table
    assert "fit:1/3" in table
    # anti row: rebuild with only the anti term so its rejection is not
    # shadowed by spread (gates subtract in order)
    pods2 = [Pod(meta=ObjectMeta(name="q", namespace="d",
                                 labels={"app": "x"}),
                 priority=9000, requests={RK.CPU: 100.0},
                 pod_affinity=[PodAffinityTerm(
                     topology_key="zone", label_selector={"app": "x"},
                     anti=True)])]
    t2 = debug_filter_table(snap, b.build_pod_batch(pods2, ctx),
                            LoadAwareConfig.make(), pod_names=["q"])
    assert "InterPodAntiAffinity:-" in t2
    # affinity row: a follower of a nonexistent app is rejected everywhere
    pods3 = [Pod(meta=ObjectMeta(name="r", namespace="d",
                                 labels={"app": "y"}),
                 priority=9000, requests={RK.CPU: 100.0},
                 pod_affinity=[PodAffinityTerm(
                     topology_key="zone",
                     label_selector={"app": "nothing"})])]
    t3 = debug_filter_table(snap, b.build_pod_batch(pods3, ctx),
                            LoadAwareConfig.make(), pod_names=["r"])
    assert "InterPodAffinity:-" in t3 and "fit:0/3" in t3


# --- auto-pack (batching-layer specializations on the service path) ---------


def test_service_auto_pack_returns_results_in_caller_order():
    """The service derives dom_classes + prefix packing per batch and
    must hand every per-pod result array back in the CALLER's pod
    order: pods with distinguishable outcomes (impossible requests,
    reservation owners, NUMA binds) keep those outcomes at their
    original rows."""
    n, p = 256, 1024
    service = SchedulerService(num_rounds=2, k_choices=4)
    snap = synthetic.full_gate_cluster(n, num_quotas=8, num_gangs=8)
    service.publish(snap)
    pods = synthetic.full_gate_pods(p, n, seed=33, num_quotas=8,
                                    num_gangs=8)
    # pin sentinel rows at known ORIGINAL indices (unpacked order):
    # scattered impossible pods that packing will reorder
    reqs = np.asarray(pods.requests).copy()
    impossible = np.array([5, 300, 777, 1000])
    reqs[impossible] = 1e9
    pods = pods.replace(requests=reqs)
    res = service.schedule(pods)
    a = np.asarray(res.assignment)
    assert (a[impossible] == -1).all(), \
        "impossible pods must be unschedulable at their ORIGINAL rows"
    placed = int((a >= 0).sum())
    assert placed > 0
    # reservation consumption reported at the owners' original rows
    slot = np.asarray(res.res_slot)
    owner = np.asarray(pods.reservation_owner)
    assert (slot[owner < 0] < 0).all(), \
        "non-owner rows must never report a consumed slot"
    if (slot >= 0).any():
        rows = np.flatnonzero(slot >= 0)
        assert (owner[rows] == slot[rows]).all(), \
            "consumed slot ids must match the owner ids at those rows"
    # NUMA zone reports land on CPU-bind rows only
    zone = np.asarray(res.numa_zone)
    assert (zone[~np.asarray(pods.numa_single)] < 0).all()


def test_service_auto_pack_matches_unpacked_on_uncontended_cluster():
    """With ample capacity both configurations place every valid pod;
    auto_pack must not change that (only tie-breaks may differ)."""
    n, p = 512, 1024
    pods = synthetic.full_gate_pods(p, n, seed=41, num_quotas=8,
                                    num_gangs=8)
    results = {}
    for auto in (True, False):
        service = SchedulerService(num_rounds=2, k_choices=8,
                                   auto_pack=auto)
        service.publish(synthetic.full_gate_cluster(
            n, num_quotas=8, num_gangs=8))
        res = service.schedule(pods)
        results[auto] = np.asarray(res.assignment)
    placed_on = int((results[True] >= 0).sum())
    placed_off = int((results[False] >= 0).sum())
    # tight contention-free bound: the two programs may break ties
    # differently but must place essentially the same pod set
    assert abs(placed_on - placed_off) <= p // 100, (placed_on,
                                                    placed_off)
    assert placed_on > p // 2


def test_service_auto_pack_skips_small_batches():
    service = SchedulerService(num_rounds=1, k_choices=4)
    snap = synthetic.full_gate_cluster(64, num_quotas=4, num_gangs=4)
    pods = synthetic.full_gate_pods(256, 64, seed=3, num_quotas=4,
                                    num_gangs=4)
    packed, kwargs, inv = service._prepare_batch(snap, pods)
    assert inv is None  # below AUTO_PACK_MIN_BATCH: no reorder
    assert "dom_classes" in kwargs  # classes are free — always derived
    assert packed is pods


def test_builder_same_key_groups_form_one_domain_class():
    """The real informer/builder flow: two spread constraints sharing
    topologyKey "zone" (distinct selectors) produce byte-identical
    domain rows, so dom_classes batches them into one class — the
    static structure the service's auto-pack derivation hands to
    schedule_batch — and the scheduled placements respect both groups'
    skew bounds."""
    from koordinator_tpu.api import types as api
    from koordinator_tpu.api.extension import ResourceKind as RK
    from koordinator_tpu.snapshot import (
        ClusterInformerHub,
        SnapshotStore,
        SnapshotSyncer,
    )

    now = 1e9
    zones = ["z0", "z0", "z1", "z1"]
    hub, store = ClusterInformerHub(), SnapshotStore()
    syncer = SnapshotSyncer(hub, store, max_nodes=4)
    service = SchedulerService(store=store, num_rounds=2, k_choices=4)
    syncer.attach_scheduler(service)
    for i, z in enumerate(zones):
        hub.upsert_node(api.Node(
            meta=api.ObjectMeta(name=f"n{i}", labels={"zone": z}),
            allocatable={RK.CPU: 32000.0, RK.MEMORY: 65536.0}))
        hub.set_node_metric(api.NodeMetric(node_name=f"n{i}",
                                           update_time=now,
                                           node_usage={}))
    assert syncer.sync(now=now) == "full"
    pods = []
    for app in ("a", "b"):
        c = api.TopologySpreadConstraint(
            max_skew=1, topology_key="zone",
            label_selector={"app": app})
        for j in range(4):
            pods.append(api.Pod(
                meta=api.ObjectMeta(name=f"{app}{j}", uid=f"{app}{j}",
                                    namespace="d",
                                    labels={"app": app}),
                priority=9000 - j, requests={RK.CPU: 1000.0},
                spread_constraints=[c]))
    batch = syncer.build_pod_batch(pods)
    assert batch.has_spread
    classes = synthetic.dom_classes(batch)
    # both zone-keyed groups share one class (identical domain rows)
    assert any(len(c) == 2 for c in classes[0]), classes[0]
    res = service.schedule(batch, typed_pods=pods)
    a = np.asarray(res.assignment)
    assert (a >= 0).all()
    # each app independently balanced across the two zones
    for app_rows in (range(0, 4), range(4, 8)):
        zs = [zones[a[j]] for j in app_rows]
        assert abs(zs.count("z0") - zs.count("z1")) <= 1, zs
