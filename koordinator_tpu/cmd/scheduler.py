"""koord-scheduler process: hosts the SchedulerService sidecar.

Capability parity with `cmd/koord-scheduler/main.go`: flags + feature
gates, the services/metrics HTTP endpoint (frameworkext ServicesServer —
/apis/v1/plugins, /debug/flags, /metrics), optional leader election (the
reference inherits it from kube-scheduler's component config), graceful
shutdown. Scheduling itself is request-driven: the edge publishes
snapshots and feeds batches through `SchedulerService.schedule`.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Callable, Optional, Sequence

from koordinator_tpu.cmd.runtime import (
    FileLeaseLock,
    LeaderElector,
    StopHandle,
    default_identity,
    parse_feature_gates,
)
from koordinator_tpu.compilecache import enable_persistent_cache
from koordinator_tpu.features import FeatureGate, new_default_gate
from koordinator_tpu.scheduler.frameworkext import (
    SchedulerService,
    ServicesServer,
)


@dataclasses.dataclass
class SchedulerProcessConfig:
    metrics_port: int = 0            # 0 = ephemeral, -1 = disabled
    sidecar_socket: str = ""         # "" = RPC edge disabled (in-process use)
    lease_file: str = "koord-scheduler.lease"
    enable_leader_election: bool = False
    lease_duration_seconds: float = 15.0
    retry_period_seconds: float = 2.0
    feature_gates: str = ""
    identity: str = ""


class SchedulerProcess:
    def __init__(self, cfg: SchedulerProcessConfig,
                 service: Optional[SchedulerService] = None,
                 gate: Optional[FeatureGate] = None,
                 clock: Callable[[], float] = time.time):
        self.cfg = cfg
        self.service = service or SchedulerService()
        self.gate = gate or new_default_gate()
        parse_feature_gates(self.gate, cfg.feature_gates)
        self.server: Optional[ServicesServer] = None
        if cfg.metrics_port >= 0:
            self.server = ServicesServer(self.service.registry,
                                         self.service.flags,
                                         port=cfg.metrics_port)
        self.sidecar = None
        try:
            identity = cfg.identity or default_identity()
            self.elector = LeaderElector(
                FileLeaseLock(cfg.lease_file, cfg.lease_duration_seconds),
                identity, cfg.retry_period_seconds, clock=clock)
        except BaseException:
            # a partially constructed process must not leak the already-
            # started metrics server (no handle would remain to close it)
            if self.server is not None:
                self.server.close()
            raise

    def _serve(self, should_stop: Callable[[], bool]) -> None:
        # the north-star RPC edge binds only WHILE LEADING: a standby must
        # neither serve mutating Publish/Ingest/Schedule calls (split
        # brain) nor hold the socket (it frees on step-down, letting a hot
        # standby take over the same path). The bind RETRIES while the
        # deposed leader's socket drains — failover must not crash the
        # fresh leader.
        sidecar = None
        if self.cfg.sidecar_socket:
            from koordinator_tpu.runtimeproxy.rpc import RpcError
            from koordinator_tpu.scheduler.sidecar import (
                SchedulerSidecarServer,
            )
            while not should_stop():
                try:
                    sidecar = SchedulerSidecarServer(
                        self.service, self.cfg.sidecar_socket)
                    break
                except RpcError:
                    if not self.cfg.enable_leader_election:
                        # no deposed leader will ever drain the socket:
                        # a live holder means misconfiguration — fail
                        # fast rather than silently spinning
                        raise
                    time.sleep(min(0.05, self.cfg.retry_period_seconds))
        self.sidecar = sidecar
        try:
            while not should_stop():
                time.sleep(min(0.05, self.cfg.retry_period_seconds))
        finally:
            if sidecar is not None:
                sidecar.close()
            self.sidecar = None

    def run(self, stop: Callable[[], bool]) -> None:
        try:
            if self.cfg.enable_leader_election:
                self.elector.run(self._serve, stop)
            else:
                self._serve(stop)
        finally:
            if self.server is not None:
                self.server.close()


def build(argv: Optional[Sequence[str]] = None,
          service: Optional[SchedulerService] = None) -> SchedulerProcess:
    p = argparse.ArgumentParser(prog="koord-scheduler")
    p.add_argument("--feature-gates", default="")
    p.add_argument("--metrics-port", type=int, default=0)
    p.add_argument("--sidecar-socket", default="")
    p.add_argument("--lease-file", default="koord-scheduler.lease")
    p.add_argument("--enable-leader-election", dest="leader_election",
                   action="store_true", default=False)
    p.add_argument("--identity", default="")
    args = p.parse_args(argv)
    cfg = SchedulerProcessConfig(
        metrics_port=args.metrics_port,
        sidecar_socket=args.sidecar_socket,
        lease_file=args.lease_file,
        enable_leader_election=args.leader_election,
        feature_gates=args.feature_gates,
        identity=args.identity)
    return SchedulerProcess(cfg, service)


def main(argv: Optional[Sequence[str]] = None,
         service: Optional[SchedulerService] = None) -> int:
    enable_persistent_cache()
    proc = build(argv, service)
    stop = StopHandle().install_signal_handlers()
    proc.run(stop.stopped)
    return 0
