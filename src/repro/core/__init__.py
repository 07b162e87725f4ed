"""Arcadia core: the paper's replicated PMEM log, faithfully.

Public surface:

  PMEMDevice / DeviceStats      — simulated PMEM with real volatility
  persist / write_and_force     — persistence + replication primitives
  IntegrityRegion / AtomicRegion— integrity + atomicity primitives
  Log / LogConfig               — the log (reserve/copy/complete/force)
  force_policy.make_policy      — sync / group / freq force policies
  build_replica_set             — local / local+remote / remote_only setups
  quorum_recover / CopyAccessor — §4.2 recovery protocol
  ClusterManager                — membership / election / fencing contract
  Scrubber / resync_backup /
    FailureDetector / HealthMonitor — self-healing lifecycle (DESIGN.md §11)
  LogRouter / ShardSpec /
    ShardPlacement / SnapshotCut  — sharded multi-log router (DESIGN.md §12)
  LogLifecycle / TrimError      — checkpoint+truncate lifecycle (§13)
  baselines                     — PMDK / FLEX / Query Fresh comparators
"""

from .pmem import CACHE_LINE, ATOM, DeviceStats, PMEMDevice
from .primitives import (AtomicRegion, ForceRound, IntegrityRegion, LF_REP,
                         ORDERINGS, PARALLEL, REP_LF, SalvageForceRound,
                         persist, reissue_segs, write_and_force,
                         write_and_force_segs, write_and_force_segs_async)
from .log import (AckRateEstimator, Batch, CorruptLogError, Log, LogConfig,
                  LogError, LogFullError, Superline, TrimError)
from .lifecycle import LifecycleConfig, LogLifecycle, TrimReport
from .force_policy import (ForcePolicy, FreqPolicy, GroupCommitPolicy,
                           SyncPolicy, make_policy)
from .ingest import (IngestClosedError, IngestConfig, IngestEngine,
                     IngestError, IngestQueueFull, IngestShedError,
                     IngestTicket, latency_percentiles)
from .transport import (QuorumError, QuorumRound, ReplicaServer,
                        ReplicationGroup, RoundSalvage, Transport,
                        TransportError)
from .replication import ReplicaSet, build_replica_set, device_size
from .recovery import CopyAccessor, RecoveryError, RecoveryReport, \
    quorum_recover
from .cluster import ClusterManager, Node
from .health import (FailureDetector, HealthMonitor, HeartbeatConfig,
                     ResyncReport, ScrubConfig, ScrubReport, Scrubber,
                     resync_backup)
from .router import (LogRouter, RouterError, RouterRecovery, Shard,
                     ShardPlacement, ShardRecovery, ShardSpec, SnapshotCut,
                     UnknownShardError, payload_digest, stream_digest)

__all__ = [
    "CACHE_LINE", "ATOM", "DeviceStats", "PMEMDevice",
    "AtomicRegion", "ForceRound", "IntegrityRegion", "LF_REP", "ORDERINGS",
    "PARALLEL", "REP_LF", "SalvageForceRound", "persist", "reissue_segs",
    "write_and_force", "write_and_force_segs", "write_and_force_segs_async",
    "AckRateEstimator", "Batch", "CorruptLogError", "Log", "LogConfig",
    "LogError", "LogFullError", "Superline", "TrimError",
    "LifecycleConfig", "LogLifecycle", "TrimReport",
    "ForcePolicy", "FreqPolicy", "GroupCommitPolicy", "SyncPolicy",
    "make_policy",
    "IngestClosedError", "IngestConfig", "IngestEngine", "IngestError",
    "IngestQueueFull", "IngestShedError", "IngestTicket",
    "latency_percentiles",
    "QuorumError", "QuorumRound", "ReplicaServer", "ReplicationGroup",
    "RoundSalvage", "Transport", "TransportError",
    "ReplicaSet", "build_replica_set", "device_size",
    "CopyAccessor", "RecoveryError", "RecoveryReport", "quorum_recover",
    "ClusterManager", "Node",
    "FailureDetector", "HealthMonitor", "HeartbeatConfig", "ResyncReport",
    "ScrubConfig", "ScrubReport", "Scrubber", "resync_backup",
    "LogRouter", "RouterError", "RouterRecovery", "Shard", "ShardPlacement",
    "ShardRecovery", "ShardSpec", "SnapshotCut", "UnknownShardError",
    "payload_digest", "stream_digest",
]
