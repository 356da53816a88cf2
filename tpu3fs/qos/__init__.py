"""QoS subsystem: admission control, weighted-fair IO scheduling, shedding.

The reference gets crude isolation from per-disk worker pools and RDMA
transmission limits (SURVEY §2.3 UpdateWorker/AioReadWorker, IBSocket); a
multi-tenant tpu3fs makes it a first-class, hot-configurable layer:

- ``core``: the traffic-class classification, context-local tagging, token
  buckets + concurrency gates, the declarative ``QosConfig`` tree and the
  ``AdmissionController`` enforced in RPC dispatch (tpu3fs/rpc/net.py and,
  as a cheap ceiling, native/rpc_net.cpp).
- ``scheduler``: weighted-fair (stride) scheduling of storage IO by
  traffic class, threaded through the per-target update workers.
- ``manager``: per-service bundle (admission + policy + recorders) wired
  into StorageService and the service binaries.

Overload surfaces as the retryable ``Code.OVERLOADED`` carrying a server
retry-after hint (reply field + envelope message), honored by
client/storage_client.py with jittered backoff instead of blind retry.
"""

from tpu3fs.qos.core import (
    BACKGROUND_CLASSES,
    SHARE_BOUNDED_CLASSES,
    AdmissionController,
    ConcurrencyGate,
    QosConfig,
    TokenBucket,
    TrafficClass,
    class_from_flags,
    class_to_flags,
    current_class,
    default_class_for,
    format_retry_after,
    infer_write_class,
    retry_after_ms_of,
    tagged,
)
from tpu3fs.qos.manager import QosManager
from tpu3fs.qos.scheduler import WeightedFairQueue, WfqPolicy

__all__ = [
    "AdmissionController",
    "BACKGROUND_CLASSES",
    "ConcurrencyGate",
    "QosConfig",
    "QosManager",
    "SHARE_BOUNDED_CLASSES",
    "TokenBucket",
    "TrafficClass",
    "WeightedFairQueue",
    "WfqPolicy",
    "class_from_flags",
    "class_to_flags",
    "current_class",
    "default_class_for",
    "format_retry_after",
    "infer_write_class",
    "retry_after_ms_of",
    "tagged",
]
