"""Serving substrate: the request-based reachability service.

* ``reach_service`` — ``ReachabilityService``: typed requests, futures,
  admission micro-batching and version-keyed snapshot reuse over any
  ``ReachabilityEngine`` backend;
* ``scheduler`` — the weighted-fair multi-tenant admission scheduler;
* ``replicas`` — ``ReplicaGroup``: one engine's snapshot served from
  several devices.
"""
from .reach_service import (MRRequest, MRSetRequest, ReachabilityService,
                            Request, REQUEST_TYPES, SDistanceRequest,
                            ServiceConfig, ServiceStats, SReachKRequest,
                            SReachRequest, TopSRequest, WitnessRequest)
from .replicas import Replica, ReplicaGroup
from .scheduler import (DeadlineExceeded, PRIORITY_CLASSES, TenantSpec,
                        WeightedFairScheduler)

__all__ = [
    "DeadlineExceeded", "MRRequest", "MRSetRequest", "PRIORITY_CLASSES",
    "REQUEST_TYPES", "ReachabilityService", "Replica", "ReplicaGroup",
    "Request", "SDistanceRequest", "SReachKRequest", "SReachRequest",
    "ServiceConfig", "ServiceStats", "TenantSpec", "TopSRequest",
    "WeightedFairScheduler", "WitnessRequest",
]
