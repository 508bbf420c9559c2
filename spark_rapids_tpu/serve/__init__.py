"""Serving tier: concurrent query scheduling over one TpuRuntime.

ROADMAP item 2 ("Accelerating Presto with GPUs" is the production
exemplar): a session-multiplexing `QueryScheduler` with priority queues
and fair-share admission control layered on the device semaphore,
per-query memory budgets feeding the existing reserve()/RetryOOM spill
machinery, and a parameterized plan cache that lifts literals out of
physical plans so the 2nd..Nth literal-variant submission replays the
1st submission's traced+compiled whole-stage executables instead of
paying warmup again (a cold query's compile time dwarfs its execution;
the cache is what makes a second user cheap).

Entry point: `TpuSession.submit(df, priority=..., memory_need=...,
deadline_ms=...)` returns a `QueryFuture`; the blocking `collect()`
paths are untouched.  Query lifecycle robustness lives in lifecycle.py:
`QueryFuture.cancel()` (cooperative cancellation with owner-confined
cleanup), per-query deadlines with admission-time shedding, and
SLO-aware preemption that suspends a lower-priority query at a stage
boundary and resumes it bit-for-bit.
"""
from .lifecycle import (QueryCancelled, QueryDeadlineExceeded,
                        QueryLifecycle, QueryTimeout)
from .plan_cache import PlanCache, extract_parameters, plan_cache_key
from .scheduler import AdmissionRejected, QueryFuture, QueryScheduler

__all__ = ["PlanCache", "extract_parameters", "plan_cache_key",
           "AdmissionRejected", "QueryFuture", "QueryScheduler",
           "QueryCancelled", "QueryDeadlineExceeded", "QueryLifecycle",
           "QueryTimeout"]
