"""Data-movement policy engine: the decision tier over the measured
memory hierarchy (ROADMAP item 3).

The memory-pressure ledger and the operators' byte counters made
every movement decision *measurable* — spill churn, victim re-touch
quality, headroom, bytes over the wire — but the decisions themselves
stayed blind: victims were picked purely by (priority, id) order,
unspill was reactive, a slow reduce side could balloon host memory, and
the shuffle codec was fixed at plan time.  This package closes the
measure->act loop with four policies behind ONE master switch
(`spark.rapids.sql.tpu.policy.enabled`; the kill switch is byte-identical
to the pre-policy engine):

  * next-use spill victim selection (engine.py MovementPolicy): the
    stores' `synchronous_spill` ranks victims by a next-use score built
    from AQE map-output read order, shuffle-partition liveness (dead vs.
    about to be read) and the ledger's re-touch history;
  * proactive unspill (engine.py): a per-runtime policy thread unspills
    soon-needed spilled buffers while headroom exists, charged to the
    owning query's scope so it can never cause another query's OOM;
  * end-to-end flow control (flow.py FlowController): map-side serve and
    `fetch_partitions_async` admission ride a windowed in-flight-bytes
    budget driven by the reduce side's observed consumption rate;
  * runtime codec re-selection (codec.py CodecAdvisor): an
    exchange proven wire-bound at runtime flips none->lz4/zstd through
    the PR 5 negotiation path for subsequent fetches.

Every decision is journaled (journal kind `policy`) and counted;
`python -m spark_rapids_tpu.metrics --memory` replays the decision
stream from journal shards alone (metrics/memledger.py).
"""
from .codec import CodecAdvisor
from .engine import MovementPolicy
from .flow import FlowController

__all__ = ["CodecAdvisor", "FlowController", "MovementPolicy"]
