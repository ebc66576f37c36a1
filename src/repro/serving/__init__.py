"""Anytime serving: deadline->rho control, batched streams, doc sharding,
Lq-bucketed executables, the continuous-batching admission queue, and the
mutable-index lifecycle (tombstone-masked serve steps, hot-swap compaction)."""
from repro.serving.bucketing import (  # noqa: F401
    bucket_for,
    bucketize_batch,
    effective_lq,
    normalize_buckets,
    pad_to_width,
    sentinel_rows,
)
from repro.serving.counters import CounterRegistry  # noqa: F401
from repro.serving.pod import (  # noqa: F401
    PodFrontEnd,
    PodResult,
    PodServer,
    pod_hosts,
    warmup_pod,
)
from repro.serving.queue import (  # noqa: F401
    AdmissionQueue,
    Completion,
    FlushRecord,
    SurvivorPredictor,
)
from repro.serving.lifecycle import (  # noqa: F401
    CompactionPolicy,
    Compactor,
    MutationEvent,
    replay_with_churn,
)
from repro.serving.scheduler import (  # noqa: F401
    AnytimeServer,
    ServingConfig,
    index_static_signature,
    run_query_stream,
)
from repro.serving.sharded import (  # noqa: F401
    abstract_stacked_index,
    make_bucketed_serve_step,
    make_pod_serve_step,
    make_sharded_serve_step,
    place_index_stack,
    shard_corpus,
    shard_live_stack,
    stack_indexes,
)
