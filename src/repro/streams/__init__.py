from repro.streams.alerts import (
    AlertSet,
    AlertSpec,
    AlertStats,
    FiredBatch,
    PollOracle,
)
from repro.streams.ingest import IngestPipeline, IngestStats
from repro.streams.traces import (
    Trace,
    zipf_frequencies,
    generate_trace,
    shift_workload,
    batched_playback,
)

__all__ = [
    "Trace",
    "zipf_frequencies",
    "generate_trace",
    "shift_workload",
    "batched_playback",
    "IngestPipeline",
    "IngestStats",
    "AlertSpec",
    "AlertSet",
    "AlertStats",
    "FiredBatch",
    "PollOracle",
]
