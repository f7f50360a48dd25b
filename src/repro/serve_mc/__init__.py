"""Sampling-as-a-service over the batched SweepEngine (DESIGN.md §Service).

    server = SampleServer(model, slots=8, chunk_sweeps=8, backend="pallas")
    server.submit(AnnealJob.constant(seed=1, sweeps=64, beta=1.2))
    server.submit(PTJob(seed=2, betas=ladder, num_rounds=16))
    results = server.drain()      # JobResult: spins, energy, magnetization

Jobs pack into replica slots of ONE resident engine; every chunk of
sweeps is a single (on pallas: fused) launch for all of them.
"""

from repro.serve_mc.jobs import AnnealJob, JobResult, PTJob
from repro.serve_mc.scheduler import (
    AdaptiveChunker,
    AdmissionPolicy,
    PlacementPlanner,
    PriorityBackfillPolicy,
    ProfilerError,
    SampleServer,
    ServeConfig,
    SlotPool,
    make_policy,
)
from repro.serve_mc.snapshot import restore_server, save_snapshot, snapshot_state

__all__ = [
    "AdaptiveChunker",
    "AdmissionPolicy",
    "AnnealJob",
    "JobResult",
    "PTJob",
    "PlacementPlanner",
    "PriorityBackfillPolicy",
    "ProfilerError",
    "SampleServer",
    "ServeConfig",
    "SlotPool",
    "make_policy",
    "restore_server",
    "save_snapshot",
    "snapshot_state",
]
