"""repro.serve — plan-cached, continuously batched spectral transforms.

The serving layer over the PR 1-5 stack (ROADMAP item 2): heterogeneous
transform requests (shape x dtype x {c2c, r2c, filtered} x direction)
arrive on an async queue, are bucketed by compiled-executable identity,
stacked into the batched packed pipelines — which PR 5 made free at the
collective level: a (B, ...) stack compiles to the SAME per-stage
collective count as B=1 — and dispatched with donated buffers.

Plan selection is FFTW's planner-in-production: the first request of a
problem key pays only ``mode="wisdom"``/``"model"`` (zero execution),
a background thread upgrades hot keys with ``mode="measure"`` and merges
the winner into the wisdom store atomically, and an LRU cap with
``Croft3D.release()`` keeps the compiled-executable set bounded under
shape diversity.

    from repro.serve import TransformService
    with TransformService(mesh, max_batch=8, wisdom_path="wisdom.json",
                          measure_after=32) as svc:
        spectrum = svc.transform(field, problem="r2c")

``tests/test_serve.py::test_batched_forward_compiles_to_single_request_collectives``
holds the batching premise: a (B, ...) stacked dispatch compiles to
the single request's collective counts, with bytes scaled by B.
"""

from repro.serve.batcher import Batcher, Bucket, padded_size, stack_and_pad
from repro.serve.plan_cache import CachedPlan, CacheStats, PlanCache
from repro.serve.request import (DIRECTIONS, PRIORITIES, PRIORITY_HIGH,
                                 PRIORITY_LOW, PRIORITY_NORMAL, PROBLEMS,
                                 ShedResult, TransformRequest,
                                 TransformResult, bucket_key)
from repro.serve.service import TransformService

__all__ = [
    "Batcher", "Bucket", "CacheStats", "CachedPlan", "DIRECTIONS",
    "PRIORITIES", "PRIORITY_HIGH", "PRIORITY_LOW", "PRIORITY_NORMAL",
    "PROBLEMS", "PlanCache", "ShedResult", "TransformRequest",
    "TransformResult", "TransformService", "bucket_key", "padded_size",
    "stack_and_pad",
]
