"""Seconds the replicated orchestrator's event loop took per 1,000 tasks
assigned in the window: the wall time of its `loop.replicated-orchestrator`
spans (one per drained event burst) that end inside the window."""
import span_records


def read(ctx):
    return span_records.wall_per_ktask(ctx, {"loop.replicated-orchestrator"})
