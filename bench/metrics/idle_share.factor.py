"""Device idle share under the orchestrator's host loop (one blocking poll
per sweep boundary): 1 - (union of device operation intervals) / the traced
window, in %."""
from bench import trace_reduce


def read(ctx):
    share = trace_reduce.idle_share(ctx.trace)
    return None if share is None else 100.0 * share
