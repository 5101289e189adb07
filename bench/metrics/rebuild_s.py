"""REBUILD seconds per factorization (``RecoveryEvent.elapsed_s``, synced
with ``block_until_ready`` on both sides), median over the window."""
import statistics


def read(ctx):
    if not ctx.telemetry.get("kill"):
        return None
    per = [s for s in ctx.telemetry["rebuild_s"] if s > 0]
    return statistics.median(per) if per else None
