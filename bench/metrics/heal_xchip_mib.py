"""Bytes a heal moved from one chip to another, per factorization
(``RecoveryEvent.xchip_bytes``, counted where the heal issues each
transfer), median over the window, in MiB. A heal that gathered the state
would count the gather."""
import statistics


def read(ctx):
    per = ctx.telemetry.get("heal_xchip_bytes")
    if not ctx.telemetry.get("kill") or not per:
        return None
    return statistics.median(per) / 2**20
