"""Bytes sent per round by the busiest agent, as the program's telemetry
counted them over the window call (``tx_bytes`` over ``rounds``).

The counter is a uint32 and wraps every 2**32 bytes, so over a window it
gives the count modulo 2**32 only.  The number of wraps is taken from
the program's own analytic count (``wire_bytes`` x rounds): the multiple
of 2**32 nearest it.  What guards that step is ``wire_gap`` in
``bench/check.py``, which holds the same counter, unwrapped the same
way, to the reference's own count of message bytes (limit 0)."""

from bench.check import unwrap


def read(ctx):
    tel = ctx.telemetry
    if not tel or not tel.get("rounds"):
        return None
    rounds = tel["rounds"]
    return unwrap(max(tel["tx_bytes"]), rounds * ctx.wire_hint) / rounds
