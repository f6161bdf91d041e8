"""Device time of the round program's ops under the ``ltadmm.exchange``
named scope (the message routing; on one chip a host-simulated gather),
per round of the traced window (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.phase_ms(ctx, "ltadmm.exchange")
