"""Device time of the round program's ops under the ``ltadmm.local``
named scope (each agent's SVRG anchor and ``tau`` local steps), per
round of the traced window (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.phase_ms(ctx, "ltadmm.local")
