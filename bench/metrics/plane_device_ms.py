"""Device time of the round program's ops under the ``ltadmm.compress``
and ``ltadmm.update`` named scopes (error feedback, compression and
the quantizer, receiver mirrors, decompression and the dual update:
the passes over the ``[A, S, N]`` plane), per round of the traced
window (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.phase_ms(ctx, "ltadmm.compress", "ltadmm.update")
