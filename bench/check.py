"""The comparison that decides ``correct``: what ``train.main`` produced
against the plain reference (``bench/ref``), number by number, each
against its limit from ``bench/limits/<workload>.json``.

Numbers (each a worst case; ``gap`` means the gap between the program's
value and the reference's, over the reference's):

* ``loss_gap``: mean loss of the consensus mean at each log point of
  the checked rounds;
* ``consensus_gap``: consensus error (squared distance of the agents
  from their mean) at the same points;
* ``change_gap``: over the weight leaves, the gap between the norms of
  the consensus mean's change from the initial weights, over the larger
  of the reference's norm for that leaf and its median over leaves.
  Leaves whose first anchor gradient in the reference is under a
  thousandth of the median leaf's are left out (they move by round-off
  alone);
* ``replay_gap``: the window's own first log point against the checked
  call's, loss and consensus error: the same seed and program give the
  same numbers, so the limit is 0;
* ``wire_gap``: busiest agent's measured bytes over the window's call
  against the reference's count of quantized message bytes (exact, 0).
  The program's counter is a uint32 and wraps every 2**32 bytes, so
  the count is unwrapped to the multiple of 2**32 nearest the
  reference's: any other disagreement of up to 2**31 bytes in total
  shows;
* ``window_compiles``: compilations inside the measured window (0).
"""
from __future__ import annotations

import numpy as np

IDLE_GRAD_SHARE = 1e-3
COUNTER_SPAN = 2 ** 32  # the program's telemetry counters are uint32


def unwrap(count, near):
    """The value ``count + k * 2**32`` nearest ``near``."""
    return count + round((near - count) / COUNTER_SPAN) * COUNTER_SPAN


def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-30)


def change_gap(xbar, weights0, ref_xbar, grad0):
    """Worst-leaf gap of change norms; ``xbar`` the program's consensus
    mean, the rest the reference's, all ``{path: array}``."""
    gnorm = {k: float(np.linalg.norm(np.asarray(v, np.float64)))
             for k, v in grad0.items()}
    med_g = float(np.median(list(gnorm.values())))
    kept = [k for k in weights0 if gnorm[k] >= IDLE_GRAD_SHARE * med_g]
    prog, ref = {}, {}
    for k in kept:
        w0 = np.asarray(weights0[k], np.float64)
        prog[k] = float(np.linalg.norm(np.asarray(xbar[k], np.float64) - w0))
        ref[k] = float(np.linalg.norm(np.asarray(ref_xbar[k], np.float64) - w0))
    med = float(np.median(list(ref.values())))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in kept}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst, len(weights0) - len(kept)


def compare(prog, ref, limits):
    """-> ``{name: (value, limit)}`` in a fixed order.

    ``prog``: ``losses``, ``consensus`` (checked call, per log point),
    ``xbar`` (its consensus mean), ``replay`` (``[(loss, consensus)]``
    of the window call's and the checked call's first log point),
    ``tx_bytes``, ``rounds`` (window call's telemetry), ``compiles``.
    ``ref``: ``Reference.run``'s result plus ``wire_per_round``.
    """
    if len(prog["xbar"]) != len(ref["xbar"]) or set(prog["xbar"]) != set(
            ref["xbar"]):
        raise ValueError("the program's weights do not match the "
                         "reference's leaves")
    n = len(ref["losses"])
    if len(prog["losses"]) < n or len(prog["consensus"]) < n:
        raise ValueError("the program logged fewer points than checked")
    loss = max(_rel(p, r) for p, r in zip(prog["losses"], ref["losses"]))
    cons = max(_rel(p, r) for p, r in zip(prog["consensus"], ref["consensus"]))
    change, _, _ = change_gap(prog["xbar"], ref["weights0"], ref["xbar"],
                              ref["grad0"])
    (lw, cw), (lc, cc) = prog["replay"]
    replay = max(abs(lw - lc), abs(cw - cc))
    due = prog["rounds"] * ref["wire_per_round"]
    wire = abs(unwrap(prog["tx_bytes"], due) - due)
    values = {
        "loss_gap": loss,
        "consensus_gap": cons,
        "change_gap": change,
        "replay_gap": replay,
        "wire_gap": float(wire),
        "window_compiles": float(prog["compiles"]),
    }
    return {k: (v, float(limits[k])) for k, v in values.items()}


def reference_as_program(out):
    """A reference result in the shape ``compare`` reads as the
    program's: for reading a control or a planted fault against the
    reference."""
    return {"losses": out["losses"], "consensus": out["consensus"],
            "xbar": out["xbar"], "replay": [(0.0, 0.0), (0.0, 0.0)],
            "tx_bytes": 0, "rounds": 0, "compiles": 0}


def passed(checks) -> bool:
    return all(np.isfinite(v) and v <= lim for v, lim in checks.values())
