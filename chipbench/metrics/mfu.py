"""Model step (``fed/dpasgd.py``, ``models/``): the whole round's share
of the chips' bf16 peak.  Analytic training FLOPs of the rounds run in
the traced window (three forward passes, no recomputation counted) over
the window's seconds, the chips and the peak, in percent."""


def read(facts):
    if facts.rounds == 0:
        return None
    achieved = facts.flops_per_round * facts.rounds / facts.trace.window_s()
    return 100.0 * achieved / (facts.chips * facts.peak.bf16_flops)
