"""Useful share, in %, of the kernel and scan steps issued in the window:
100 * placements / sum(steps x lanes) over the `cc.issue` spans.  Steps
past a template's stop (speculative windows, lanes of a batched group
that stopped before its longest template) are the rest."""

import program_spans


def read(ctx):
    red = program_spans.of_run(ctx)
    if red is None or red["lane_steps"] <= 0:
        return None
    return 100.0 * ctx["placements"] / red["lane_steps"]
