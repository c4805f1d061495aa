"""Host time of the kernels' cross-checks against the XLA step and their
bookkeeping (`cc.verify`, self time), in ms an answer."""

import program_spans


def read(ctx):
    return program_spans.ms_per_answer(ctx, "cc.verify")
