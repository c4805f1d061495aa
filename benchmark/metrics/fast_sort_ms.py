"""Host time of the fast path's stable sort of the score vector
(`cc.fast.sort`, self time), in ms an answer."""

import program_spans


def read(ctx):
    return program_spans.ms_per_answer(ctx, "cc.fast.sort")
