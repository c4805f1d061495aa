"""Host time of the per-question encode (`cc.encode`, inclusive of the
spread and affinity encodes inside it), in ms an answer."""

import program_spans


def read(ctx):
    return program_spans.ms_per_answer(ctx, "cc.encode", inclusive=True)
