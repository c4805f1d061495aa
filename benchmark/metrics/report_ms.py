"""Host time of building the report from the solve results (`cc.report`,
self time), in ms an answer."""

import program_spans


def read(ctx):
    return program_spans.ms_per_answer(ctx, "cc.report")
