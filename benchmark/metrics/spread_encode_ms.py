"""Host time of the topology spread encodes (`cc.encode.spread`, self
time), in ms an answer."""

import program_spans


def read(ctx):
    return program_spans.ms_per_answer(ctx, "cc.encode.spread")
