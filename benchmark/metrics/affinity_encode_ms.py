"""Host time of the inter-pod affinity encode (`cc.encode.affinity`, self
time), in ms an answer."""

import program_spans


def read(ctx):
    return program_spans.ms_per_answer(ctx, "cc.encode.affinity")
