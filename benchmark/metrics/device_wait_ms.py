"""Host time blocked on device results (`cc.wait`, self time), in ms an
answer."""

import program_spans


def read(ctx):
    return program_spans.ms_per_answer(ctx, "cc.wait")
