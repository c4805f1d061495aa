"""Host time from the encoded problem to the first kernel or scan chunk
(`cc.setup`, self time: static config, consts, carry, the capacity
bracket, runner build and pack), in ms an answer."""

import program_spans


def read(ctx):
    return program_spans.ms_per_answer(ctx, "cc.setup")
