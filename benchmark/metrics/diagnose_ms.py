"""Host time of the fail-reason diagnosis at a refused pod (`cc.diagnose`,
self time), in ms an answer."""

import program_spans


def read(ctx):
    return program_spans.ms_per_answer(ctx, "cc.diagnose")
