"""Device time of the fast path's program per answer, in ms.  Read only
where the window ran no scan kernel."""

import reduce_trace


def read(ctx):
    return reduce_trace.fast_path_ms_per_answer(ctx)
