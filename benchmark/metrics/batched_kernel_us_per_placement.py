"""Device time of the batched sweep kernel per placement, in us: the
kernel's op time in the traced window over the placements made in it.
Read only where the window ran that kernel and not the single-template
one."""

import reduce_trace


def read(ctx):
    return reduce_trace.kernel_us_per_placement(ctx, "batched_chunks")
