"""Control `bfloat16`: the reference computed in bfloat16, the precision
below the float32 the configurations state.  The default where a traffic
file names no control."""

import ml_dtypes


def apply(pods: list, templates: list):
    return pods, templates, ml_dtypes.bfloat16
