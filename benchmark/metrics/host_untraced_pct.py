"""The window's share, in %, that no `cc.` span of the program covers on
any thread: the client and the framework between the program's layers."""

import program_spans


def read(ctx):
    red = program_spans.of_run(ctx)
    if red is None:
        return None
    return 100.0 * red["program_outside_s"] / red["window_s"]
