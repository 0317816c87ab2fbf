"""jit traces the retriever made during the window (trace_count after
minus before): every shape is warmed in set-up, so this reads 0."""


def read(ctx):
    return ctx.compiles_in_window
