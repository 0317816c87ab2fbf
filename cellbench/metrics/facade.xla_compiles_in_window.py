"""XLA executables the process compiled, or loaded from the persistent
cache, inside the window: the program's own process-wide counter
(repro.retriever.xla_compile_count, fed by jax.monitoring's compile
events), read for the window's bounds after the run.  Every shape is
warmed in set-up, so this reads 0; unlike facade.compiles_in_window it
also sees a compile that no Python retrace announced.  A program without
the counter reports nothing."""


def read(ctx):
    try:
        from repro.retriever import xla_compile_count
    except ImportError:
        return None
    rec = ctx.record
    return (xla_compile_count(since=rec.t_start)
            - xla_compile_count(since=rec.t_end))
