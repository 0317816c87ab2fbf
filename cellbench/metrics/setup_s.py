"""Seconds from process start to the window's first timed request: the
corpus, LemurRetriever.build, the query pool, the warm-up, the ramp."""


def read(ctx):
    return ctx.setup_s
