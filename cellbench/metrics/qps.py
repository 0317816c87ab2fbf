"""Queries answered inside the window over the window's length."""
from harness import traffic


def read(ctx):
    return traffic.completed_in_window(ctx.record) / ctx.record.seconds
