"""Seconds from process start to the window's start: data generation,
the warm-up query and any compilation (host clock)."""


def read(ctx):
    return ctx.setup_s
