"""Seconds from process start to the window: server and JAX start, the
archive made and ingested, decode shapes warmed, the cache filled where the
mix asks for it."""


def read(ctx):
    return ctx.setup_s
