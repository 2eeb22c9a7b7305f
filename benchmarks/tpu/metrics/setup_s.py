"""Seconds from process start to the start of the window: imports,
plan_sync, compile or cache load, state init and warm-up steps."""


def read(ctx):
    return ctx["set_up_seconds"]
