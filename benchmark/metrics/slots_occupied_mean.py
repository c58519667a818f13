"""Slots live or prefilling, mean over the window's fences."""


def read(ctx):
    return ctx["slots_occupied_mean"]
