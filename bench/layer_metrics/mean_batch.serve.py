"""Requests per batch the services ran in the window, from their
``stats()`` counters (admission and batching layer)."""


def read(ctx):
    counts = ctx["counts"]
    if not counts["batches"]:
        return None
    return counts["requests"] / counts["batches"]
