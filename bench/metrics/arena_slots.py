"""Paged arena (``serving/arena.py``): decode slots per group after the
capacity fit to device memory.  Source: ``runtime.groups[*].arena
.capacity``, as the launcher reads it."""


def read(rec):
    return rec.slots
