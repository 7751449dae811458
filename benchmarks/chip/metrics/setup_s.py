"""Seconds from the start of the process to the opening of the window:
imports, weights, engine, warm-up and any compilation."""


def read(rec):
    return rec.setup_s
