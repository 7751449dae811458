"""A reference that a test-only configuration names (``toy.json``), as a
configuration of another architecture names its own: the dense
reference's equations under an ``Arch`` of this module, with every call
recorded, so a test sees that the harness judged by this module."""

from benchmarks.chip import reference

#: (tokens, rows, quant) of each call
CALLS: list = []


class Arch(reference.Arch):
    pass


def logits_at(params, tokens, rows, a, quant=False):
    if not isinstance(a, Arch):
        raise TypeError(f"judged with another module's Arch: {type(a)}")
    CALLS.append((len(tokens), len(rows), quant))
    return reference.logits_at(params, tokens, rows, a, quant=quant)
