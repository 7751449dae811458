"""Output tokens that requests received inside the window, over the
window's length."""


def read(rec):
    n = sum(1 for s in rec.run.served for t in s.stamps if t < rec.seconds)
    return n / rec.seconds
