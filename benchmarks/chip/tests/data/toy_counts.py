"""Counts that a test-only configuration names (``toy.json``), as a
configuration of another architecture names its own: round numbers that
a test can count by hand, and a latent cache of 512 + 64 bfloat16 values
a position a layer."""


def prefill_flops(m, n):
    return 1e6 * n


def decode_flops(m, keys):
    return 1e3 * keys


def weight_bytes(m):
    return 2e6


def kv_bytes(m, keys):
    return float(m["n_layers"] * (512 + 64) * 2 * keys)
