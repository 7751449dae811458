"""The HLO roofline analyzer, validated against known-answer programs.

Key validations (DESIGN.md §6):
* scanned vs unrolled: trip-count scaling recovers the unrolled FLOPs;
* collective bytes match hand-computed ring formulas for an explicit
  psum program;
* the raw ``cost_analysis()`` flops really do count the while body once
  (the artifact that motivates the custom walker).
"""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.launch.roofline import analyze_hlo, roofline_terms

D = 64


def _flops_of(fn, *args) -> tuple[float, float]:
    compiled = jax.jit(fn).lower(*args).compile()
    a = analyze_hlo(compiled.as_text(), n_devices=1)
    ca = compiled.cost_analysis()
    if isinstance(ca, list):        # older jax returns [dict], newer dict
        ca = ca[0] if ca else {}
    raw = ca.get("flops", 0.0)
    return a.flops, raw


def test_single_matmul_flops_exact():
    x = jnp.ones((8, D), jnp.float32)
    w = jnp.ones((D, D), jnp.float32)
    flops, _ = _flops_of(lambda a, b: a @ b, x, w)
    assert flops == pytest.approx(2 * 8 * D * D, rel=0.01)


def test_scan_flops_match_unrolled():
    n = 7
    ws = jnp.ones((n, D, D), jnp.float32)
    x = jnp.ones((8, D), jnp.float32)

    def scanned(ws, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, ws)
        return h

    def unrolled(ws, x):
        h = x
        for i in range(n):
            h = jnp.tanh(h @ ws[i])
        return h

    f_scan, raw_scan = _flops_of(scanned, ws, x)
    f_unr, _ = _flops_of(unrolled, ws, x)
    assert f_scan == pytest.approx(f_unr, rel=0.05)
    # and the raw cost_analysis undercounts the scanned one (body once)
    assert raw_scan < f_scan / 2


def test_nested_scan_trip_scaling():
    inner, outer = 3, 5
    ws = jnp.ones((outer, inner, D, D), jnp.float32)
    x = jnp.ones((4, D), jnp.float32)

    def fn(ws, x):
        def outer_body(h, w_in):
            def inner_body(h2, w):
                return h2 @ w, None
            h, _ = jax.lax.scan(inner_body, h, w_in)
            return h, None
        h, _ = jax.lax.scan(outer_body, x, ws)
        return h

    flops, _ = _flops_of(fn, ws, x)
    assert flops == pytest.approx(2 * 4 * D * D * inner * outer, rel=0.05)


def test_memory_bytes_scale_with_scan():
    n = 9
    xs = jnp.ones((n, 128, 128), jnp.float32)

    def fn(xs):
        def body(c, x):
            return c + x * 2.0, None
        c, _ = jax.lax.scan(body, jnp.zeros((128, 128)), xs)
        return c

    compiled = jax.jit(fn).lower(xs).compile()
    a = analyze_hlo(compiled.as_text(), n_devices=1)
    # each step reads + writes ≥ one (128,128) f32 tile
    assert a.hbm_bytes >= n * 128 * 128 * 4 * 2


_COLLECTIVE_PROBE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.roofline import analyze_hlo

    mesh = jax.make_mesh((8,), ("d",))
    X = jax.ShapeDtypeStruct((8, 1024), jnp.float32,
                             sharding=NamedSharding(mesh, P("d", None)))

    def fn(x):
        # one full all-reduce of a (1024,) f32 vector over 8 devices
        return jax.lax.with_sharding_constraint(
            x.sum(axis=0, keepdims=True),
            NamedSharding(mesh, P(None, None)))

    compiled = jax.jit(fn).lower(X).compile()
    a = analyze_hlo(compiled.as_text(), n_devices=8)
    # ring all-reduce: 2 * size * (g-1)/g per device
    expect = 2 * 1024 * 4 * 7 / 8
    assert a.collective_by_kind.get("all-reduce", 0) == expect, \\
        (a.collective_by_kind, expect)
    print("COLLECTIVE_OK")
""")


def test_collective_bytes_hand_computed():
    """Run in a subprocess so the 8-device flag can't leak into the
    single-device test session."""
    r = subprocess.run([sys.executable, "-c", _COLLECTIVE_PROBE],
                       capture_output=True, text=True, cwd=".",
                       timeout=300)
    assert "COLLECTIVE_OK" in r.stdout, (r.stdout, r.stderr)


def test_roofline_terms_math():
    class A:
        flops = 197e12          # exactly one second of compute
        hbm_bytes = 819e9 / 2   # half a second of HBM
        collective_bytes = 0.0
        collective_by_kind = {}
        collective_count = 0

    t = roofline_terms(A(), n_chips=4, model_flops_total=4 * 197e12)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(0.5)
    assert t.dominant == "compute"
    assert t.useful_ratio == pytest.approx(1.0)


def test_dominant_term_selection():
    class A:
        flops = 1.0
        hbm_bytes = 819e9 * 3
        collective_bytes = 0.0
        collective_by_kind = {}
        collective_count = 0

    t = roofline_terms(A(), 1, 1.0)
    assert t.dominant == "memory"
