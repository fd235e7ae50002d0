"""HLO cost walker: trip-count awareness verified against hand-computed
flops and wire bytes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_walker import module_cost, _shape_bytes


def test_shape_bytes():
    assert _shape_bytes("bf16[4,8]") == 64
    assert _shape_bytes("f32[10]{0}") == 40
    assert _shape_bytes("(f32[2,2], s32[3])") == 28
    assert _shape_bytes("pred[]") == 1


def _scanned(w, x, n):
    def body(c, wi):
        return jnp.tanh(c @ wi), None
    out, _ = jax.lax.scan(body, x, w)
    return out


def test_walker_multiplies_scan_flops():
    n, b, d = 8, 4, 64
    w = jax.ShapeDtypeStruct((n, d, d), jnp.float32)
    x = jax.ShapeDtypeStruct((b, d), jnp.float32)
    comp = jax.jit(lambda w, x: _scanned(w, x, n)).lower(w, x).compile()
    cost = module_cost(comp.as_text(), 1)
    true_flops = n * 2 * b * d * d
    assert cost.flops == pytest.approx(true_flops, rel=1e-6)
    # XLA's own analysis undercounts by the trip count
    assert comp.cost_analysis()["flops"] < true_flops / 2


def test_walker_matches_unrolled():
    b, d, n = 4, 32, 6
    w = jax.ShapeDtypeStruct((n, d, d), jnp.float32)
    x = jax.ShapeDtypeStruct((b, d), jnp.float32)

    def unrolled(w, x):
        for i in range(n):
            x = jnp.tanh(x @ w[i])
        return x

    c_scan = module_cost(jax.jit(lambda w, x: _scanned(w, x, n))
                         .lower(w, x).compile().as_text(), 1)
    c_unrl = module_cost(jax.jit(unrolled).lower(w, x).compile().as_text(), 1)
    assert c_scan.flops == pytest.approx(c_unrl.flops, rel=1e-6)


def test_walker_nested_scans_multiply():
    d = 32
    w = jax.ShapeDtypeStruct((3, 4, d, d), jnp.float32)
    x = jax.ShapeDtypeStruct((2, d), jnp.float32)

    def nested(w, x):
        def outer(c, wo):
            def inner(ci, wi):
                return jnp.tanh(ci @ wi), None
            c, _ = jax.lax.scan(inner, c, wo)
            return c, None
        out, _ = jax.lax.scan(outer, x, w)
        return out

    cost = module_cost(jax.jit(nested).lower(w, x).compile().as_text(), 1)
    assert cost.flops == pytest.approx(12 * 2 * 2 * d * d, rel=1e-6)


def test_walker_collective_wire_bytes_subprocess():
    import subprocess, sys, os, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.launch.hlo_walker import module_cost
        mesh = make_mesh((2, 4), ("data", "model"))
        w = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
        x = jax.ShapeDtypeStruct((4, 256), jnp.float32)
        def f(w, x):
            def body(c, wi): return jnp.tanh(c @ wi), None
            return jax.lax.scan(body, x, w)[0]
        comp = jax.jit(f, in_shardings=(
            NamedSharding(mesh, P(None, "model", None)),
            NamedSharding(mesh, P(None, "model"))),
            out_shardings=NamedSharding(mesh, P(None, "model"))
        ).lower(w, x).compile()
        c = module_cost(comp.as_text(), 8)
        # 8 iterations x ring all-reduce of (4,256) f32 over k=4:
        #   2*(k-1)/k*4096 = 6144 B/iter -> 49152 B
        assert abs(c.coll_bytes - 49152) < 1, c.coll_bytes
        assert c.coll_by_kind.get("all-reduce", 0) == c.coll_bytes
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code],
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       capture_output=True, text=True, timeout=300)
    assert "OK" in r.stdout, r.stderr[-2000:]
