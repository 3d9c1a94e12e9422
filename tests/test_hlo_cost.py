"""The trip-count-aware HLO cost model vs ground truth on known programs."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_cost import analyze


def _cost(f, *args):
    co = jax.jit(f).lower(*args).compile()
    return analyze(co.as_text()), co


def test_scan_flops_match_unrolled():
    a = jnp.ones((128, 128))

    def scanned(x):
        def body(c, _):
            return c @ a, None
        y, _ = jax.lax.scan(body, x, None, length=12)
        return y.sum()

    def unrolled(x):
        for _ in range(12):
            x = x @ a
        return x.sum()

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    cs, _ = _cost(scanned, x)
    cu, _ = _cost(unrolled, x)
    # trip-weighted scan flops must match the unrolled program (XLA's own
    # cost_analysis is ~12x off here — the whole reason this module exists)
    assert abs(cs["flops"] - cu["flops"]) / cu["flops"] < 0.02
    expected = 2 * 128**3 * 12
    assert abs(cu["flops"] - expected) / expected < 0.05


def test_matmul_flops_exact():
    def f(a, b):
        return a @ b

    c, _ = _cost(f, jax.ShapeDtypeStruct((64, 32), jnp.float32), jax.ShapeDtypeStruct((32, 16), jnp.float32))
    expected = 2 * 64 * 32 * 16
    assert abs(c["flops"] - expected) / expected < 0.05


def test_nested_scan_multiplies():
    a = jnp.ones((64, 64))

    def nested(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ a, None
            c, _ = jax.lax.scan(inner, c, None, length=5)
            return c, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y.sum()

    c, _ = _cost(nested, jax.ShapeDtypeStruct((64, 64), jnp.float32))
    expected = 2 * 64**3 * 15
    assert abs(c["flops"] - expected) / expected < 0.1


def test_dynamic_update_slice_bytes_not_inflated():
    """DUS into a big buffer must count the update region, not the buffer."""
    def f(buf, upd):
        def body(c, i):
            return jax.lax.dynamic_update_slice_in_dim(c, upd, i * 4, axis=0), None
        out, _ = jax.lax.scan(body, buf, jnp.arange(64))
        return out

    buf = jax.ShapeDtypeStruct((4096, 1024), jnp.float32)
    upd = jnp.ones((4, 1024), jnp.float32)
    c, _ = _cost(f, buf, upd)
    # 64 trips x 2*(4*1024*4B) = 2.1MB; buffer itself is 16MB — stay well under
    # a "buffer re-read per trip" interpretation (64 * 16MB = 1GB)
    assert c["bytes"] < 3e8


def test_collectives_parsed_with_groups(tmp_path):
    import subprocess, sys, os, json, textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, json
        from jax.sharding import PartitionSpec as PS, NamedSharding
        from repro.launch.hlo_cost import analyze
        mesh = jax.make_mesh((8,), ("d",))
        def f(x):
            return jax.shard_map(lambda a: jax.lax.psum(a, "d"), mesh=mesh,
                             in_specs=PS("d"), out_specs=PS())(x)
        x = jax.ShapeDtypeStruct((1024, 64), jnp.float32)
        co = jax.jit(f).lower(x).compile()
        c = analyze(co.as_text())
        print(json.dumps({k: v["count"] for k, v in c["coll"].items()}))
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                       env=dict(os.environ, PYTHONPATH="src"), timeout=300,
                       cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
    counts = json.loads(r.stdout.strip().splitlines()[-1])
    assert counts["all-reduce"] >= 1
