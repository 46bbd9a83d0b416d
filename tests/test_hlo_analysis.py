"""HLO cost parser: trip-count multiplication, dot flops, collective wire
bytes (the roofline's foundation — cost_analysis() ignores loop trips)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.analysis import hlo


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_trip_count_multiplies():
    def make(n):
        def f(x, w):
            def body(c, _):
                return jax.nn.relu(c @ w), None
            out, _ = lax.scan(body, x, None, length=n)
            return out.sum()
        return f

    x = jnp.ones((128, 256))
    w = jnp.ones((256, 256))
    flops = {}
    for n in (1, 4, 8):
        cs = hlo.analyze(_compile_text(make(n), x, w))
        flops[n] = cs.flops
    dot = 2 * 128 * 256 * 256
    for n in (1, 4, 8):
        assert flops[n] == pytest.approx(n * flops[1], rel=0.02)
        assert flops[n] >= n * dot


def test_dot_flops_exact():
    f = lambda a, b: a @ b
    a = jnp.ones((64, 128))
    b = jnp.ones((128, 32))
    cs = hlo.analyze(_compile_text(f, a, b))
    assert cs.per_opcode_flops.get("dot", 0) == pytest.approx(2 * 64 * 128 * 32)


def test_batched_dot_flops():
    f = lambda a, b: jnp.einsum("bij,bjk->bik", a, b)
    a = jnp.ones((4, 16, 32))
    b = jnp.ones((4, 32, 8))
    cs = hlo.analyze(_compile_text(f, a, b))
    assert cs.per_opcode_flops.get("dot", 0) == pytest.approx(2 * 4 * 16 * 32 * 8)


def test_bytes_scale_with_data():
    f = lambda x: (x * 2.0 + 1.0).sum()
    small = hlo.analyze(_compile_text(f, jnp.ones((256, 256))))
    big = hlo.analyze(_compile_text(f, jnp.ones((1024, 256))))
    assert big.bytes > 3 * small.bytes


def test_shape_parsing():
    assert hlo.shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert hlo.shape_bytes("bf16[2,4]{1,0}") == 16
    assert hlo.shape_bytes("(s32[], f32[8]{0})") == 4 + 32
    assert hlo.shape_elems("pred[16,16]") == 256
    assert hlo.first_shape_dims("f32[3,5,7]{2,1,0}") == [3, 5, 7]


_COLLECTIVE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.analysis import hlo

    mesh = jax.make_mesh((8,), ("d",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    x = jnp.ones((1024, 256))

    def f(v):
        return jax.lax.with_sharding_constraint(
            (v * 2).sum(axis=0), P())       # cross-device reduce

    with jax.sharding.set_mesh(mesh):
        c = jax.jit(f, in_shardings=NamedSharding(mesh, P("d", None)),
                    out_shardings=NamedSharding(mesh, P())).lower(x).compile()
    cs = hlo.analyze(c.as_text(), num_partitions=8)
    assert cs.collective_bytes > 0, "expected an all-reduce"
    assert "all-reduce" in cs.collective_breakdown, cs.collective_breakdown
    # ring all-reduce of a (256,) f32: 2 * 7/8 * 1024 bytes
    want = 2 * (7 / 8) * 256 * 4
    assert abs(cs.collective_breakdown["all-reduce"] - want) / want < 0.01
    print("COLL_OK")
""")


@pytest.mark.slow
def test_collective_bytes_on_8_devices():
    r = subprocess.run([sys.executable, "-c", _COLLECTIVE_SCRIPT],
                       capture_output=True, text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert "COLL_OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


def test_group_size_parsing():
    assert hlo._group_size("replica_groups=[16,16]<=[256]", 256) == 16
    assert hlo._group_size("replica_groups={{0,1,2,3}}", 256) == 4
    assert hlo._group_size("no groups here", 256) == 256


def test_peaks_are_keyed_by_device_kind():
    """The roofline and the JigSaw cost model price a named chip from one
    table; a kind with no published peaks is an error, not a default."""
    from repro.analysis.roofline import peaks
    v5e = peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="cpu"):
        peaks("cpu")
