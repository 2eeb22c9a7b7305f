"""Validation of the trip-count-aware HLO cost model (the §Roofline
measurement instrument): exact on known-flop programs, exact loop
scaling, collective conventions."""
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.jax]

_PROBE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.launch.hlo_cost import analyze_text

out = {}

# 1) scan of 7 matmuls 64^3: flops must scale by trip count
def f(x, w):
    def body(c, wi):
        return jnp.tanh(c @ wi), jnp.sum(c)
    c, s = jax.lax.scan(body, x, w)
    return c.sum() + s.sum()
comp = jax.jit(f).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32),
                        jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)
                        ).compile()
out["scan_flops"] = analyze_text(comp.as_text()).flops

# 2) plain matmul: must match XLA's own cost_analysis exactly
def g(a, b):
    return a @ b
comp2 = jax.jit(g).lower(jax.ShapeDtypeStruct((128, 256), jnp.float32),
                         jax.ShapeDtypeStruct((256, 512), jnp.float32)
                         ).compile()
xc = comp2.cost_analysis()
xc = xc[0] if isinstance(xc, list) else xc
out["matmul_flops"] = analyze_text(comp2.as_text()).flops
out["matmul_flops_xla"] = float(xc["flops"])

# 3) psum inside a scan: collective bytes scale by trips
mesh = make_mesh((8,), ("d",))
def h(xs):
    def body(c, x):
        y = jax.shard_map(lambda v: jax.lax.psum(v, "d"), mesh=mesh,
                          in_specs=P("d"), out_specs=P())(x)
        return c + y.sum(), None
    return jax.lax.scan(body, 0.0, xs)[0]
comp3 = jax.jit(h).lower(
    jax.ShapeDtypeStruct((5, 64), jnp.float32)).compile()
out["scan_coll"] = analyze_text(comp3.as_text()).coll

# 4) nested scans: multiplicative trip scaling
def nest(x, w):
    def outer(c, _):
        def inner(ci, wi):
            return ci @ wi, None
        c2, _ = jax.lax.scan(inner, c, w)
        return c2, None
    return jax.lax.scan(outer, x, None, length=3)[0].sum()
comp4 = jax.jit(nest).lower(jax.ShapeDtypeStruct((32, 32), jnp.float32),
                            jax.ShapeDtypeStruct((5, 32, 32), jnp.float32)
                            ).compile()
out["nested_flops"] = analyze_text(comp4.as_text()).flops
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def probe():
    import json
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run([sys.executable, "-c", _PROBE],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_peaks_are_keyed_by_device_kind():
    from repro.launch.hlo_analysis import V5E, peaks
    assert peaks(V5E).flops == 197e12 and peaks(V5E).hbm_bw == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        peaks("TPU v9")


def test_scan_flops_scaled_by_trip_count(probe):
    assert probe["scan_flops"] == 7 * 2 * 64 ** 3


def test_plain_matmul_matches_xla(probe):
    assert probe["matmul_flops"] == probe["matmul_flops_xla"]
    assert probe["matmul_flops"] == 2 * 128 * 256 * 512


def test_collectives_scaled_by_trip_count(probe):
    # psum of 64 f32 on 8 devices: all-reduce convention 2x input bytes,
    # per shard input = 8 f32 = 32B -> 64B x 5 trips = 320
    assert probe["scan_coll"] == {"all-reduce": 320.0}


def test_nested_scan_multiplicative(probe):
    assert probe["nested_flops"] == 3 * 5 * 2 * 32 ** 3
