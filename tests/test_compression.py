"""Gradient-compression tests (distributed/compression.py)."""

import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests skip cleanly without it
from hypothesis import given, settings, strategies as st

from repro.distributed.compression import (
    CompressionConfig,
    dequantize_int8,
    quantize_int8,
)


class TestInt8Quant:
    @given(st.integers(0, 2**31 - 1), st.sampled_from([64, 128, 256]))
    @settings(max_examples=30, deadline=None)
    def test_roundtrip_error_bounded(self, seed, block):
        g = jax.random.normal(jax.random.key(seed), (777,), jnp.float32)
        q, s = quantize_int8(g, block)
        back = dequantize_int8(q, s, g.shape, g.size)
        # symmetric int8: error <= scale/2 = max|block| / 254
        err = jnp.abs(back - g)
        assert float(err.max()) <= float(jnp.abs(g).max()) / 127.0 + 1e-7

    def test_zero_tensor(self):
        g = jnp.zeros((100,), jnp.float32)
        q, s = quantize_int8(g, 64)
        back = dequantize_int8(q, s, g.shape, g.size)
        np.testing.assert_array_equal(np.asarray(back), 0.0)

    def test_wire_bytes_are_4x_smaller(self):
        g = jnp.ones((1024,), jnp.float32)
        q, s = quantize_int8(g, 256)
        wire = q.size * 1 + s.size * 4
        assert wire < g.size * 4 / 3  # >3x reduction incl. scales


MULTIDEV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import (int8_psum_mean, topk_psum_mean,
                                           CompressionConfig,
                                           compressed_mean,
                                           init_error_state)

mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
g = jax.random.normal(jax.random.key(1), (8, 512), jnp.float32)
ref = jnp.mean(g, axis=0)

f = jax.shard_map(lambda gg: int8_psum_mean(gg[0], "data")[None], mesh=mesh,
                  in_specs=P("data"), out_specs=P("data"), check_vma=False)
err = float(jnp.abs(f(g)[0] - ref).max() / (jnp.abs(ref).max() + 1e-9))
assert err < 0.05, f"int8 err {err}"

# error feedback: compressed SGD with EF tracks the true mean over steps
cfg = CompressionConfig(kind="int8", block=64)
def step(gg, ee):
    red, e2 = compressed_mean({"g": gg[0]}, {"g": ee[0]}, "data", cfg)
    return red["g"][None], e2["g"][None]
fstep = jax.shard_map(step, mesh=mesh, in_specs=(P("data"), P("data")),
                      out_specs=(P("data"), P("data")), check_vma=False)
e = jnp.zeros_like(g)
acc_c = jnp.zeros_like(ref); acc_t = jnp.zeros_like(ref)
for s in range(8):
    gs = jax.random.normal(jax.random.key(100 + s), g.shape, jnp.float32)
    red, e = fstep(gs, e)
    acc_c = acc_c + red[0]
    acc_t = acc_t + jnp.mean(gs, axis=0)
drift = float(jnp.abs(acc_c - acc_t).max() / (jnp.abs(acc_t).max() + 1e-9))
assert drift < 0.08, f"EF drift {drift}"
print("COMPRESS_OK")
"""


def test_compressed_allreduce_multidev():
    r = subprocess.run([sys.executable, "-c", MULTIDEV],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "COMPRESS_OK" in r.stdout, r.stderr[-2000:]
