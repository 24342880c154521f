"""repro.dist unit tests: collective identities and compression
accounting. Multi-device collective
correctness runs in a subprocess with 8 fake devices (slow lane) — the main
test process must keep its single CPU device."""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.compression import (CompressionConfig, compress_with_feedback,
                                    compression_ratio, init_error_feedback,
                                    topk_sparsify)


# -- collectives (single device: identity) -----------------------------------

def test_collective_identities_single_device():
    from repro.dist.collectives import (hierarchical_all_reduce,
                                        reduce_scatter, ring_all_gather,
                                        ring_all_reduce)
    mesh = jax.make_mesh((1,), ("data",))
    x = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    for fn in (lambda v: ring_all_reduce(v, mesh, "data"),
               lambda v: reduce_scatter(v, mesh, "data"),
               lambda v: ring_all_gather(v, mesh, "data")):
        np.testing.assert_allclose(np.asarray(fn(x)), np.asarray(x))
    mesh2 = jax.make_mesh((1, 1), ("data", "model"))
    np.testing.assert_allclose(
        np.asarray(hierarchical_all_reduce(x, mesh2, "model", "data")),
        np.asarray(x))


_COLLECTIVE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.dist.collectives import (hierarchical_all_reduce,
                                        reduce_scatter, ring_all_gather,
                                        ring_all_reduce)
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 5)), jnp.float32)
    out = {}
    # ring all-reduce over data: sum of the 4 contribution slices
    r = ring_all_reduce(x, mesh, "data")
    ref = np.asarray(x).reshape(4, 4, 5).sum(0)
    out["ring"] = float(np.abs(np.asarray(r) - ref).max())
    # hierarchical: intra-model then inter-data ring == sum of 8 slices
    h = hierarchical_all_reduce(x, mesh, "model", "data")
    ref8 = np.asarray(x).reshape(8, 2, 5).sum(0)
    out["hier"] = float(np.abs(np.asarray(h) - ref8).max())
    # reduce-scatter + all-gather round trip == all-reduce
    rs = reduce_scatter(x, mesh, "data")
    ag = ring_all_gather(rs, mesh, "data")
    out["rs_ag"] = float(np.abs(np.asarray(ag) - ref).max())
    # non-divisible contribution rows must be rejected, not duplicated
    try:
        reduce_scatter(x[:8], mesh, "data")  # 2 rows/device, 4-way axis
        out["rs_guard"] = "missing"
    except ValueError:
        out["rs_guard"] = "raised"
    print("RESULT:" + json.dumps(out))
""")


@pytest.mark.slow
def test_ring_collectives_multi_device_subprocess():
    res = subprocess.run([sys.executable, "-c", _COLLECTIVE_SCRIPT],
                         capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [ln for ln in res.stdout.splitlines()
            if ln.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert out["ring"] < 1e-5, out
    assert out["hier"] < 1e-5, out
    assert out["rs_ag"] < 1e-5, out
    assert out["rs_guard"] == "raised", out


# -- compression -------------------------------------------------------------

def test_topk_sparsify_keeps_largest():
    g = jnp.asarray([0.1, -5.0, 0.2, 3.0, -0.05])
    out = np.asarray(topk_sparsify(g, 2))
    np.testing.assert_allclose(out, [0.0, -5.0, 0.0, 3.0, 0.0])


def test_compression_residual_bounded_every_step():
    rng = np.random.default_rng(1)
    g = {"w": jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)}
    err = init_error_feedback(g)
    for _ in range(20):
        sent, err = compress_with_feedback(g, err)
        # error feedback holds only the int8 quantization residual
        assert float(jnp.abs(err["w"]).max()) < 0.05
        assert sent["w"].shape == g["w"].shape


def test_compression_bf16_cast_error_fed_back():
    """Low-precision gradients: the bf16 rounding of the transmitted value
    must enter the error feedback, or it accumulates uncorrected."""
    rng = np.random.default_rng(3)
    g32 = rng.standard_normal(512).astype(np.float32)
    g = {"w": jnp.asarray(g32, jnp.bfloat16)}
    err = init_error_feedback(g)
    total_true = np.zeros(512, np.float64)
    total_sent = np.zeros(512, np.float64)
    for _ in range(50):
        total_true += np.asarray(g["w"], np.float64)
        sent, err = compress_with_feedback(g, err)
        assert sent["w"].dtype == jnp.bfloat16
        total_sent += np.asarray(sent["w"], np.float64)
    assert np.abs(total_true - total_sent).max() < 0.1


def test_compression_is_jittable():
    g = {"w": jnp.ones(64)}
    err = init_error_feedback(g)
    sent, new_err = jax.jit(compress_with_feedback)(g, err)
    np.testing.assert_allclose(np.asarray(sent["w"] + new_err["w"]),
                               np.asarray(g["w"]), atol=1e-6)


def test_compression_ratio_scales_with_bits():
    g = {"w": jnp.ones(4096)}
    r8 = compression_ratio(g)
    r4 = compression_ratio(g, CompressionConfig(residual_bits=4))
    assert r4 > r8 > 3.5
