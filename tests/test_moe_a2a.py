"""Explicit all-to-all MoE dispatch (models/moe_a2a.py): numerics vs the dense
oracle on a real multi-device mesh (subprocess: 8 host devices), plus the
single-device fallback path."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.models import moe, moe_a2a, param


def test_fallback_single_device_matches_gspmd_path():
    """t % (dp*tp) != 0 or trivial mesh -> falls back to moe.apply."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = moe.MoEConfig(d_model=32, d_ff=16, n_experts=8, top_k=2,
                        capacity_factor=8.0, group_size=32)
    params = param.init_params(moe.specs(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    y1, _ = moe_a2a.apply(params, cfg, x, mesh)
    y2, _ = moe.apply(params, cfg, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


A2A_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_host_mesh
    from repro.models import moe, moe_a2a, param

    mesh = make_host_mesh(model=4)   # (2, 4) over the 8 host devices
    cfg = moe.MoEConfig(d_model=32, d_ff=16, n_experts=8, top_k=2,
                        capacity_factor=8.0, group_size=32)
    params = param.init_params(moe.specs(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (4, 16, 32))

    y_ref = moe.dense_reference(params, cfg, x)
    y, aux = jax.jit(lambda p, x: moe_a2a.apply(p, cfg, x, mesh))(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)
    assert 0.5 < float(aux) < 4.0, aux

    # a2a ops really appear in the compiled program
    compiled = jax.jit(lambda p, x: moe_a2a.apply(p, cfg, x, mesh)[0]).lower(
        params, x).compile()
    assert "all-to-all" in compiled.as_text(), "expected explicit a2a dispatch"

    # grads flow through the dispatch
    g = jax.grad(lambda p: jnp.sum(moe_a2a.apply(p, cfg, x, mesh)[0] ** 2))(params)
    assert all(bool(jnp.isfinite(v).all()) for v in jax.tree.leaves(g))
    print("A2A_OK")
""")
@pytest.mark.slow
def test_a2a_matches_dense_oracle_on_mesh():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", A2A_SCRIPT.format(src=src)],
                         capture_output=True, text=True, timeout=420)
    assert "A2A_OK" in out.stdout, (out.stdout[-1000:], out.stderr[-2000:])
