"""moe_fwd_sharded (shard_map a2a) must equal moe_fwd_einsum exactly.

Both implementations use identical per-row capacity semantics: a token's
position within an expert's segment is its rank among that expert's tokens in
flat (s, k) order, so drops coincide and outputs match to numerics.
"""

import subprocess
import sys

SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.models import moe

# the einsum MoE is written for GSPMD's Auto sharding (bare-spec
# with_sharding_constraint); jax.make_mesh defaults to Explicit axes
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
spec_e = moe.MoeSpec(d_model=32, d_ff=16, n_experts=8, top_k=2,
                     ep_pad_to=4, batch_axes=("data",), ep_axis="model")
spec_s = moe.MoeSpec(d_model=32, d_ff=16, n_experts=8, top_k=2,
                     ep_pad_to=4, batch_axes=("data",), ep_axis="model",
                     impl="shard_a2a", mesh=mesh)
params = moe.moe_params(jax.random.PRNGKey(0), spec_e, jnp.float32, False)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))

with mesh:
    oe, ae = jax.jit(lambda p, x: moe.moe_fwd_einsum(p, x, spec_e))(params, x)
    os_, as_ = jax.jit(lambda p, x: moe.moe_fwd_sharded(p, x, spec_s))(params, x)
assert np.allclose(np.asarray(oe), np.asarray(os_), rtol=1e-4, atol=1e-5), \
    np.abs(np.asarray(oe) - np.asarray(os_)).max()
assert abs(float(ae) - float(as_)) < 1e-5

# gradients must agree too (training path)
def loss_e(p, x):
    o, a = moe.moe_fwd_einsum(p, x, spec_e)
    return jnp.sum(o * o) + a

def loss_s(p, x):
    o, a = moe.moe_fwd_sharded(p, x, spec_s)
    return jnp.sum(o * o) + a

with mesh:
    ge = jax.jit(jax.grad(loss_e))(params, x)
    gs = jax.jit(jax.grad(loss_s))(params, x)
for k in ge:
    assert np.allclose(np.asarray(ge[k]), np.asarray(gs[k]),
                       rtol=1e-3, atol=1e-4), k
print("MOE_A2A_OK")
"""


def test_moe_sharded_matches_einsum():
    r = subprocess.run([sys.executable, "-c", SNIPPET],
                       capture_output=True, text=True, timeout=600,
                       cwd=__file__.rsplit("/tests/", 1)[0])
    assert "MOE_A2A_OK" in r.stdout, r.stdout + r.stderr[-3000:]
