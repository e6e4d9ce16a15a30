"""Pipeline parallelism == sequential execution (values AND gradients)."""

import subprocess
import sys

SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.models.pipeline import pipeline_apply
from jax.sharding import PartitionSpec as P

mesh = jax.make_mesh((2,), ("pod",))
L, D = 4, 16           # 4 layers -> 2 stages x 2 layers
n_micro, mb, S = 3, 2, 8

rng = np.random.default_rng(0)
Ws = jnp.asarray(rng.normal(size=(L, D, D)).astype(np.float32) / np.sqrt(D))
x = jnp.asarray(rng.normal(size=(n_micro, mb, S, D)).astype(np.float32))

def stage_fn(w_local, h):     # w_local: (2, D, D) — this stage's layers
    for i in range(w_local.shape[0]):
        h = jnp.tanh(h @ w_local[i])
    return h

def pipe(Ws, x):
    return pipeline_apply(Ws, x, stage_fn, mesh=mesh, axis="pod",
                          inner_specs=P(None, None, None, None))

def seq(Ws, x):
    h = x
    for i in range(L):
        h = jnp.tanh(h @ Ws[i])
    return h

with mesh:
    got = jax.jit(pipe)(Ws, x)
want = seq(Ws, x)
assert np.allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6), \
    np.abs(np.asarray(got) - np.asarray(want)).max()

# gradients flow through the ppermute schedule
def loss_p(Ws, x): return jnp.sum(pipe(Ws, x) ** 2)
def loss_s(Ws, x): return jnp.sum(seq(Ws, x) ** 2)
with mesh:
    gp = jax.jit(jax.grad(loss_p))(Ws, x)
gs = jax.grad(loss_s)(Ws, x)
assert np.allclose(np.asarray(gp), np.asarray(gs), rtol=1e-4, atol=1e-5), \
    np.abs(np.asarray(gp) - np.asarray(gs)).max()
print("PIPELINE_OK")
"""


def test_pipeline_matches_sequential():
    r = subprocess.run([sys.executable, "-c", SNIPPET],
                       capture_output=True, text=True, timeout=600,
                       cwd=__file__.rsplit("/tests/", 1)[0])
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr[-3000:]


TRANSFORMER_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.models import transformer as tf

# the transformer scaffold is written for GSPMD's Auto sharding (bare-spec
# with_sharding_constraint); jax.make_mesh defaults to Explicit axes
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
cfg = tf.TransformerConfig(name="t", n_layers=4, d_model=64, n_heads=4,
                           n_kv_heads=2, d_ff=128, vocab=512, d_head=16,
                           dtype="float32", remat=False, kv_chunk=32,
                           batch_axes=("data",))
params = tf.init_params(jax.random.PRNGKey(0), cfg)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
with mesh:
    l_pipe = float(jax.jit(lambda p, t, y: tf.pipeline_loss_fn(
        p, cfg, t, y, mesh=mesh, n_micro=4))(params, tokens, tokens))
    l_seq = float(jax.jit(lambda p, t, y: tf.loss_fn(p, cfg, t, y))(
        params, tokens, tokens))
assert abs(l_pipe - l_seq) < 1e-4, (l_pipe, l_seq)
with mesh:
    g = jax.jit(jax.grad(lambda p: tf.pipeline_loss_fn(
        p, cfg, tokens, tokens, mesh=mesh, n_micro=4)))(params)
gn = float(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
assert np.isfinite(gn) and gn > 0
print("PP_TRANSFORMER_OK")
"""


def test_transformer_pipeline_loss_matches():
    """Full-transformer pipeline_loss_fn == loss_fn on a (pod,data,model)
    mesh, with finite grads through the ppermute schedule."""
    r = subprocess.run([sys.executable, "-c", TRANSFORMER_SNIPPET],
                       capture_output=True, text=True, timeout=600,
                       cwd=__file__.rsplit("/tests/", 1)[0])
    assert "PP_TRANSFORMER_OK" in r.stdout, r.stdout + r.stderr[-3000:]
