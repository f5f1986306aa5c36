"""Numerical correctness of the cross-chip flash-decoding path
(dist_decode_attention) on a multi-device host mesh. Runs in a subprocess so
the main test process keeps the default single-device backend."""
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models import layers as L
from repro.models.sharding import standard_rules, use_rules
from repro.kernels.decode_attention.ref import decode_attention_ref

mesh = jax.make_mesh((2, 4), ("data", "model"))
rules = standard_rules(False)
rules["kv_seq"] = "model"

B, S, Hq, K, hd, pos = 2, 64, 8, 2, 16, 41
key = jax.random.key(0)
ks = jax.random.split(key, 5)
q = jax.random.normal(ks[0], (B, 1, Hq, hd), jnp.float32)
kc = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
vc = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
kn = jax.random.normal(ks[3], (B, 1, K, hd), jnp.float32)
vn = jax.random.normal(ks[4], (B, 1, K, hd), jnp.float32)

def run(q, kc, vc, kn, vn):
    with use_rules(rules, mesh):
        return L.dist_decode_attention(q, kc, vc, kn, vn, pos)

cs = NamedSharding(mesh, P("data", "model", None, None))
with mesh:
    out, kc2, vc2 = jax.jit(run, in_shardings=(
        NamedSharding(mesh, P("data",)), cs, cs,
        NamedSharding(mesh, P("data",)), NamedSharding(mesh, P("data",))
    ))(q, kc, vc, kn, vn)

# reference: write the new token at pos, then plain decode attention
kc_ref = kc.at[:, pos].set(kn[:, 0])
vc_ref = vc.at[:, pos].set(vn[:, 0])
ref = decode_attention_ref(q[:, 0], kc_ref, vc_ref, pos)
err = float(jnp.max(jnp.abs(out[:, 0] - ref)))
assert err < 2e-5, err
np.testing.assert_allclose(np.asarray(kc2), np.asarray(kc_ref), atol=1e-6)
np.testing.assert_allclose(np.asarray(vc2), np.asarray(vc_ref), atol=1e-6)

# the lane-dense (B, S, K * hd) cache: the same result, the caches handed
# back in that layout
fs = NamedSharding(mesh, P("data", "model", None))
with mesh:
    out_f, kc3, vc3 = jax.jit(run, in_shardings=(
        NamedSharding(mesh, P("data",)), fs, fs,
        NamedSharding(mesh, P("data",)), NamedSharding(mesh, P("data",))
    ))(q, kc.reshape(B, S, K * hd), vc.reshape(B, S, K * hd), kn, vn)
assert kc3.shape == vc3.shape == (B, S, K * hd)
np.testing.assert_allclose(np.asarray(out_f), np.asarray(out), atol=1e-6)
np.testing.assert_allclose(np.asarray(kc3).reshape(kc_ref.shape),
                           np.asarray(kc_ref), atol=1e-6)
np.testing.assert_allclose(np.asarray(vc3).reshape(vc_ref.shape),
                           np.asarray(vc_ref), atol=1e-6)
print("DIST_ATTENTION_OK", err)
"""


def test_dist_decode_attention_multidevice():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert "DIST_ATTENTION_OK" in r.stdout, r.stdout + r.stderr
