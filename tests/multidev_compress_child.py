"""Child test: int8 compressed psum — unbiasedness, error bound, training
parity on an 8-device data-parallel mesh."""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.train.compress import (compressed_psum, compressed_psum_tree,
                                  make_compressed_allreduce_step)

mesh = make_mesh((8,), ("data",))

# ---- error bound: |compressed_psum - psum| <= n_shards * max_scale --------
x = jax.random.normal(jax.random.PRNGKey(0), (8, 256))
xs = jax.device_put(x, NamedSharding(mesh, P("data")))


def f(x, key):
    return compressed_psum(x, "data", key)


got = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P()),
                            out_specs=P("data"), check_vma=False))(
    xs, jax.random.PRNGKey(1))
want = jnp.broadcast_to(x.sum(axis=0, keepdims=True), x.shape)
bound = 8 * float(jnp.abs(x).max()) / 127.0
err = float(jnp.abs(got - want).max())
assert err <= bound + 1e-5, (err, bound)
print(f"psum err {err:.4f} <= bound {bound:.4f}")

# ---- unbiasedness: mean over many keys converges to the true sum ---------
samples = []
for i in range(64):
    samples.append(np.asarray(jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P("data"), P()),
        out_specs=P("data"), check_vma=False))(
            xs, jax.random.PRNGKey(100 + i))))
bias = np.abs(np.mean(samples, axis=0) - np.asarray(want)).max()
assert bias < 0.1 * bound, (bias, bound)
print(f"bias {bias:.4f} (stochastic rounding unbiased)")

# ---- training parity: compressed DP-SGD reaches a similar loss ------------
w_true = jax.random.normal(jax.random.PRNGKey(2), (16,))


def loss_fn(params, batch):
    x, y = batch
    pred = x @ params["w"]
    return jnp.mean((pred - y) ** 2)


k = jax.random.PRNGKey(3)
X = jax.random.normal(k, (64, 16))
Y = X @ w_true
Xs = jax.device_put(X, NamedSharding(mesh, P("data")))
Ys = jax.device_put(Y, NamedSharding(mesh, P("data")))
params = {"w": jnp.zeros((16,))}
step = make_compressed_allreduce_step(loss_fn, mesh, "data", lr=0.05)
for i in range(200):
    params = step(params, (Xs, Ys), jax.random.PRNGKey(i))
final = float(loss_fn(params, (X, Y)))
assert final < 0.05, final
print(f"compressed-DP-SGD final loss {final:.4f}")
print("ALL-OK")
