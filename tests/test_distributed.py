"""Multi-device parity tests (subprocess with 8 fake host devices):
the §Perf-optimized distributed paths must equal their single-device
references exactly."""
import subprocess
import sys



def _run(script: str) -> str:
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=580,
                       cwd="/root/repo")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    return r.stdout


HEADER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.parallel.sharding import ShardingRules
mesh = jax.make_mesh((2, 4), ("data", "model"))
rules = ShardingRules(batch=("data",), fsdp=("data",))
rng = np.random.default_rng(0)
"""


def test_distributed_predict_matches_reference():
    _run(HEADER + r"""
from repro.core import knn
q = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
c = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
ref = knn.predict(q, c, k=5, alpha=0.7, exclude_self=False)
with jax.set_mesh(mesh):
    cd = jax.device_put(c, NamedSharding(mesh, P(("data","model"), None)))
    out = jax.jit(lambda q, c: knn.distributed_predict(
        q, c, 5, 0.7, mesh, rules))(q, cd)
assert float(jnp.max(jnp.abs(out - ref))) < 1e-4
print("OK")
""")


def test_sharded_moe_matches_local():
    _run(HEADER + r"""
from repro.models.transformer import TransformerConfig, moe_block
import repro.models.transformer as T
c = TransformerConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                      d_head=16, d_ff=128, vocab_size=97, q_block=4,
                      moe=True, n_experts=8, n_shared_experts=0, top_k=2,
                      moe_d_ff=32, capacity_factor=4.0, dtype=jnp.float32)
shapes = T._dense_layer_shapes(c, False)
layer = {k: jax.random.normal(jax.random.PRNGKey(i), v, jnp.float32)*0.1
         for i, (k, v) in enumerate(shapes.items())
         if k.startswith(("router", "we_"))}
x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 64), jnp.float32)
out_local = moe_block(x, layer, c, None, None)
with jax.set_mesh(mesh):
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    ls = {"router": jax.device_put(layer["router"],
                                   NamedSharding(mesh, P(None, None))),
          "we_gate": jax.device_put(layer["we_gate"],
                                    NamedSharding(mesh, P("model", "data", None))),
          "we_up": jax.device_put(layer["we_up"],
                                  NamedSharding(mesh, P("model", "data", None))),
          "we_down": jax.device_put(layer["we_down"],
                                    NamedSharding(mesh, P("model", None, "data")))}
    out_sh = jax.jit(lambda x, l: moe_block(x, l, c, mesh, rules))(xs, ls)
assert float(jnp.max(jnp.abs(out_local - out_sh))) < 1e-4
print("OK")
""")


def test_bert4rec_shardmap_serve_matches_fallback():
    _run(HEADER + r"""
from repro.models import bert4rec
c = bert4rec.Bert4RecConfig(n_items=1000, embed_dim=32, n_blocks=2,
                            n_heads=2, seq_len=16, d_ff=64)
params = bert4rec.init_params(c, jax.random.PRNGKey(0))
ids = jnp.asarray(rng.integers(2, 900, (8, 16)), jnp.int32)
v0, i0 = bert4rec.serve_step(params, {"ids": ids}, c, top_n=10)
with jax.set_mesh(mesh):
    v1, i1 = jax.jit(lambda p, b: bert4rec.serve_step(
        p, b, c, top_n=10, mesh=mesh, rules=rules))(params, {"ids": ids})
np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), atol=1e-4)
for a, b in zip(np.asarray(i0), np.asarray(i1)):
    assert set(map(int, a)) == set(map(int, b))
print("OK")
""")


def test_lm_train_step_runs_sharded():
    """A real (executed, not just compiled) sharded MoE train step."""
    _run(HEADER + r"""
from repro.models import transformer as T
from repro.optim import adamw, adamw_state_pspecs
from repro.configs.base import named
c = T.TransformerConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                        d_head=16, d_ff=128, vocab_size=256, moe=True,
                        n_experts=8, n_shared_experts=1, top_k=2,
                        moe_d_ff=32, first_dense_layers=1, q_block=8,
                        capacity_factor=2.0, dtype=jnp.float32)
params = T.init_params(c, jax.random.PRNGKey(0))
pspecs = T.param_pspecs(c, mesh, rules)
opt = adamw(total_steps=5)
batch = {"tokens": jnp.ones((8, 32), jnp.int32),
         "labels": jnp.ones((8, 32), jnp.int32)}
with jax.set_mesh(mesh):
    params = jax.tree.map(lambda x, s: jax.device_put(
        x, NamedSharding(mesh, s)), params, pspecs,
        is_leaf=lambda x: isinstance(x, jax.Array))
    opt_state = opt.init(params)
    step = jax.jit(T.make_train_step(c, opt, mesh, rules),
                   donate_argnums=(0, 1))
    for _ in range(3):
        params, opt_state, m = step(params, opt_state, batch)
    loss = float(m["loss"])
assert np.isfinite(loss) and loss > 0
print("OK", loss)
""")
