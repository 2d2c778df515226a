"""CI-size dry-run: lower + compile representative cells on a small
multi-device mesh in a SUBPROCESS (jax locks the host device count on
first init, so the fake-device env var cannot be set in this process).
The full 512-chip sweep is launch/dryrun.py (results/ JSON)."""
import subprocess
import sys


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.parallel.sharding import ShardingRules
from repro.models import transformer as T
from repro.optim import adamw, adamw_state_pspecs
from repro.configs.base import named

mesh = jax.make_mesh((4, 4), ("data", "model"))
rules = ShardingRules(batch=("data",), fsdp=("data",))
c = T.TransformerConfig(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_head=16, d_ff=128,
    vocab_size=256, moe=True, n_experts=8, n_shared_experts=1, top_k=2,
    moe_d_ff=32, first_dense_layers=1, q_block=8, dtype=jnp.bfloat16)
params = T.abstract_params(c)
pspecs = T.param_pspecs(c, mesh, rules)
opt = adamw(total_steps=10)
opt_state = jax.eval_shape(opt.init, params)
batch = {"tokens": jax.ShapeDtypeStruct((16, 32), jnp.int32),
         "labels": jax.ShapeDtypeStruct((16, 32), jnp.int32)}
bshard = {k: NamedSharding(mesh, P(("data",), None)) for k in batch}
with jax.set_mesh(mesh):
    fn = T.make_train_step(c, opt, mesh, rules)
    lowered = jax.jit(fn, in_shardings=(
        named(mesh, pspecs), named(mesh, adamw_state_pspecs(pspecs)),
        bshard), donate_argnums=(0, 1)).lower(params, opt_state, batch)
    compiled = lowered.compile()
mem = compiled.memory_analysis()
assert mem.temp_size_in_bytes > 0
hlo = compiled.as_text()
assert "all-reduce" in hlo or "all-gather" in hlo, "no collectives?!"
# roofline terms extract cleanly
from repro.launch.roofline import analyze
terms = analyze(compiled, hlo, 16)
assert terms.flops > 0 and terms.hbm_bytes > 0
print("SMALL_DRYRUN_OK", int(terms.flops), terms.bottleneck)
"""


def test_small_mesh_moe_train_lowers_and_compiles():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=580,
                       cwd="/root/repo")
    assert "SMALL_DRYRUN_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


def test_production_cell_builders_construct():
    """Every (arch × shape) builder must at least CONSTRUCT its program
    spec (ShapeDtypeStructs + shardings) on the production mesh shape —
    without compiling (that is the full dry-run's job)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import sys
sys.path.insert(0, "src")
import jax
from repro.configs import REGISTRY
from repro.launch.mesh import make_production_mesh, make_rules
mesh = make_production_mesh(multi_pod=True)
rules = make_rules(mesh)
n = 0
for name, arch in REGISTRY.items():
    for shape, builder in arch.cells.items():
        prog = builder(mesh, rules)
        assert prog.fn is not None and prog.args
        n += 1
print("BUILT", n)
"""
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=580,
                       cwd="/root/repo")
    # 40 assigned cells + tifu-knn stream_update/serve_topk/serve_topk_opt
    assert "BUILT 43" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
