"""Multi-device tests: run in a subprocess with 8 forced host devices so
the main pytest process keeps its single-device view.

Covers: sharded train step executes + matches single-device numerics,
seq-sharded decode (shard_map flash-decode) equals unsharded decode,
shard_map MoE equals local MoE, and elastic checkpoint restore onto a
different mesh."""

import os
import subprocess
import sys
import textwrap


SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_sub(code: str, timeout=420):
    env = dict(os.environ,
               PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               TF_CPP_MIN_LOG_LEVEL="3")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


COMMON = """
import sys; sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np, dataclasses
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.configs.reduced import reduced_config as reduced
from repro.launch.mesh import _make_mesh
from repro.models import build_model, ImplConfig

mesh = _make_mesh((2, 4), ("data", "model"))
"""


def test_seqshard_decode_equals_unsharded():
    run_sub(COMMON.format(src=SRC) + """
cfg = reduced(get_config("mistral-nemo-12b"))
B, S, CL = 4, 12, 32
rng = jax.random.PRNGKey(0)
toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)

# unsharded reference
m0 = build_model(cfg, ImplConfig(remat="none"))
params = m0.init_params(rng)
logits0, cache0 = jax.jit(lambda p, b: m0.prefill(p, b, CL))(params, {"tokens": toks})
nxt = jnp.zeros((B, 1), jnp.int32)
l0, c0 = jax.jit(m0.decode_step)(params, nxt, cache0, jnp.asarray(S, jnp.int32))

# sequence-sharded decode via shard_map flash-decode
impl = ImplConfig(remat="none", decode_shard_ctx=(mesh, ("model",), ("data",)))
m1 = build_model(cfg, impl)
cache_sharding = jax.tree.map(
    lambda a: NamedSharding(mesh, P(None, "data", None, "model", None)), cache0)
with mesh:
    cache_sh = jax.tree.map(lambda a, s: jax.device_put(a, s), cache0, cache_sharding)
    l1, c1 = jax.jit(m1.decode_step)(params, nxt, cache_sh, jnp.asarray(S, jnp.int32))
np.testing.assert_allclose(np.asarray(l0, np.float32), np.asarray(l1, np.float32),
                           rtol=5e-2, atol=5e-2)
# cache contents must match too (the new token row written on the owner shard)
for a, b in zip(jax.tree.leaves(c0), jax.tree.leaves(c1)):
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                               rtol=5e-2, atol=5e-2)
print("seqshard decode OK")
""")


def test_moe_shard_map_equals_local():
    run_sub(COMMON.format(src=SRC) + """
from repro.models.moe import moe_block
from repro.models.transformer import block_specs
from repro.models import layers as L
cfg = reduced(get_config("qwen2-moe-a2.7b"))
p = L.init_from_specs(jax.random.PRNGKey(0), block_specs(cfg, "moe")["moe"])
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model), jnp.bfloat16)
y0, aux0 = moe_block(p, x, cfg)                      # local reference
with mesh:
    y1, aux1 = jax.jit(lambda p, x: moe_block(p, x, cfg,
        shard_ctx=(mesh, "model", ("data",))))(p, x)
np.testing.assert_allclose(np.asarray(y0, np.float32), np.asarray(y1, np.float32),
                           rtol=6e-2, atol=6e-2)
assert abs(float(aux0) - float(aux1)) < 4e-2, (float(aux0), float(aux1))
print("moe shard_map OK")
""")


def test_sharded_train_step_matches_single_device():
    run_sub(COMMON.format(src=SRC) + """
from repro.training import optimizer as opt
from repro.training.train_step import make_train_step
from repro.core.materializer import Plan, MeshSpec
from repro.sharding import planner

cfg = reduced(get_config("tinyllama-1.1b"))
model = build_model(cfg, ImplConfig(remat="none"))
rng = jax.random.PRNGKey(0)
params = model.init_params(rng)
opt_state = opt.init_opt_state(params)
batch = {"tokens": jax.random.randint(rng, (8, 16), 0, cfg.vocab_size),
         "labels": jax.random.randint(rng, (8, 16), 0, cfg.vocab_size)}

spec = MeshSpec.of_chip("test", (2, 4), ("data", "model"), "TPU v5 lite")
plan = Plan("t", "train_4k", spec, batch_axes=("data",), tp=True,
            zero=True, remat="none", microbatch=1)
step = make_train_step(model, plan)

# single device
p1, o1, m1 = jax.jit(step)(params, opt_state, batch)

# sharded
specs = model.param_specs()
psh = planner.to_named(planner.param_specs_tree(plan, cfg, specs), mesh)
osh = {"m": planner.to_named(planner.opt_state_specs_tree(plan, cfg, specs), mesh),
       "v": planner.to_named(planner.opt_state_specs_tree(plan, cfg, specs), mesh),
       "master": planner.to_named(planner.opt_state_specs_tree(plan, cfg, specs), mesh),
       "count": NamedSharding(mesh, P())}
bsh = {k: NamedSharding(mesh, P("data", None)) for k in batch}
with mesh:
    p2, o2, m2 = jax.jit(step, in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, None))(params, opt_state, batch)
assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-2, (float(m1["loss"]), float(m2["loss"]))
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                               rtol=8e-2, atol=8e-2)
print("sharded train OK", float(m1["loss"]), float(m2["loss"]))
""")


def test_elastic_restore_onto_different_mesh():
    run_sub(COMMON.format(src=SRC) + """
import tempfile, os
from repro.checkpoint.checkpointer import save_checkpoint, restore_checkpoint
from repro.sharding import planner
from repro.core.materializer import Plan, MeshSpec

cfg = reduced(get_config("tinyllama-1.1b"))
model = build_model(cfg, ImplConfig(remat="none"))
params = model.init_params(jax.random.PRNGKey(0))

mesh_a = _make_mesh((2, 4), ("data", "model"))
mesh_b = _make_mesh((4, 2), ("data", "model"))
spec_a = MeshSpec.of_chip("a", (2, 4), ("data", "model"), "TPU v5 lite")
spec_b = MeshSpec.of_chip("b", (4, 2), ("data", "model"), "TPU v5 lite")
plan_a = Plan("t", "train_4k", spec_a, batch_axes=("data",), tp=True)
plan_b = Plan("t", "train_4k", spec_b, batch_axes=("data",), tp=True)
specs = model.param_specs()
sh_a = planner.to_named(planner.param_specs_tree(plan_a, cfg, specs), mesh_a)
sh_b = planner.to_named(planner.param_specs_tree(plan_b, cfg, specs), mesh_b)
params_a = jax.tree.map(lambda x, s: jax.device_put(x, s), params, sh_a)

d = tempfile.mkdtemp()
save_checkpoint(d, 5, params_a, extra={"mesh": "a"})
restored, extra, step = restore_checkpoint(d, 5, params, shardings=sh_b)
for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
# verify placement follows mesh_b
leaf = jax.tree.leaves(restored)[0]
assert leaf.sharding.mesh.shape["data"] == 4
print("elastic restore OK")
""")
