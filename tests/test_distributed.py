"""Distributed runtime: multi-device variance statistics (eq. 5) vs brute
force, sharding-spec sanity, mini dry-run — in subprocesses with forced
device counts (the main process keeps 1 device)."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.params import param_pspecs, cache_pspecs
from repro.launch.mesh import make_host_mesh
from repro.configs import get_smoke_config
from repro.models import build_model


def test_param_pspecs_divisibility_fallback():
    """internvl2 has 14 heads: head-dim sharding over a 2-wide model axis
    works (14 % 2 == 0) but its kv_heads=2 over 4 would not."""
    cfg = get_smoke_config("llama3.2-1b")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mesh = make_host_mesh(data=1, model=1)
    specs = param_pspecs(params, mesh, fsdp=False)
    # single-device mesh: everything must sanitize to replicated
    for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        assert all(a is None for a in s), s


def test_fsdp_norm_matches_bruteforce(subproc):
    out = subproc("""
import jax, jax.numpy as jnp
from jax import set_mesh
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.launch.mesh import make_host_mesh
from repro.distributed.train_step import make_fsdp_norm_step
from repro.optim.adamw import AdamWConfig, init_adamw
from repro.data.pipeline import MarkovTokens, make_batch
from repro.core.schedule import BatchPlan
from repro.core.norm_test import tree_sqdiff, tree_sqnorm

cfg = get_smoke_config("llama3.2-1b")
model = build_model(cfg)
key = jax.random.PRNGKey(0)
params = model.init(key)
opt = init_adamw(params)
mesh = make_host_mesh(data=4, model=1)
src = MarkovTokens(vocab_size=cfg.vocab_size, seed=0)
plan = BatchPlan(global_batch=8, micro_batch=2, accum_steps=1, workers=4)
batch = jax.tree.map(jnp.asarray, make_batch(src, 0, plan, 16))
wrap, _, _ = make_fsdp_norm_step(model, AdamWConfig(), mesh, params_like=params)
step = wrap(jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch))
with set_mesh(mesh):
    _, _, metrics = step(params, opt, batch, jnp.float32(1e-3))
params = model.init(key)
gs = []
for j in range(4):
    mb = {k: v[0, j*2:(j+1)*2] for k, v in batch.items()}
    gs.append(jax.grad(lambda p: model.loss(p, mb)[0])(params))
gmean = jax.tree.map(lambda *x: sum(x)/4, *gs)
var_l1 = sum(float(tree_sqdiff(g, gmean)) for g in gs)/4
gsq = float(tree_sqnorm(gmean))
assert abs(var_l1 - float(metrics["var_l1"]))/max(var_l1,1e-9) < 1e-3, (var_l1, float(metrics["var_l1"]))
assert abs(gsq - float(metrics["grad_sqnorm"]))/gsq < 1e-3
print("MATCH")
""", devices=4)
    assert "MATCH" in out


def test_paper_vs_scalar_variance_equal(subproc):
    """The optimized scalar-psum statistic must equal the paper-literal
    full-vector all-reduce formulation (DESIGN §7.1)."""
    out = subproc("""
import jax, jax.numpy as jnp
from jax import set_mesh
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.launch.mesh import make_host_mesh
from repro.distributed.train_step import make_fsdp_norm_step
from repro.optim.adamw import AdamWConfig, init_adamw
from repro.data.pipeline import MarkovTokens, make_batch
from repro.core.schedule import BatchPlan

cfg = get_smoke_config("tinyllama-1.1b")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
mesh = make_host_mesh(data=4, model=1)
src = MarkovTokens(vocab_size=cfg.vocab_size, seed=0)
plan = BatchPlan(global_batch=8, micro_batch=2, accum_steps=1, workers=4)
batch = jax.tree.map(jnp.asarray, make_batch(src, 0, plan, 16))
sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
vals = {}
for impl in ("scalar", "paper"):
    params_i = model.init(jax.random.PRNGKey(0))   # fresh: steps donate args
    opt = init_adamw(params_i)
    wrap, _, _ = make_fsdp_norm_step(model, AdamWConfig(), mesh,
                                     variance_impl=impl, params_like=params_i)
    with set_mesh(mesh):
        _, _, m = wrap(sds)(params_i, opt, batch, jnp.float32(1e-3))
    vals[impl] = float(m["var_l1"])
assert abs(vals["scalar"] - vals["paper"]) / max(vals["scalar"], 1e-12) < 1e-4, vals
print("EQUAL", vals)
""", devices=4)
    assert "EQUAL" in out


def test_2d_mesh_train_and_serve(subproc):
    """data x model hybrid step + decode step on a 2x2 mesh for a GQA arch
    and an SSM arch."""
    out = subproc("""
import jax, jax.numpy as jnp
from jax import set_mesh
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.launch.mesh import make_host_mesh
from repro.distributed.train_step import make_fsdp_norm_step
from repro.distributed.serve_step import make_decode_step
from repro.optim.adamw import AdamWConfig, init_adamw
from repro.data.pipeline import MarkovTokens, make_batch
from repro.core.schedule import BatchPlan

for arch in ("llama3.2-1b", "mamba2-370m"):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_host_mesh(data=2, model=2)
    src = MarkovTokens(vocab_size=cfg.vocab_size, seed=0)
    plan = BatchPlan(global_batch=8, micro_batch=2, accum_steps=2, workers=2)
    batch = jax.tree.map(jnp.asarray, make_batch(src, 0, plan, 16))
    opt = init_adamw(params)
    wrap, _, _ = make_fsdp_norm_step(model, AdamWConfig(), mesh, params_like=params)
    step = wrap(jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch))
    # build caches OUTSIDE the mesh context so they stay uncommitted and the
    # jitted in_shardings can place them
    dec_wrap, _ = make_decode_step(model, mesh, batch=4, params_like=params)
    cache = model.init_cache(4, 8)
    dstep = dec_wrap(jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache))
    tok = jnp.zeros((4,), jnp.int32)
    with set_mesh(mesh):
        p2, o2, m = step(params, opt, batch, jnp.float32(1e-3))
        assert all(float(jnp.isfinite(v)) for v in jax.tree.leaves(m))
        lg, cache = dstep(p2, cache, tok, jnp.int32(0))
        assert bool(jnp.all(jnp.isfinite(lg)))
    print("OK", arch)
""", devices=4)
    assert out.count("OK") == 2


def test_mini_dryrun_all_shapes(subproc):
    """Reduced-scale dry-run: lower+compile train/prefill/decode for a smoke
    config on an 8-device 4x2 mesh (the structural twin of the 512-chip run)."""
    out = subproc("""
import jax, jax.numpy as jnp
from jax import set_mesh
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.launch.mesh import make_host_mesh
from repro.distributed.train_step import make_fsdp_norm_step
from repro.distributed.serve_step import make_decode_step, make_prefill
from repro.optim.adamw import AdamWConfig, init_adamw

cfg = get_smoke_config("gemma2-27b").replace(xent_chunk=16)
model = build_model(cfg)
mesh = make_host_mesh(data=4, model=2)
params_like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
opt_like = jax.eval_shape(init_adamw, params_like)
i32 = jnp.int32
with set_mesh(mesh):
    # train
    batch = {"tokens": jax.ShapeDtypeStruct((1, 8, 64), i32),
             "labels": jax.ShapeDtypeStruct((1, 8, 64), i32)}
    wrap, _, _ = make_fsdp_norm_step(model, AdamWConfig(), mesh, params_like=params_like)
    c = wrap(batch).lower(params_like, opt_like, batch,
                          jax.ShapeDtypeStruct((), jnp.float32)).compile()
    ca = c.cost_analysis()
    if isinstance(ca, (list, tuple)):   # older jaxlib: one dict per device
        ca = ca[0]
    assert ca["flops"] > 0
    # prefill
    pwrap, _ = make_prefill(model, mesh, batch=4, params_like=params_like)
    pb = {"tokens": jax.ShapeDtypeStruct((4, 64), i32)}
    pc = pwrap(pb).lower(params_like, pb).compile()
    # decode
    cache = jax.eval_shape(lambda: model.init_cache(4, 64))
    dwrap, _ = make_decode_step(model, mesh, batch=4, params_like=params_like)
    dc = dwrap(cache).lower(params_like, cache,
                            jax.ShapeDtypeStruct((4,), i32),
                            jax.ShapeDtypeStruct((), i32)).compile()
    print("LOWERED", c.memory_analysis().temp_size_in_bytes >= 0)
""", devices=8)
    assert "LOWERED" in out
