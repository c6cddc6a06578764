"""Local-update training (companion scheme, arXiv:2406.13936): H local steps
between syncs; inter-worker divergence drives the adaptive batch."""
import jax
import pytest


def test_local_sgd_round_and_divergence_signal(subproc):
    out = subproc("""
import jax, jax.numpy as jnp
from jax import set_mesh
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.launch.mesh import make_host_mesh
from repro.distributed.local_step import make_local_sgd_step
from repro.optim.adamw import AdamWConfig, init_adamw
from repro.data.pipeline import MarkovTokens, make_batch
from repro.core.schedule import BatchPlan

cfg = get_smoke_config("llama3.2-1b")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
opt = init_adamw(params)
mesh = make_host_mesh(data=4, model=1)
src = MarkovTokens(vocab_size=cfg.vocab_size, seed=0)
H = 3
plan = BatchPlan(global_batch=8, micro_batch=2, accum_steps=1, workers=4)
# stack H local-step batches
import numpy as np
bs = [make_batch(src, s, plan, 16) for s in range(H)]
batch = {k: jnp.asarray(np.stack([b[k][0] for b in bs])) for k in bs[0]}
wrap, _, _ = make_local_sgd_step(model, AdamWConfig(), mesh, params_like=params)
rnd = wrap(jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch))
with set_mesh(mesh):
    p2, o2, m = rnd(params, opt, batch, jnp.float32(5e-3))
assert all(bool(jnp.isfinite(v)) for v in jax.tree.leaves(m)), m
# workers saw different data for H steps -> replicas diverged -> signal > 0
assert float(m["var_l1"]) > 0, m
assert float(m["grad_sqnorm"]) > 0
# after sync all replicas identical: feeding IDENTICAL data to all workers
# must produce zero divergence
same = {k: jnp.asarray(np.stack([np.tile(b[k][0][:2], (4,1)) for b in bs])) for k in bs[0]}
rnd2 = wrap(jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), same))
with set_mesh(mesh):
    p3, o3, m2 = rnd2(p2, o2, same, jnp.float32(5e-3))
assert float(m2["var_l1"]) < 1e-8 * max(float(m2["grad_sqnorm"]), 1e-9), m2
print("LOCAL_OK", float(m["var_l1"]), float(m2["var_l1"]))
""", devices=4)
    assert "LOCAL_OK" in out


def test_local_sgd_rejects_tree_stats_over_flat_params():
    """Local-SGD has no tree-oracle tail over flat params (the flat round
    always runs the buffer AdamW) — the combo must be rejected loudly."""
    import pytest
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.launch.mesh import make_host_mesh
    from repro.distributed.local_step import make_local_sgd_step
    from repro.optim.adamw import AdamWConfig

    model = build_model(get_smoke_config("llama3.2-1b"))
    mesh = make_host_mesh(data=1, model=1)
    with pytest.raises(ValueError):
        make_local_sgd_step(model, AdamWConfig(), mesh,
                            stats_impl="tree", params_impl="flat")


def test_local_sgd_flat_resident_matches_tree():
    """DESIGN §10 on the local-SGD round: a flat-resident replica (gradients
    born flat every local step, buffer AdamW, buffer divergence statistic)
    reproduces the tree round's metrics and synced params to 1e-5, with
    ZERO packs in the traced round."""
    import numpy as np
    import jax.numpy as jnp
    from jax import set_mesh
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.launch.mesh import make_host_mesh
    from repro.distributed.local_step import make_local_sgd_step
    from repro.analysis import count_layout_ops
    from repro.optim.adamw import AdamWConfig, init_adamw, init_adamw_flat

    from repro.data.pipeline import MarkovTokens, make_batch
    from repro.core.schedule import BatchPlan

    cfg = get_smoke_config("llama3.2-1b")
    model = build_model(cfg)
    mesh = make_host_mesh(data=1, model=1)
    src = MarkovTokens(vocab_size=cfg.vocab_size, seed=0)
    plan = BatchPlan(global_batch=2, micro_batch=2, accum_steps=1, workers=1)
    bs = [make_batch(src, s, plan, 16) for s in range(3)]     # H = 3
    batch = {k: jnp.asarray(np.stack([b[k][0] for b in bs])) for k in bs[0]}
    sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)

    res = {}
    for params_impl in ("tree", "flat"):
        params = model.init(jax.random.PRNGKey(0))
        wrap, _, _ = make_local_sgd_step(model, AdamWConfig(), mesh,
                                         stats_impl=params_impl,
                                         params_impl=params_impl,
                                         params_like=params)
        layout = wrap.flat_layout
        opt = (init_adamw_flat(params, layout=layout)
               if params_impl == "flat" else init_adamw(params))
        if params_impl == "flat":
            params = tuple(layout.flatten(params))
        with set_mesh(mesh):
            if params_impl == "flat":
                # jaxpr-eqn count: zero pack eqns in the traced flat round
                ops_seen = count_layout_ops(
                    wrap(sds), params, opt, batch, jnp.float32(5e-3))
                assert not ops_seen["pack"], (
                    f"{len(ops_seen['pack'])} pack eqns in flat-resident "
                    f"round: {ops_seen}")
            p2, _, m = wrap(sds)(params, opt, batch, jnp.float32(5e-3))
        if params_impl == "flat":
            p2 = layout.unflatten(list(p2))
        res[params_impl] = (p2, m)
    for k in ("loss", "var_l1", "grad_sqnorm"):
        np.testing.assert_allclose(float(res["tree"][1][k]),
                                   float(res["flat"][1][k]),
                                   rtol=1e-5, atol=1e-8, err_msg=k)
    for a, b in zip(jax.tree.leaves(res["tree"][0]),
                    jax.tree.leaves(res["flat"][0])):
        # atol 5e-6: the embedding-table scatter adjoint reorders its adds
        # when differentiated through the buffer slice (H chained steps
        # compound the reassociation to ~1e-6 absolute)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=5e-6)
