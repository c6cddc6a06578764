"""Flat gradient buffers & single-pass statistics (DESIGN §9): layout
round-trips, fused-stats agreement with the tree oracles, flat-vs-tree
train-step equality, and the launch-count (op-count) regression proxy."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.norm_test import tree_sqdiff, tree_sqnorm
from repro.distributed.flatbuf import FlatLayout, flatten_tree
from repro.kernels import ops, ref, resolve_interpret
from repro.optim.adamw import (
    AdamWConfig, init_adamw, init_adamw_flat, adamw_update, adamw_update_flat,
    flat_opt_state, unflat_opt_state)

KEY = jax.random.PRNGKey(11)


def _mixed_tree():
    """Mixed-dtype, odd-shape pytree incl. scalar and >bucket-size leaf."""
    return {
        "a": jnp.arange(17, dtype=jnp.float32),
        "nested": {"b": jnp.ones((3, 5), jnp.bfloat16),
                   "c": jnp.full((), 2.5, jnp.float32),
                   "d": jnp.arange(6, dtype=jnp.int32).reshape(2, 3)},
        "e": (jnp.linspace(0, 1, 257 * 3).reshape(257, 3).astype(jnp.bfloat16),
              jnp.eye(9, 7, dtype=jnp.float32)),
    }


def _randlike(seed, tree):
    leaves, td = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return td.unflatten([jax.random.normal(k, l.shape).astype(l.dtype)
                         for k, l in zip(keys, leaves)])


# ------------------------------------------------------------ layout ----

def test_roundtrip_bit_exact_mixed_dtypes():
    tree = _mixed_tree()
    layout, buffers = flatten_tree(tree)
    # dtype-homogeneous buffers, one per dtype here (all under bucket size)
    assert {str(d) for d in layout.buffer_dtypes} == \
        {"float32", "bfloat16", "int32"}
    back = layout.unflatten(buffers)
    for want, got in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert want.dtype == got.dtype and want.shape == got.shape
        assert bool(jnp.all(want == got))     # bit-exact, no casts


def test_bucketing_splits_groups_and_respects_leaf_boundaries():
    tree = {f"w{i}": jnp.zeros((1000,), jnp.float32) for i in range(64)}
    layout = FlatLayout.from_tree(tree, bucket_bytes=16000)   # 4000 elems
    assert layout.num_buffers > 1
    assert sum(layout.buffer_sizes) == 64_000
    for slot in layout.slots:                 # leaves never straddle buckets
        assert slot.offset + slot.size <= layout.buffer_sizes[slot.buffer_index]
    # an oversized leaf becomes its own bucket
    big = {"big": jnp.zeros((10_000,)), "small": jnp.zeros((10,))}
    lay2 = FlatLayout.from_tree(big, bucket_bytes=4000)
    assert lay2.num_buffers == 2


def test_flatten_congruent_tree_through_param_layout():
    """f32 grads of a mixed-dtype param tree pack through the same slots."""
    params = {"p16": jnp.ones((8, 4), jnp.bfloat16), "p32": jnp.ones((5,))}
    layout = FlatLayout.from_tree(params)
    grads = jax.tree.map(lambda x: jnp.ones(x.shape, jnp.float32), params)
    bufs = layout.flatten(grads)
    assert all(b.dtype == jnp.float32 for b in bufs)
    back = layout.unflatten(bufs)
    assert jax.tree.leaves(back)[0].dtype == jnp.float32


def test_empty_tree_layout():
    """Zero-leaf trees: a valid (degenerate) layout with no buffers."""
    layout, bufs = flatten_tree({})
    assert layout.num_buffers == 0 and layout.num_leaves == 0
    assert bufs == [] and layout.zeros() == []
    assert layout.unflatten([]) == {}


def test_size0_leaves_roundtrip():
    """Size-0 leaves round trip; a dtype group holding ONLY size-0 leaves
    still owns a real (0-sized) bucket instead of a dangling slot."""
    tree = {"data": jnp.arange(5, dtype=jnp.float32),
            "empty": jnp.zeros((0,), jnp.float32),
            "empty2d": jnp.zeros((0, 3), jnp.float32),
            "ints": jnp.zeros((0,), jnp.int32)}      # all-empty int32 group
    layout, bufs = flatten_tree(tree)
    assert layout.num_buffers == 2                   # f32 bucket + 0-size i32
    assert 0 in layout.buffer_sizes
    assert all(s.buffer_index < layout.num_buffers for s in layout.slots)
    back = layout.unflatten(bufs)
    for want, got in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert want.dtype == got.dtype and want.shape == got.shape
        assert bool(jnp.all(want == got))


def test_single_oversized_leaf_is_own_bucket():
    """One leaf above bucket_bytes in a single-leaf tree: exactly one bucket
    of exactly the leaf's size (plus shard padding when requested)."""
    tree = {"big": jnp.zeros((5001,), jnp.float32)}
    layout = FlatLayout.from_tree(tree, bucket_bytes=4000)   # 1000-elem target
    assert layout.num_buffers == 1
    assert layout.buffer_sizes == (5001,) and layout.buffer_pads == (0,)
    lay8 = FlatLayout.from_tree(tree, bucket_bytes=4000, shard_divisor=8)
    assert lay8.buffer_sizes == (5008,) and lay8.buffer_pads == (7,)


def test_shard_divisor_padding_roundtrip():
    """Mesh-divisible bucket padding: every bucket size divides J, the pad
    is zero-filled on flatten, never referenced by a slot, and the
    flatten→unflatten round trip stays bit-exact."""
    tree = {"a": jnp.arange(17, dtype=jnp.float32),
            "b": jnp.linspace(-1, 1, 23).astype(jnp.float32),
            "c": jnp.arange(6, dtype=jnp.int32).reshape(2, 3),
            "d": jnp.ones((3, 5), jnp.bfloat16)}
    layout, bufs = flatten_tree(tree, bucket_bytes=64, shard_divisor=4)
    assert all(n % 4 == 0 for n in layout.buffer_sizes)
    assert sum(layout.buffer_pads) > 0               # padding actually occurred
    for buf, pad, size in zip(bufs, layout.buffer_pads, layout.buffer_sizes):
        assert buf.size == size
        if pad:
            assert bool(jnp.all(buf[size - pad:] == 0))   # zero-filled tail
    for s in layout.slots:                           # slots never touch the pad
        bi = s.buffer_index
        assert s.offset + s.size <= layout.buffer_sizes[bi] - layout.buffer_pads[bi]
    back = layout.unflatten(bufs)
    for want, got in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert want.dtype == got.dtype and want.shape == got.shape
        assert bool(jnp.all(want == got))            # bit-exact through the pad
    # moment state built at the same divisor matches the padded bucketing
    flat = init_adamw_flat(tree, shard_divisor=4)
    default_layout = FlatLayout.from_tree(tree, shard_divisor=4)
    assert tuple(b.size for b in flat["m"]) == default_layout.buffer_sizes
    assert all(n % 4 == 0 for n in default_layout.buffer_sizes)


def test_adamw_flat_padded_matches_tree():
    """Shard padding is inert end-to-end: the padded flat AdamW equals the
    tree update, and the pad region of the moments stays zero."""
    params = {"w": jax.random.normal(KEY, (37,)),
              "b": jax.random.normal(jax.random.PRNGKey(3), (10,))}
    grads = jax.tree.map(lambda x: x * 0.05 + 0.01, params)
    cfg = AdamWConfig()
    layout = FlatLayout.from_tree(params, shard_divisor=16)
    assert sum(layout.buffer_pads) > 0
    st = init_adamw(params)
    p1, s1, gn1 = adamw_update(params, grads, st, cfg, 1e-3)
    p2, s2, gn2, _ = adamw_update_flat(
        params, grads, flat_opt_state(params, st, shard_divisor=16), cfg,
        1e-3, layout=layout)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(float(gn1), float(gn2), rtol=1e-6)
    for mv in ("m", "v"):
        for buf, pad, size in zip(s2[mv], layout.buffer_pads,
                                  layout.buffer_sizes):
            if pad:
                assert bool(jnp.all(buf[size - pad:] == 0))


def test_layout_validation_errors():
    layout = FlatLayout.from_tree({"a": jnp.zeros((4,)), "b": jnp.zeros((2,))})
    with pytest.raises(ValueError):
        layout.flatten({"a": jnp.zeros((4,))})            # wrong leaf count
    with pytest.raises(ValueError):
        layout.flatten({"a": jnp.zeros((5,)), "b": jnp.zeros((2,))})  # shape
    with pytest.raises(ValueError):
        layout.unflatten([jnp.zeros((7,))])               # wrong buffers


def test_pack_cotangents_keeps_f32_through_low_precision_layout():
    """The manual unflatten adjoint must NOT downcast: f32-accumulated
    gradients of bf16 params transpose through the bf16 layout's slots
    into f32 buffers bit-identical to `flatten` of the same f32 tree
    (the dtype-strict jax.vjp route would have quantized them to bf16)."""
    params = {"a": jnp.ones((9, 3), jnp.bfloat16), "b": jnp.ones((7,))}
    layout = FlatLayout.from_tree(params, shard_divisor=4)
    g32 = _randlike(0, jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32), params))
    got = layout.pack_cotangents(g32)
    want = layout.flatten(g32)
    for a, b in zip(got, want):
        assert a.dtype == jnp.float32
        assert bool(jnp.all(a == b))
    with pytest.raises(ValueError):
        layout.pack_cotangents({"a": jnp.zeros((9, 3))})   # wrong leaf count


# ------------------------------------------------------ fused stats ----

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_stats_kernel_matches_tree_oracles(dtype):
    """One-read (Σ(x−y)², Σy²) == tree_sqdiff + tree_sqnorm to 1e-5."""
    tree = {"a": jnp.zeros((300, 7)), "b": jnp.zeros((129,)),
            "c": jnp.zeros((2, 3, 5))}
    x = jax.tree.map(lambda l: l.astype(dtype), _randlike(0, tree))
    y = jax.tree.map(lambda l: l.astype(dtype), _randlike(1, tree))
    layout = FlatLayout.from_tree(x)
    xb, yb = layout.flatten(x), layout.flatten(y)
    tol = 2e-3 if dtype == jnp.bfloat16 else 1e-5
    d = q = 0.0
    for a, b in zip(xb, yb):
        dd, qq = ops.fused_stats(a, b)      # Pallas (interpret on CPU)
        d += float(dd)
        q += float(qq)
    np.testing.assert_allclose(d, float(tree_sqdiff(x, y)), rtol=tol)
    np.testing.assert_allclose(q, float(tree_sqnorm(y)), rtol=tol)


def test_stats_flat_dispatch_matches_ref():
    x = jax.random.normal(KEY, (1000,))
    y = jax.random.normal(jax.random.PRNGKey(1), (1000,))
    got = ops.stats_flat(x, y)               # CPU: fused-jnp reference
    want = ref.fused_stats_ref(x, y)
    for a, b in zip(got, want):
        np.testing.assert_allclose(float(a), float(b), rtol=1e-6)


def test_fused_adamw_stats_kernel_matches_ref():
    ks = jax.random.split(KEY, 4)
    p = jax.random.normal(ks[0], (700,))
    g = jax.random.normal(ks[1], (700,))
    m = jax.random.normal(ks[2], (700,))
    v = jnp.abs(jax.random.normal(ks[3], (700,)))
    kw = dict(lr=3e-4, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1,
              c1=0.7, c2=0.4, clip_scale=0.37)
    got = ops.fused_adamw_stats(p, g, m, v, **kw)
    want = ref.adamw_stats_ref(p, g, m, v, **kw)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    # the Σg² byproduct is of the RAW (pre-clip) gradient
    np.testing.assert_allclose(float(got[3]), float(jnp.sum(g * g)), rtol=1e-5)


# ------------------------------------------------------- flat adamw ----

@pytest.mark.parametrize("grad_clip", [1.0, 0.0])
def test_adamw_flat_matches_tree(grad_clip):
    params = {"w1": jax.random.normal(KEY, (64, 33)),
              "b": jax.random.normal(KEY, (65,)),
              "w2": jax.random.normal(jax.random.PRNGKey(1), (200, 3))}
    grads = jax.tree.map(lambda x: x * 0.02 + 0.1, params)
    cfg = AdamWConfig(grad_clip=grad_clip)
    st = init_adamw(params)
    st["m"] = jax.tree.map(lambda x: x * 0.5, grads)
    st["v"] = jax.tree.map(lambda x: jnp.abs(x) * 0.2, grads)
    st["count"] = jnp.asarray(5, jnp.int32)
    p1, s1, gn1 = adamw_update(params, grads, st, cfg, 1e-3)
    p2, s2, gn2, gsq2 = adamw_update_flat(
        params, grads, flat_opt_state(params, st), cfg, 1e-3)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(float(gn1), float(gn2), rtol=1e-6)
    np.testing.assert_allclose(float(gsq2), float(tree_sqnorm(grads)),
                               rtol=1e-5)
    s2_tree = unflat_opt_state(params, s2)
    for a, b in zip(jax.tree.leaves(s1["m"]), jax.tree.leaves(s2_tree["m"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    assert int(s2["count"]) == 6


def test_flat_opt_state_roundtrip():
    params = {"a": jnp.ones((10, 3), jnp.bfloat16), "b": jnp.ones((7,))}
    st = init_adamw(params)
    back = unflat_opt_state(params, flat_opt_state(params, st))
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back)):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))
    flat = init_adamw_flat(params)
    assert all(b.dtype == jnp.float32 for b in flat["m"] + flat["v"])


# ------------------------------------------- step-level equivalence ----

def _tiny_step_setup():
    from jax import set_mesh
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.launch.mesh import make_host_mesh
    from repro.data.pipeline import MarkovTokens, make_batch
    from repro.core.schedule import BatchPlan
    cfg = get_smoke_config("llama3.2-1b")
    model = build_model(cfg)
    mesh = make_host_mesh(data=1, model=1)
    src = MarkovTokens(vocab_size=cfg.vocab_size, seed=0)
    plan = BatchPlan(global_batch=4, micro_batch=2, accum_steps=2, workers=1)
    batch = jax.tree.map(jnp.asarray, make_batch(src, 0, plan, 16))
    return model, mesh, batch, set_mesh


@pytest.mark.parametrize("step_impl", ["fsdp_norm", "accum_norm"])
def test_flat_vs_tree_step_metrics_equal(step_impl):
    """Acceptance: identical (≤1e-5) loss, var_l1, grad_sqnorm and updated
    params on both FSDP-Norm and ACCUM-NORM steps."""
    from repro.distributed.train_step import (
        make_fsdp_norm_step, make_accum_norm_step)
    model, mesh, batch, set_mesh = _tiny_step_setup()
    sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    make = (make_fsdp_norm_step if step_impl == "fsdp_norm"
            else make_accum_norm_step)
    res = {}
    for stats_impl in ("tree", "flat"):
        params = model.init(jax.random.PRNGKey(0))
        opt = (init_adamw_flat(params) if stats_impl == "flat"
               else init_adamw(params))
        wrap, _, _ = make(model, AdamWConfig(), mesh, stats_impl=stats_impl,
                          params_like=params)
        with set_mesh(mesh):
            p, o, m = wrap(sds)(params, opt, batch, jnp.float32(1e-3))
        res[stats_impl] = (p, m)
    for k in ("loss", "var_l1", "grad_sqnorm", "grad_norm"):
        np.testing.assert_allclose(
            float(res["tree"][1][k]), float(res["flat"][1][k]),
            rtol=1e-5, atol=1e-8, err_msg=k)
    for a, b in zip(jax.tree.leaves(res["tree"][0]),
                    jax.tree.leaves(res["flat"][0])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


def test_stats_impl_validation():
    from repro.distributed.train_step import (
        make_fsdp_norm_step, make_accum_norm_step)
    model, mesh, _, _ = _tiny_step_setup()
    with pytest.raises(ValueError):
        make_fsdp_norm_step(model, AdamWConfig(), mesh, stats_impl="bogus")
    with pytest.raises(ValueError):
        make_fsdp_norm_step(model, AdamWConfig(), mesh, stats_impl="flat",
                            variance_impl="paper")
    with pytest.raises(ValueError):
        make_accum_norm_step(model, AdamWConfig(), mesh, stats_impl="nope")


# --------------------------------------------- launch-count proxy ----

def test_flat_tail_op_count_scales_with_buckets_not_leaves():
    """The regression the flat path exists to prevent: the statistics tail
    must issue O(buckets) reductions, not O(leaves)."""
    tree = {f"w{i}": jnp.ones((100,)) for i in range(40)}
    layout = FlatLayout.from_tree(tree)     # 40 leaves -> 1 bucket
    assert layout.num_buffers == 1
    xb, yb = layout.flatten(tree), layout.flatten(tree)

    def count_reduce(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            n += str(eqn.primitive).startswith("reduce")
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    n += count_reduce(sub.jaxpr)
        return n

    tree_jaxpr = jax.make_jaxpr(
        lambda a, b: (tree_sqdiff(a, b), tree_sqnorm(b)))(tree, tree)
    flat_jaxpr = jax.make_jaxpr(
        lambda a, b: ops.stats_flat(a[0], b[0]))(xb, yb)
    n_tree = count_reduce(tree_jaxpr.jaxpr)
    n_flat = count_reduce(flat_jaxpr.jaxpr)
    assert n_tree >= 2 * 40                  # two reductions per leaf
    assert n_flat <= 2 * layout.num_buffers  # two per bucket


@pytest.mark.parametrize("step_impl,stats_impl,params_impl,expected", [
    # flat STATS on tree-resident params (DESIGN §9): FSDP-Norm packs g_j,
    # the mean gradient g, and the params (3); ACCUM-NORM packs g and the
    # params (2).  The old tail packed g twice — THE double-pack regression.
    ("fsdp_norm", "flat", "tree", 3),
    ("accum_norm", "flat", "tree", 2),
    # flat-RESIDENT params (DESIGN §10): gradients are born flat through
    # `unflatten_for_grad`, params never leave buffer form — the
    # steady-state step performs ZERO flatten packs.
    ("fsdp_norm", "flat", "flat", 0),
    ("accum_norm", "flat", "flat", 0),
    # tree-oracle tail over flat-resident params: the one pack is the
    # updated param tree re-entering residency.
    ("fsdp_norm", "tree", "flat", 1),
    ("accum_norm", "tree", "flat", 1),
])
def test_step_pack_count(step_impl, stats_impl, params_impl, expected):
    """THE pack-count regression guard: tracing one step must show exactly
    the pack eqns its residency combination requires — 3/2 for the
    flat-stats path (mean gradient packed exactly once), and ZERO for the
    flat-resident steady state (so neither the PR 3 double-pack bug class
    nor a regression to re-packing born-flat gradients can recur).

    Counted from the traced jaxpr's `repro_layout_marker` eqns
    (`repro.analysis.count_layout_ops`) — unlike the removed
    Python-call proxy, the eqn count holds THROUGH a jit
    boundary, so the same assertion also covers the jitted step (and the
    full stats×params×local-SGD matrix, including the unflatten/adjoint
    counts, is frozen in `analysis.invariants.EXPECTED_LAYOUT_COUNTS`)."""
    from repro.analysis import count_layout_ops
    from repro.distributed.train_step import (
        make_fsdp_norm_step, make_accum_norm_step)
    model, mesh, batch, set_mesh = _tiny_step_setup()
    sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    make = (make_fsdp_norm_step if step_impl == "fsdp_norm"
            else make_accum_norm_step)
    params = model.init(jax.random.PRNGKey(0))
    wrap, _, _ = make(model, AdamWConfig(), mesh, stats_impl=stats_impl,
                      params_impl=params_impl, params_like=params)
    opt = (init_adamw_flat(params, layout=wrap.flat_layout)
           if stats_impl == "flat" else init_adamw(params))
    if params_impl == "flat":
        # entering residency packs once, OUTSIDE the step — host-side cost,
        # paid once per run, not per step
        params = tuple(wrap.flat_layout.flatten(params))
    fn = wrap(sds)                       # the real JITTED step
    with set_mesh(mesh):
        ops_seen = count_layout_ops(fn, params, opt, batch, jnp.float32(1e-3))
    assert len(ops_seen["pack"]) == expected, (
        f"{step_impl}/{stats_impl}/{params_impl}: {len(ops_seen['pack'])} "
        f"pack eqns per step (expected {expected}): {ops_seen}")


def test_count_packs_alias_removed():
    """The PR 8 one-release transition is over: the Python-call proxy is
    gone from the module and its `__all__`; `count_layout_ops` (jaxpr-eqn
    counting) is the only pack counter."""
    import repro.distributed.flatbuf as fb
    assert not hasattr(fb, "count_packs")
    assert "count_packs" not in fb.__all__
    layout = FlatLayout.from_tree({"a": jnp.zeros((4,)), "b": jnp.zeros((2,))})
    from repro.analysis import count_layout_ops
    got = count_layout_ops(
        lambda t: layout.flatten(t),
        {"a": jnp.zeros((4,)), "b": jnp.zeros((2,))})
    assert got["pack"] == [2]


def test_layout_markers_visible_inside_jit():
    """The reason the proxy was replaced: pack/unflatten events inside an
    already-jitted callable are invisible to the Python-call counter but
    present as marker eqns in the traced jaxpr."""
    from repro.analysis import count_layout_ops
    tree = {"a": jnp.ones((5,)), "b": jnp.ones((3, 2))}
    layout = FlatLayout.from_tree(tree)
    jitted = jax.jit(lambda t: layout.unflatten(layout.flatten(t)))
    got = count_layout_ops(jitted, tree)
    assert len(got["pack"]) == 1 and len(got["unflatten"]) == 1
    # the adjoint pack of a flat-resident gradient is its own kind
    bufs = tuple(layout.flatten(tree))
    grad_fn = jax.grad(lambda bs: sum(
        jnp.sum(x) for x in jax.tree.leaves(layout.unflatten_for_grad(bs))))
    got = count_layout_ops(jax.jit(grad_fn), bufs)
    assert len(got["adjoint"]) == 1 and len(got["pack"]) == 0


def test_flat_moments_sharded_over_data_axes(subproc):
    """Acceptance: with a 2-device data axis the flat moment buffers carry
    data-axis PartitionSpecs (not P()) on BOTH step impls, and per-device
    optimizer-state bytes are exactly half the replicated footprint."""
    out = subproc("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import set_mesh
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.launch.mesh import make_host_mesh
from repro.distributed.train_step import (
    make_fsdp_norm_step, make_accum_norm_step)
from repro.optim.adamw import AdamWConfig, init_adamw_flat
from repro.data.pipeline import MarkovTokens, make_batch
from repro.core.schedule import BatchPlan

cfg = get_smoke_config("llama3.2-1b")
model = build_model(cfg)
mesh = make_host_mesh(data=2, model=1)
src = MarkovTokens(vocab_size=cfg.vocab_size, seed=0)
plan = BatchPlan(global_batch=8, micro_batch=2, accum_steps=2, workers=2)
batch = jax.tree.map(jnp.asarray, make_batch(src, 0, plan, 16))
sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
for make in (make_fsdp_norm_step, make_accum_norm_step):
    params = model.init(jax.random.PRNGKey(0))
    opt = init_adamw_flat(params, shard_divisor=2)
    wrap, _, o_specs = make(model, AdamWConfig(), mesh, stats_impl="flat",
                            params_like=params)
    with set_mesh(mesh):
        _, o, _ = wrap(sds)(params, opt, batch, jnp.float32(1e-3))
    for spec in o_specs["m"] + o_specs["v"]:
        assert spec != P(), f"replicated moment spec: {spec}"
        first = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
        assert "data" in first, spec
    total = local = 0
    for buf in o["m"] + o["v"]:
        assert buf.size % 2 == 0, buf.size        # J-divisible buckets
        dim0 = buf.sharding.spec[0] if buf.sharding.spec else None
        assert dim0 not in (None,), f"unsharded live buffer: {buf.sharding}"
        total += buf.size
        local += buf.addressable_shards[0].data.size
    assert local * 2 == total, (local, total)     # ~Jx memory saving, J=2
print("SHARDED_FLAT_OK")
""", devices=2)
    assert "SHARDED_FLAT_OK" in out


# ------------------------------------------------- interpret default ----

def test_resolve_interpret_env_override(monkeypatch):
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert resolve_interpret(None) is True          # CPU container
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert resolve_interpret(None) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert resolve_interpret(None) is True
