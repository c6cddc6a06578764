"""Compile-only checks against a described (not attached) TPU v5e.

The TPU compiler is installed with JAX, so these tests build the main
path's Pallas kernels at real sizes and full-width train steps for a
`v5e:2x2` topology that is described rather than attached: what the chip's
compiler refuses (unaligned blocks, unpartitionable Mosaic calls, a step
that does not fit HBM) fails here, at no chip time.  Nothing runs, so
nothing here is a measurement.

The topology is described inside a fixture (never at import), because only
one process at a time may load the TPU library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

V5E_HBM_BYTES = 15.75e9        # usable HBM of one v5e chip (16 GB part)
BUCKET = (4 << 20) // 4        # one 4 MiB f32 flat bucket


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip cannot be read back from
    # the persistent cache without one: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Steer the backend dispatch to its TPU branch: compiled Pallas flat
    tail and the 4 MiB TPU bucket size, as on the chip."""
    import repro.kernels
    import repro.kernels.ops
    monkeypatch.setattr(repro.kernels, "_backend_is_tpu", lambda: True)
    monkeypatch.setattr(repro.kernels.ops, "_backend_is_tpu", lambda: True)


def _kernel_case(name, sds):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.fused_adamw import fused_adamw_stats
    from repro.kernels.fused_stats import fused_stats
    from repro.kernels.rmsnorm import rmsnorm
    from repro.kernels.sqdiff_norm import sqdiff_norm
    bucket = sds((BUCKET,))
    if name == "fused_stats":
        return (lambda x, y: fused_stats(x, y, interpret=False),
                (bucket, bucket))
    if name == "sqdiff_norm":
        return (lambda x, y: sqdiff_norm(x, y, interpret=False),
                (bucket, bucket))
    if name == "fused_adamw_stats":
        return (lambda p, g, m, v, s: fused_adamw_stats(
            p, g, m, v, lr=s[0], beta1=0.9, beta2=0.95, eps=1e-8,
            weight_decay=0.1, c1=s[1], c2=s[2], clip_scale=s[3],
            interpret=False), (bucket,) * 4 + (sds((4,)),))
    # MicroLlama-300M widths: d_model 1024, 16 heads of 64, seq 2048, micro 4
    if name == "rmsnorm":
        return (lambda x, s: rmsnorm(x, s, interpret=False),
                (sds((4 * 2048, 1024)), sds((1024,))))
    assert name == "flash_attention"
    qkv = sds((4, 2048, 16, 64))
    return (lambda q, k, v: flash_attention(q, k, v, interpret=False),
            (qkv, qkv, qkv))


@pytest.mark.parametrize("name", ["fused_stats", "sqdiff_norm",
                                  "fused_adamw_stats", "rmsnorm",
                                  "flash_attention"])
def test_kernel_compiles_for_v5e(one_chip, name):
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    fn, args = _kernel_case(name, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_step(topo, arch, step_impl, stats_impl, params_impl, *,
                  workers, accum, micro, seq=2048):
    """Compile one full-width train step (remat full) on a `workers`-chip
    data mesh of the described topology; returns the compiled step."""
    from repro.configs import get_config
    from repro.distributed.train_step import (
        _opt_like_for, make_accum_norm_step, make_fsdp_norm_step)
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig
    mesh = Mesh(np.array(topo.devices[:workers]).reshape(workers, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    model = build_model(get_config(arch).replace(remat="full"))
    params_like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    build = (make_accum_norm_step if step_impl == "accum_norm"
             else make_fsdp_norm_step)
    wrap, _, _ = build(model, AdamWConfig(), mesh, stats_impl=stats_impl,
                       params_impl=params_impl, params_like=params_like)
    lay = wrap.flat_layout
    p_like = (tuple(jax.ShapeDtypeStruct((n,), d) for n, d in
                    zip(lay.buffer_sizes, lay.buffer_dtypes))
              if params_impl == "flat" else params_like)
    o_like = _opt_like_for(stats_impl, params_like, shard_divisor=workers,
                           layout=lay)
    tok = jax.ShapeDtypeStruct((accum, workers * micro, seq), jnp.int32)
    batch = {"tokens": tok, "labels": tok}
    with jax.set_mesh(mesh):
        return wrap(batch).lower(p_like, o_like, batch, jax.ShapeDtypeStruct(
            (), jnp.float32)).compile()


def _hbm_bytes(compiled):
    ma = compiled.memory_analysis()
    return ma.temp_size_in_bytes + ma.argument_size_in_bytes


def test_full_width_accum_norm_step_fits_one_v5e(topo, as_tpu):
    """MicroLlama-300M at published widths, ACCUM-NORM with the flat/flat
    residency (M=2 x micro 4, seq 2048): the compiled Pallas tail is in the
    step and temporaries plus arguments fit one chip's HBM."""
    compiled = _compile_step(topo, "microllama-300m", "accum_norm", "flat",
                             "flat", workers=1, accum=2, micro=4)
    assert "tpu_custom_call" in compiled.as_text()
    assert _hbm_bytes(compiled) <= V5E_HBM_BYTES


@pytest.mark.parametrize("step_impl", ["fsdp_norm", "accum_norm"])
def test_four_chip_flat_tail_step_compiles(topo, as_tpu, step_impl):
    """J=4 data mesh, flat/flat: the Pallas tail lowers inside the
    FSDP-Norm manual region and on the GSPMD-sharded ACCUM-NORM buckets."""
    compiled = _compile_step(topo, "microllama-300m", step_impl, "flat",
                             "flat", workers=4, accum=2, micro=2)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text or "all-gather" in text
    assert _hbm_bytes(compiled) <= V5E_HBM_BYTES


def test_pallas_tail_makes_no_relayout_copies(topo, as_tpu, monkeypatch):
    """The compiled Pallas tail streams the flat buffers as they are: the
    J=4 FSDP-Norm step needs at most 10% more temporaries with it than with
    the XLA tail.  A (rows, 128) view of a 1-D buffer is a relayout copy on
    TPU; through such views this step needed 25% more, and TinyLlama-1.1B's
    at micro-batch 2 did not fit a chip."""
    import repro.kernels.ops
    kw = dict(workers=4, accum=1, micro=2)
    pallas = _compile_step(topo, "microllama-300m", "fsdp_norm", "flat",
                           "flat", **kw)
    monkeypatch.setattr(repro.kernels.ops, "_backend_is_tpu", lambda: False)
    xla = _compile_step(topo, "microllama-300m", "fsdp_norm", "flat", "flat",
                        **kw)
    assert "tpu_custom_call" in pallas.as_text()
    assert "tpu_custom_call" not in xla.as_text()
    temp = lambda c: c.memory_analysis().temp_size_in_bytes
    assert temp(pallas) <= 1.1 * temp(xla), (temp(pallas), temp(xla))
