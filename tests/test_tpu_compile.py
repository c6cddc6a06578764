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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

V5E_HBM_BYTES = 15.75e9        # usable HBM of one v5e chip (16 GB part)
# what the allocator of one v5e chip hands out (`memory_stats()["bytes_limit"]`
# read on the chip: 15.75 GiB)
V5E_ALLOCATOR_BYTES = 16_909_336_064
BUCKET = (4 << 20) // 4        # one 4 MiB f32 flat bucket
FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_dkv",
                 "flash_attention_dq")


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
    if name == "flash_attention":
        qkv = sds((4, 2048, 16, 64))
        return (lambda q, k, v: flash_attention(q, k, v, interpret=False),
                (qkv, qkv, qkv))
    # the forward and both backward kernels at the Phi-3-mini cell's
    # attention: 32 heads of 96, seq 2048, micro-batch 2
    assert name == "flash_attention_bwd"
    qkv = sds((2, 2048, 32, 96))
    return (jax.grad(lambda q, k, v, w: jnp.sum(w * flash_attention(
        q, k, v, interpret=False)), (0, 1, 2)), (qkv,) * 4)


@pytest.mark.parametrize("name", ["fused_stats", "sqdiff_norm",
                                  "fused_adamw_stats", "rmsnorm",
                                  "flash_attention", "flash_attention_bwd"])
def test_kernel_compiles_for_v5e(one_chip, name):
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    fn, args = _kernel_case(name, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if name == "flash_attention_bwd":
        for kernel in FLASH_KERNELS:
            assert kernel in compiled.as_text(), kernel


def _compile_step(topo, arch, step_impl, stats_impl, params_impl, *,
                  workers, accum, micro, seq=2048, layers=None,
                  overrides=None):
    """Compile one full-width train step (remat full; `layers` deep where
    given, `overrides` of the model config applied) on a `workers`-chip
    data mesh of the described topology; returns the compiled step."""
    from repro.configs import get_config
    from repro.distributed.train_step import (
        _opt_like_for, make_accum_norm_step, make_fsdp_norm_step)
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig
    mesh = Mesh(np.array(topo.devices[:workers]).reshape(workers, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    cfg = get_config(arch).replace(remat="full", **(overrides or {}))
    model = build_model(cfg if layers is None else cfg.replace(
        num_layers=layers))
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


def test_phi3_mini_step_runs_flash_attention(topo, as_tpu):
    """The benchmark's Phi-3-mini step (3 layers at published widths,
    ACCUM-NORM M=4 x micro 2, seq 2048) runs attention in the flash
    kernels, forward and backward: no (2, 32, 512, 2048) f32 score chunk
    of the jnp path is left, and the step fits one chip."""
    compiled = _compile_step(topo, "phi3-mini-3.8b", "accum_norm", "flat",
                             "flat", workers=1, accum=4, micro=2, layers=3)
    text = compiled.as_text()
    for kernel in FLASH_KERNELS:
        assert kernel in text, kernel
    assert "f32[2,32,512,2048]" not in text
    assert _hbm_bytes(compiled) <= V5E_HBM_BYTES


def test_deepseek_v2_lite_step_fits_one_v5e(topo, as_tpu):
    """The benchmark's DeepSeek-V2-Lite cut (`deepseek-v2-lite-1chip`: 5
    layers, 8 of 64 experts held, 12,800 vocabulary rows, at published
    widths), its top-rung ACCUM-NORM step as its cell runs it
    (`adaptive-cross-4k`: M=8 x micro 2, seq 4096, tree-resident weights):
    MLA in the flash kernels at query/key width 192 and value width 128,
    the experts in the megablox grouped-matmul kernels, no jnp score
    matrix, temporaries plus arguments within one chip's HBM, and room in
    the chip's allocator for one more copy of the weights (the benchmark
    copies them out after step 2 while that step may still hold its
    temporaries)."""
    import json
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip")
    with open(os.path.join(bench, "configs",
                           "deepseek-v2-lite-1chip.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", "adaptive-cross-4k.json")) as f:
        traffic = json.load(f)
    program, train = config["program"], config["train"]
    overrides = dict(program["overrides"])
    layers = overrides.pop("num_layers")
    micro = traffic["micro_batch"]
    compiled = _compile_step(topo, program["arch"], train["step_impl"],
                             train["stats_impl"], train["params_impl"],
                             workers=1, accum=traffic["rungs"][-1] // micro,
                             micro=micro, seq=traffic["seq_len"],
                             layers=layers, overrides=overrides)
    text = compiled.as_text()
    for kernel in FLASH_KERNELS:
        assert kernel in text, kernel
    assert not re.search(r"f32\[\d+,16,4096,4096\]", text)
    # gate, up and down: forward, recomputed and the input's gradient
    # (`gmm`), the weights' gradient (`tgmm`), for each chunk of the
    # dispatch buffer (`moe.buffer_chunks`: 4 of 12,288 rows here)
    from repro.configs import get_config
    from repro.models.moe import buffer_chunks
    m = get_config(program["arch"]).replace(**overrides).moe
    _, chunks = buffer_chunks(micro * traffic["seq_len"], m.top_k, m.held,
                              m.num_experts)
    assert len(re.findall(r"%gmm(?:\.\d+)? = ", text)) == 9 * chunks
    assert len(re.findall(r"%tgmm(?:\.\d+)? = ", text)) == 3 * chunks
    assert "ragged-dot" not in text
    weights = compiled.memory_analysis().argument_size_in_bytes / 3
    assert _hbm_bytes(compiled) <= V5E_HBM_BYTES
    assert _hbm_bytes(compiled) + weights <= V5E_ALLOCATOR_BYTES


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
    from repro.models.attention import sdpa_self
    # attention on the jnp path in both steps: the tail alone differs
    monkeypatch.setattr(repro.kernels.ops, "attention_core", sdpa_self)
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
