"""Compile rehearsal, run by hand: the depth of a configuration.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py \
        --config phi3-mini-1chip --traffic adaptive-cross --layers 2 3 4

Compiles the configuration's top-rung train step, through the trainer's
own step builder, for a TPU v5e that is described and not attached
(the first chip of a `v5e:2x2` host, or all four), at each depth given, and prints
the compiler's temporaries and arguments per device.  A configuration's
depth is the largest whose step fits the chip's HBM.  Nothing runs, so
nothing here is a measurement.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

V5E_HBM_BYTES = 15.75e9      # usable HBM of one v5e chip (16 GB part)


def compile_top_step(config: dict, traffic: dict, layers: int, chips: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import AxisType, Mesh
    import repro.kernels
    import repro.kernels.ops
    from repro.configs import get_config
    from repro.distributed.train_step import (
        _opt_like_for, make_accum_norm_step, make_fsdp_norm_step)
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig

    # the chip's branch of the backend dispatch: compiled Pallas tail and
    # the TPU bucket size
    repro.kernels._backend_is_tpu = lambda: True
    repro.kernels.ops._backend_is_tpu = lambda: True
    # one host of four chips is described; a one-chip cell takes the first
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(chips, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    overrides = dict(config["program"].get("overrides", {}),
                     num_layers=layers)
    cfg = get_config(config["program"]["arch"]).replace(
        remat=config["train"]["remat"], **overrides)
    model = build_model(cfg)
    params_like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    build = (make_fsdp_norm_step if config["train"]["step_impl"] ==
             "fsdp_norm" else make_accum_norm_step)
    si, pi = config["train"]["stats_impl"], config["train"]["params_impl"]
    wrap, _, _ = build(model, AdamWConfig(), mesh, stats_impl=si,
                       params_impl=pi, params_like=params_like)
    lay = wrap.flat_layout
    p_like = (tuple(jax.ShapeDtypeStruct((n,), d) for n, d in
                    zip(lay.buffer_sizes, lay.buffer_dtypes))
              if pi == "flat" else params_like)
    o_like = _opt_like_for(si, params_like, shard_divisor=chips, layout=lay)
    mb, top = traffic["micro_batch"], traffic["rungs"][-1]
    tok = jax.ShapeDtypeStruct((top // (chips * mb), chips * mb,
                                traffic["seq_len"]), jnp.int32)
    batch = {"tokens": tok, "labels": tok}
    with jax.set_mesh(mesh):
        return wrap(batch).lower(p_like, o_like, batch, jax.ShapeDtypeStruct(
            (), jnp.float32)).compile()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--layers", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from chipbench import spec
    config = spec.load_json(spec.config_path(args.config))
    traffic = spec.load_json(spec.traffic_path(args.traffic))
    for n in args.layers:
        try:
            c = compile_top_step(config, traffic, n, args.chips)
        except Exception as e:  # noqa: BLE001 — a refusal is the answer
            print(f"layers {n}: refused: {str(e).splitlines()[0][:300]}",
                  flush=True)
            continue
        ma = c.memory_analysis()
        total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
                 + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        print(f"layers {n}: temp {ma.temp_size_in_bytes / 1e9:.3f} GB "
              f"args {ma.argument_size_in_bytes / 1e9:.3f} GB "
              f"outputs {ma.output_size_in_bytes / 1e9:.3f} GB "
              f"aliased {ma.alias_size_in_bytes / 1e9:.3f} GB "
              f"total {total / 1e9:.3f} GB "
              f"fits {total <= V5E_HBM_BYTES} "
              f"pallas {'tpu_custom_call' in c.as_text()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
