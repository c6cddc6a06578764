"""The correctness check's control and planted faults, run by hand.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 \
        --variants bfloat16 half_batch

Puts the configuration's plain reference, following the cell's own step
(ACCUM-NORM or FSDP-Norm), in the trainer's place, computed a precision
lower (`bfloat16`, the control) or with a fault planted in its step
(`half_batch`: half of each worker's rows of every microbatch left out,
the mean taken over the rest; `exchange`, on several workers: the last
worker's rows replaced by the first's, so its contribution is missing from
the reduced gradient), and compares it with the float32 reference exactly
as a run compares the trainer.  The
readings, one line per seed and variant, set the upper end of each
number's limit (PERF.md).  Runs at the cell's own sizes on the machine it
is started on; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def control_numbers(resolved: dict, seed: int, variants: list[str],
                    seconds: float) -> dict:
    """Variant -> the numbers a run of `seconds` compares, with the variant
    in the trainer's place (one float32 reference for all of them)."""
    import jax
    import jax.numpy as jnp
    from chipbench import compare, harness, spec
    from chipbench.reference import train as ref_train
    config, traffic = resolved["config"], resolved["traffic"]
    chips = resolved["workload"]["chips"]
    k = traffic["setup_steps"]
    total_steps = harness.plan_steps(resolved["cell"], traffic,
                                     seconds)["total"]
    ref = harness.reference(config, traffic, chips, seed, k, total_steps)
    beta1 = config["optimizer"]["beta1"]
    out = {}
    for variant in variants:
        kw = ({"dtype": jnp.bfloat16} if variant == "bfloat16"
              else {"fault": variant})
        side = harness.reference(config, traffic, chips, seed, k,
                                 total_steps, keep_params=True, **kw)
        # the initial weights live on the device only beside the side's
        # host copy, never beside a reference run's working set
        ref_mod = spec.reference_module(config["reference"])
        p0 = ref_train.initial_weights(
            ref_mod, ref_mod.sizes(config["model"]), seed,
            jax.devices()[:chips])
        ref.prog_change = ref_train.change_from(p0, side.params)
        del p0
        hist = {"loss": side.loss, "var_l1": side.var_l1,
                "grad_sqnorm": side.grad_sqnorm,
                "global_batch": side.batch}
        first_m = {key: v * (1.0 - beta1)
                   for key, v in side.first_grad.items()}
        out[variant] = compare.numbers(
            hist, first_m, ref, beta1, traffic["schedule"], traffic["rungs"],
            traffic["eta"], traffic["test_interval"])
        del side
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", required=True,
                    choices=("bfloat16", "half_batch", "exchange"))
    args = ap.parse_args(argv)
    from chipbench import harness, spec
    bench = spec.benchmark()
    resolved = spec.resolve(bench, args.workload)
    harness.device_check(resolved["workload"]["chips"], require_tpu=True)
    for seed in args.seeds:
        for variant, nums in control_numbers(
                resolved, seed, args.variants, bench["run_seconds"]).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "variant": variant, "numbers": nums}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
