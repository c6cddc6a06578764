"""End-to-end integration: the adaptive loop trains, grows batches, and beats
noise; checkpoints round-trip through the driver."""
import math

import numpy as np
import pytest

from repro.launch.train import TrainJob, run_training, summarize


def test_adaptive_run_grows_batch_and_learns(tmp_path):
    job = TrainJob(arch="llama3.2-1b", steps=40, seq_len=64,
                   base_global_batch=4, max_global_batch=64,
                   base_micro_batch=2, max_micro_batch=4, base_accum=2,
                   eta=0.12, step_impl="accum_norm", eval_every=20,
                   log_path=str(tmp_path / "log.csv"))
    hist = run_training(job)
    s = summarize(hist)
    assert hist["global_batch"][-1] > hist["global_batch"][0], "batch must grow"
    assert hist["loss"][-1] < hist["loss"][0], "loss must decrease"
    assert all(math.isfinite(l) for l in hist["loss"])
    # log file written with all columns
    lines = (tmp_path / "log.csv").read_text().strip().splitlines()
    assert len(lines) == 41 and lines[0].startswith("step,")


def test_constant_schedule_stays_constant():
    job = TrainJob(arch="llama3.2-1b", schedule="constant", steps=6,
                   seq_len=32, base_global_batch=8, max_global_batch=8,
                   base_micro_batch=2, max_micro_batch=2, base_accum=4,
                   eval_every=0)
    hist = run_training(job)
    assert set(hist["global_batch"]) == {8}


def test_stagewise_schedule_ramps():
    job = TrainJob(arch="llama3.2-1b", schedule="stagewise", steps=30,
                   seq_len=32, total_samples=600,
                   stages=((0.2, 8), (0.2, 16), (0.6, 32)),
                   base_micro_batch=2, max_micro_batch=4, base_accum=2,
                   eval_every=0)
    hist = run_training(job)
    batches = hist["global_batch"]
    assert batches[0] == 8
    assert max(batches) == 32
    assert sorted(set(batches)) == [8, 16, 32]


def test_checkpoint_written(tmp_path):
    from repro.checkpoint.store import latest_step
    job = TrainJob(arch="llama3.2-1b", steps=3, seq_len=32,
                   base_global_batch=4, max_global_batch=4,
                   base_micro_batch=2, max_micro_batch=2, base_accum=1,
                   eval_every=0, checkpoint_dir=str(tmp_path / "ckpt"))
    run_training(job)
    assert latest_step(str(tmp_path / "ckpt")) == 3


def test_sequence_length_warmup():
    """Paper §2: sequence-length warmup composes with batch schedules."""
    job = TrainJob(arch="llama3.2-1b", schedule="constant", steps=12,
                   total_samples=12 * 8, seq_len=64,
                   seq_stages=((0.3, 16), (0.3, 32), (0.4, 64)),
                   base_global_batch=8, max_global_batch=8,
                   base_micro_batch=2, max_micro_batch=2, base_accum=2,
                   eval_every=0)
    hist = run_training(job)
    assert hist["loss"][0] > 0  # ran
    assert len(hist["step"]) == 12


@pytest.mark.parametrize("argv,smoke,remat", [
    ([], True, "none"),                                  # defaults unchanged
    (["--no-smoke", "--remat", "full"], False, "full"),  # published widths
    (["--smoke", "--remat", "full"], True, "full"),
])
def test_cli_smoke_and_remat_reach_model_config(argv, smoke, remat):
    """`--no-smoke` turns the default-on smoke preset off, and `--remat`
    feeds the model's own recomputation switch."""
    from repro.configs import get_config, get_smoke_config
    from repro.launch.train import model_config, parse_job
    job = parse_job(["--arch", "microllama-300m", *argv])
    assert (job.smoke, job.remat) == (smoke, remat)
    base = (get_smoke_config if smoke else get_config)("microllama-300m")
    assert model_config(job) == base.replace(remat=remat)
    assert model_config(job).d_model == (128 if smoke else 1024)


_SHARDED_INIT = """
import json, sys
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import get_smoke_config
from repro.core.schedule import BatchPlan
from repro.data.pipeline import MarkovTokens, make_batch
from repro.distributed.train_step import make_accum_norm_step, make_fsdp_norm_step
from repro.launch.mesh import make_host_mesh
from repro.launch.train import init_train_state
from repro.models import build_model
from repro.optim.adamw import AdamWConfig, init_adamw, init_adamw_flat

step_impl, impl = sys.argv[1], sys.argv[2]
cfg = get_smoke_config("llama3.2-1b")
model = build_model(cfg)
mesh = make_host_mesh(data=2)
key = jax.random.PRNGKey(0)
build = make_accum_norm_step if step_impl == "accum_norm" else make_fsdp_norm_step
plan = BatchPlan(global_batch=8, micro_batch=2, accum_steps=2, workers=2)
batch = {k: jnp.asarray(v) for k, v in make_batch(
    MarkovTokens(vocab_size=cfg.vocab_size, seed=0), 0, plan, 16).items()}
sds = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch)

def first_loss(params, opt, wrap):
    with jax.set_mesh(mesh):
        return float(wrap(sds)(params, opt, batch, jnp.float32(1e-3))[2]["loss"])

# eager init on the default device
eager = model.init(key)
wrap, p_specs, o_specs = build(model, AdamWConfig(), mesh, stats_impl=impl,
                               params_impl=impl, params_like=eager)
layout = wrap.flat_layout
opt = (init_adamw_flat(eager, shard_divisor=2, layout=layout) if impl == "flat"
       else init_adamw(eager))
params = tuple(layout.flatten(eager)) if impl == "flat" else eager
want_leaves = [np.asarray(a) for a in jax.tree.leaves(params)]
want = first_loss(params, opt, wrap)

# sharded: born in the step's own layout
wrap, p_specs, o_specs = build(model, AdamWConfig(), mesh, stats_impl=impl,
                               params_impl=impl,
                               params_like=jax.eval_shape(model.init, key))
params, opt = init_train_state(model, key, mesh, wrap, p_specs, o_specs,
                               stats_impl=impl, params_impl=impl)
same = all(np.array_equal(a, np.asarray(b))
           for a, b in zip(want_leaves, jax.tree.leaves(params)))
# each device holds half of the largest weight and moment, not a copy
big = lambda tree: max(jax.tree.leaves(tree), key=lambda a: a.size)
split = [big(t).addressable_shards[0].data.size * 2 == big(t).size
         for t in (params, opt["m"])]
got = first_loss(params, opt, wrap)
print("INIT", json.dumps({"want": want, "got": got, "same": same,
                          "split": split}))
"""


@pytest.mark.parametrize("step_impl,impl", [("fsdp_norm", "flat"),
                                            ("accum_norm", "tree"),
                                            ("accum_norm", "flat")])
def test_sharded_init_matches_eager_init(subproc, step_impl, impl):
    """State created under jit with the step's own out_shardings holds the
    same weights as an eager init on the default device, gives the same
    first-step loss on a 2-device mesh, and leaves each device only its
    shard of the moments."""
    import json
    out = subproc(_SHARDED_INIT.replace("sys.argv[1], sys.argv[2]",
                                        f"{step_impl!r}, {impl!r}"),
                  devices=2)
    got = json.loads(next(l for l in out.splitlines()
                          if l.startswith("INIT")).split(" ", 1)[1])
    assert got["same"], got
    assert got["got"] == got["want"], got
    assert got["split"] == [True, True], got
