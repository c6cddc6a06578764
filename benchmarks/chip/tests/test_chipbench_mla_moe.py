"""DeepSeek-V2-Lite on one chip's share: the configuration file holds the
published config with its cut, the trainer's model is the file's model
width for width, the trainer agrees with the plain reference
(`mla_moe_decoder`) at a small size on seeded weights, a tiny run of the
cell through the harness is `correct`, and each fault planted under the
timed path (normalised gates, capacity dropping, the softmax scale
without YaRN's factor) makes it not so."""

import copy
import math
import time

import chipbench_tiny
from chipbench import harness, spec

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CELL = "deepseek-v2-lite-1chip.adaptive-cross-4k"
CONFIG = "deepseek-v2-lite-1chip"
SEED = 2147483659
# DeepSeek-V2-Lite's published config.json
PUBLISHED_WIDTHS = {
    "hidden_size": 2048, "intermediate_size": 10944,
    "moe_intermediate_size": 1408, "num_attention_heads": 16,
    "num_key_value_heads": 16, "kv_lora_rank": 512, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts_per_tok": 6, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "norm_topk_prob": False,
    "routed_scaling_factor": 1, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"}}

# the file's widths shrunk (the published ones' ratios kept where they
# matter: 8 experts routed over, half of them held, 3 per token)
TINY_MODEL = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4,
    num_key_value_heads=4, num_hidden_layers=3, vocab_size=256,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    moe_intermediate_size=32, n_routed_experts=4, router_experts=8,
    num_experts_per_tok=3)
TINY_ROPE = {"original_max_position_embeddings": 16}
TINY_PROGRAM = dict(
    num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
    d_ff=96, vocab_size=256,
    mla={"kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "original_max_position": 16},
    moe={"num_experts": 8, "held_experts": 4, "top_k": 3, "d_expert": 32,
         "shared_d_expert": 32})


def tiny() -> dict:
    r = copy.deepcopy(spec.resolve(spec.benchmark(), CELL))
    model = r["config"]["model"]
    model.update(TINY_MODEL)
    model["rope_scaling"] = dict(model["rope_scaling"], **TINY_ROPE)
    r["config"]["program"]["overrides"] = copy.deepcopy(TINY_PROGRAM)
    r["config"]["train"]["remat"] = "none"
    r["traffic"]["seq_len"] = 32
    r["cell"] = {"step_s": {str(b): 0.05 for b in r["traffic"]["rungs"]},
                 "limits": dict(chipbench_tiny.LIMITS)}
    return r


def reference_module():
    return spec.reference_module(
        spec.load_json(spec.config_path(CONFIG))["reference"])


def program(model_overrides: dict):
    from repro.configs import get_config
    cfg = spec.load_json(spec.config_path(CONFIG))
    return get_config(cfg["program"]["arch"]).replace(
        **copy.deepcopy(model_overrides))


# ------------------------------------------------------------ the file --

def test_file_is_the_published_config_with_its_cut():
    cfg = spec.load_json(spec.config_path(CONFIG))
    model = cfg["model"]
    assert sorted(cfg["reduced"]) == ["n_routed_experts",
                                      "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64, "vocab_size": 102400}
    assert model["router_experts"] == 64 and model["n_routed_experts"] == 8
    # the catalog's numbers sit at the file's top level as in its model
    for key, value in model.items():
        if key in cfg and key not in ("name",):
            assert cfg[key] == value, key
    # every width as published
    assert {k: model[k] for k in PUBLISHED_WIDTHS} == PUBLISHED_WIDTHS
    assert cfg["source"] == ("https://huggingface.co/deepseek-ai/"
                             "DeepSeek-V2-Lite/blob/main/config.json")


def test_trainer_model_is_the_file_model():
    ref = reference_module()
    cfg = spec.load_json(spec.config_path(CONFIG))
    m = ref.sizes(cfg["model"])
    prog = program(cfg["program"]["overrides"])
    ref.check_program(prog, m)
    for bad, match in [({"moe": {"normalize_gates": True}}, "normalize"),
                       ({"moe": {"held_experts": 16}}, "held"),
                       ({"moe": {"num_experts": 8}}, "num_experts"),
                       ({"mla": {"rope_factor": 1.0}}, "rope_factor"),
                       ({"mla": {"v_head_dim": 192}}, "v_head_dim"),
                       ({"prefix_pattern": ()}, "prefix_pattern")]:
        with pytest.raises(ValueError, match=match):
            ref.check_program(prog.replace(**bad), m)


def test_counts_at_the_cells_sizes():
    """535.06 M parameters held (as the trainer counts them too) and
    1.86 GFLOP a token, MLA 39%, the expert layers 31%, layer 0's MLP 22%
    and the head 8%."""
    ref = reference_module()
    cfg = spec.load_json(spec.config_path(CONFIG))
    m = ref.sizes(cfg["model"])
    n = ref.param_count(m)
    assert abs(n - 535.06e6) < 0.01e6, n
    assert n == program(cfg["program"]["overrides"]).param_count()
    f = ref.flops_per_token(m, 4096)
    assert abs(f - 1.86e9) < 0.01e9, f
    head = 6 * m["vocab_size"] * m["hidden_size"]
    dense = 6 * 3 * m["hidden_size"] * m["intermediate_size"]
    assert round(100 * head / f) == 8 and round(100 * dense / f) == 22


# ------------------------------------------- the trainer vs the reference --

def _both(seed=7):
    """The trainer's model and the reference's sizes at the tiny widths,
    each with its weights from one seed."""
    from repro.models import build_model
    r = tiny()
    ref = reference_module()
    m = ref.sizes(r["config"]["model"])
    prog = program(r["config"]["program"]["overrides"])
    ref.check_program(prog, m)
    model = build_model(prog)
    key = ref.seed_key(seed)
    return model, model.init(key), ref, m, ref.init(key, m)


def _leaves(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): np.asarray(v) for k, v in flat}


def test_weights_from_one_seed_are_the_same():
    _, p_prog, _, _, p_ref = _both()
    a, b = _leaves(p_prog), _leaves(p_ref)
    assert sorted(a) == sorted(b)
    for k in sorted(a):
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_loss_and_gradient_match_the_reference():
    """Mean cross entropy plus the sequence-level balance loss, and its
    gradient, leaf by leaf (float32 on the CPU)."""
    model, params, ref, m, p_ref = _both()
    tok = jax.random.randint(jax.random.PRNGKey(3), (2, 33), 0,
                             m["vocab_size"])
    batch = {"tokens": tok[:, :-1], "labels": tok[:, 1:]}
    with jax.default_matmul_precision("highest"):
        (loss, metrics), g = jax.value_and_grad(
            lambda p: model.loss(p, batch), has_aux=True)(params)
        s, n, g_ref = ref.block_grad(m)(p_ref, batch["tokens"],
                                        batch["labels"])
    assert float(metrics["aux"]) > 0
    np.testing.assert_allclose(float(loss), float(s / n), rtol=1e-5)
    a, b = _leaves(g), _leaves(g_ref)
    for k in sorted(b):
        np.testing.assert_allclose(a[k], b[k] / float(n), rtol=2e-3,
                                   atol=2e-6, err_msg=k)


# --------------------------------------------------- the cell, end to end --

def run(fault_patches=(), monkeypatch=None, seed=SEED):
    for target, value in fault_patches:
        monkeypatch.setattr(target[0], target[1], value)
    return harness.run_cell(CELL, seed, 0.5, False, time.time(),
                            resolved=tiny(), require_tpu=False)


def test_tiny_run_through_the_reference_is_correct():
    out = run()
    assert out["correct"], out["compared"]
    assert out["failed"] == 0


def _normalised_route(route):
    def planted(params, x, m):
        probs, experts, gates = route(params, x, m)
        return probs, experts, gates / jnp.sum(gates, -1, keepdims=True)
    return planted


def _capacity_route(route, factor=1.25):
    """Each expert keeps only its first factor·n·k/E pairs of the call (in
    token order), as the capacity dispatch did; the rest are dropped."""
    def planted(params, x, m):
        probs, experts, gates = route(params, x, m)
        pairs = experts.reshape(-1)
        onehot = jax.nn.one_hot(pairs, m.num_experts, dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
        cap = int(factor * pairs.shape[0] / m.num_experts)
        keep = (pos < cap).reshape(gates.shape)
        return probs, experts, jnp.where(keep, gates, 0.0)
    return planted


def _unscaled(m):
    return 1.0 / math.sqrt(m.qk_head_dim)


@pytest.mark.parametrize("fault", ["normalised_gates", "capacity_dropping",
                                   "scale_without_yarn"])
def test_fault_under_timed_path_is_caught(fault, monkeypatch):
    from repro.models import mla, moe
    patch = {
        "normalised_gates": ((moe, "route"), _normalised_route(moe.route)),
        "capacity_dropping": ((moe, "route"), _capacity_route(moe.route)),
        "scale_without_yarn": ((mla, "softmax_scale"), _unscaled),
    }[fault]
    out = run([patch], monkeypatch)
    assert not out["correct"], out["compared"]


def test_traced_run_reaches_the_readers(monkeypatch):
    """A traced tiny run goes through every reader of the cell, the new
    ones too, and records the dispatch counters; the CPU has no published
    peaks and no device trace, so the chip's peaks stand in and the
    device readers are silent: no number here is a device metric."""
    from chipbench import peaks
    monkeypatch.setattr(harness.peaks, "peaks_for",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])
    r = tiny()
    seen = {}
    real = harness.layer_context

    def keep(hist, *a, **k):
        seen["history"] = hist
        return real(hist, *a, **k)
    monkeypatch.setattr(harness, "layer_context", keep)
    out = harness.run_cell(CELL, SEED + 2, 0.5, True, time.time(),
                           resolved=r, require_tpu=False)
    assert out["correct"], out["compared"]
    assert set(out["metrics"]) <= {m["name"] for m in
                                   r["metrics"]["per_layer"]}
    assert "step_mfu" in out["metrics"]
    hist = seen["history"]
    # each step: every microbatch's tokens meet k experts, k·held/E of
    # them held here on average, over the 2 expert layers
    tokens = [b * r["traffic"]["seq_len"] for b in hist["global_batch"]]
    for rows, n in zip(hist["expert_rows"], tokens):
        assert 0 < rows <= 2 * n * 3
    assert all(x >= 1.0 for x in hist["expert_load_max"])


def _op(name, start, dur, stack):
    return {"plane": "/device:TPU:0", "line": "XLA Ops",
            "thread": "/device:TPU:0#0",
            "name": f"%{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %p)",
            "start_ns": start, "dur_ns": dur, "stats": {"tf_op": stack}}


def test_expert_readers_on_hand_made_events(monkeypatch):
    """moe_share is the `moe` scope's share of busy time; the grouped
    matmuls' roofline share is the pairs' operations (3 products, 2·d·f
    each, 4 passes) over the `expert_matmul` scope's time at the peak."""
    import types
    from chipbench import spans, trace as tr
    mark = {"plane": "/host:CPU", "line": "python", "thread": "/host:CPU#0",
            "name": tr.WINDOW_MARK, "start_ns": 0, "dur_ns": 10_000,
            "stats": {}}
    events = [mark,
              _op("gmm.1", 0, 1000, "jit(step)/jvp(moe)/expert_matmul/gmm"),
              _op("tgmm.2", 1000, 1000,
                  "jit(step)/transpose(jvp(moe))/expert_matmul/tgmm"),
              _op("fusion.3", 2000, 2000, "jit(step)/jvp(moe)/scatter-add"),
              _op("fusion.4", 4000, 4000,
                  "jit(step)/jvp(attention)/attention_core/fa")]
    monkeypatch.setattr(spans, "traced", lambda: events)
    lo, hi = tr.window(events)
    model = {"hidden_size": 4, "moe_intermediate_size": 2}
    ctx = types.SimpleNamespace(
        device_ops={0: tr.device_ops(events, 0, lo, hi)}, window_ns=(lo, hi),
        history={"expert_rows": [7.0, 10.0, 20.0]}, setup_steps=1,
        model=model, peak={"bf16_flops_per_s": 1e12})
    assert spec.metric_reader("moe_share")(ctx) == pytest.approx(50.0)
    work = 30.0 * 4 * 3 * 2 * 4 * 2
    assert spec.metric_reader("expert_matmul_roofline")(ctx) == \
        pytest.approx(100.0 * work / 1e12 / 2e-6)
    ctx.history = {"global_batch": [1]}
    assert spec.metric_reader("expert_matmul_roofline")(ctx) is None
