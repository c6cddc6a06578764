"""Layer-3 (cost model + SPMD divergence, DESIGN §15) tests.

The acceptance bar is the planted-regression suite: a step graph with one
extra all-gather, one with a dropped donation, one with rank-dependent
collective order, and one with a cond-branch collective mismatch — the
analyzer must flag all of them, and must pass clean on their unplanted
twins.  Plus the budget lifecycle: round-trip through `write_budget`,
symmetric drift detection, staleness in both directions, and the
`--update-budget` flow.

Planted fixtures live in tests/fixtures/costmodel/planted.py (trace-only;
nothing here compiles or executes a step).
"""

import importlib.util
import json
import pathlib

import pytest

import jax
import jax.numpy as jnp

from repro.analysis.costmodel import (
    DEFAULT_TOLERANCES, budget_diff, collective_kind, collective_profile,
    flops_estimate, load_budget, peak_memory, run_cost_checks, variant_cost,
    write_budget)
from repro.analysis.divergence import (
    branch_collective_mismatches, check_fn_divergence, collective_signature)
from repro.analysis.jaxpr_check import main_arg_attrs, trace

FIXTURE = (pathlib.Path(__file__).parent / "fixtures" / "costmodel" /
           "planted.py")


def _planted():
    spec = importlib.util.spec_from_file_location("costmodel_planted", FIXTURE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def planted():
    return _planted()


@pytest.fixture(scope="module")
def mesh(planted):
    return planted.fixture_mesh()


def _x(mesh):
    return jnp.zeros((4 * mesh.devices.size,), jnp.float32)


# ------------------------------------------- planted regression: comms ----

def test_planted_extra_allgather_flagged(planted, mesh, tmp_path):
    """The planted extra all_gather shows up in the profile (new kind, new
    bytes) and trips the budget diff with an exact op-count finding."""
    from jax import set_mesh
    x = _x(mesh)
    with set_mesh(mesh):
        clean = trace(planted.clean_step(mesh), x)
        dirty = trace(planted.extra_gather_step(mesh), x)
    p_clean = collective_profile(clean)
    p_dirty = collective_profile(dirty)
    assert "psum" in p_clean["per_kind"]
    assert "all_gather" not in p_clean["per_kind"]
    ag = p_dirty["per_kind"]["all_gather"]
    assert ag["count"] == 1 and ag["bytes"] > 0

    def metrics(traced):
        return {"collectives": collective_profile(traced)["per_kind"],
                "flatbuf": {"count": 0, "bytes": 0},
                "flops": flops_estimate(traced),
                "peak_bytes": peak_memory(traced), "donated_aliased": 0}

    budget = write_budget(tmp_path / "b.json", {"planted": metrics(clean)})
    findings = budget_diff({"planted": metrics(dirty)}, budget)
    comm = [f for f in findings if f.rule == "cost-collectives"]
    assert comm and any("all_gather" in f.message for f in comm)


# ----------------------------------------- planted regression: donation ----

def test_planted_dropped_donation_raises_watermark(planted):
    """peak_memory prices the dropped donation at exactly the
    double-allocated params buffer, and the budget diff calls out the
    aliased-input decrease."""
    n = 1 << 16
    good_fn, good_args = planted.donating_update(n)
    bad_fn, bad_args = planted.dropped_donation_update(n)
    peaks, aliased = {}, {}
    for tag, (fn, args) in (("good", (good_fn, good_args)),
                            ("bad", (bad_fn, bad_args))):
        attrs = main_arg_attrs(fn.lower(*args).as_text())
        aliased[tag] = sum(1 for a in attrs if a.aliased)
        peaks[tag] = peak_memory(trace(fn, *args), attrs)
    assert aliased["good"] >= 1 and aliased["bad"] == 0
    buf = n * 4
    assert peaks["bad"] >= peaks["good"] + buf // 2, (peaks, buf)

    base = {"collectives": {}, "flatbuf": {"count": 0, "bytes": 0},
            "flops": 100}
    findings = budget_diff(
        {"v": {**base, "peak_bytes": peaks["bad"],
               "donated_aliased": aliased["bad"]}},
        {"schema": 1, "tolerances": DEFAULT_TOLERANCES,
         "topology": {"device_count": jax.device_count()},
         "variants": {"v": {**base, "peak_bytes": peaks["good"],
                            "donated_aliased": aliased["good"]}}})
    mem = [f for f in findings if f.rule == "cost-peak-memory"]
    assert any("donation was dropped" in f.message for f in mem)
    assert any("watermark" in f.message for f in mem)


# --------------------------------------- planted regression: divergence ----

def test_planted_rank_dependent_order_flagged(planted, mesh):
    """Two traces of the order-flipping builder produce different ordered
    collective signatures -> divergence-order; the clean step is stable."""
    x = _x(mesh)
    findings = check_fn_divergence(planted.make_flipping_step(mesh), (x,),
                                   "planted/flip", mesh)
    assert [f.rule for f in findings] == ["divergence-order"]
    assert "deadlock" in findings[0].message
    assert check_fn_divergence(planted.clean_step(mesh), (x,),
                               "planted/clean", mesh) == []


def test_planted_cond_branch_mismatch_flagged(planted, mesh):
    """A psum under only one cond branch -> divergence-cond, and the raw
    mismatch API names the cond site."""
    from jax import set_mesh
    x = _x(mesh)
    with set_mesh(mesh):
        traced = trace(planted.cond_collective_step(mesh), x)
    mismatches = branch_collective_mismatches(traced)
    assert len(mismatches) == 1
    label, sigs = mismatches[0]
    assert "cond" in label and {len(s) for s in sigs} == {0, 1}
    findings = check_fn_divergence(planted.cond_collective_step(mesh), (x,),
                                   "planted/cond", mesh)
    assert "divergence-cond" in [f.rule for f in findings]


def test_collective_signature_orders_and_scopes(planted, mesh):
    """The signature is ordered and scope-tagged: clean step = one psum,
    extra-gather step = psum then all_gather, in emission order."""
    from jax import set_mesh
    x = _x(mesh)
    with set_mesh(mesh):
        sig = collective_signature(trace(planted.extra_gather_step(mesh), x))
    kinds = [collective_kind(name) for _, name, _, _ in sig]
    assert kinds == ["psum", "all_gather"]
    assert all(ax == ("d",) for _, _, ax, _ in sig)


# ----------------------------------------------------- budget lifecycle ----

def _fake_variant(planted, mesh):
    """A StepVariant-shaped object over the cheap planted clean step, so
    the budget lifecycle tests never trace the full smoke model."""
    from repro.analysis.invariants import LayoutCounts, StepVariant
    return StepVariant(name="planted/clean", fn=planted.clean_step(mesh),
                       args=(_x(mesh),), expected=LayoutCounts(0, 0, 0),
                       spec_prefix=[], flat_groups=[], layout=None,
                       mesh=mesh)


def test_budget_roundtrip_update_and_drift(planted, mesh, tmp_path):
    """measure -> --update-budget -> clean diff; then each perturbation
    class (flops drift, collective count, peak) fires its own rule; an
    IMPROVEMENT fails symmetrically."""
    v = _fake_variant(planted, mesh)
    path = tmp_path / "analysis_budget.json"

    # missing budget is itself a finding, not a crash
    findings, checked = run_cost_checks(path, variants=[v])
    assert [f.rule for f in findings] == ["budget-stale"]
    assert "planted/clean" in checked["metrics"]

    # the update flow writes the file and reports clean
    findings, checked = run_cost_checks(path, variants=[v], update=True)
    assert findings == [] and checked["budget_updated"]
    budget = load_budget(path)
    assert budget["schema"] == 1
    assert budget["topology"]["device_count"] == jax.device_count()
    assert budget["variants"]["planted/clean"]["flops"] > 0

    # round-trip: a fresh measurement against the fresh budget is clean
    findings, _ = run_cost_checks(path, variants=[v])
    assert findings == []

    # perturbations: each metric fires its own rule, both directions
    for mutate, rule in (
            (lambda e: e.update(flops=int(e["flops"] * 2)), "cost-flops"),
            (lambda e: e.update(flops=int(e["flops"] * 0.5)), "cost-flops"),
            (lambda e: e["collectives"]["psum"].update(
                count=e["collectives"]["psum"]["count"] + 1),
             "cost-collectives"),
            (lambda e: e.update(peak_bytes=int(e["peak_bytes"] * 2)),
             "cost-peak-memory")):
        b = json.loads(path.read_text())
        mutate(b["variants"]["planted/clean"])
        (tmp_path / "mut.json").write_text(json.dumps(b))
        findings, _ = run_cost_checks(tmp_path / "mut.json", variants=[v])
        assert rule in [f.rule for f in findings], (rule, findings)


def test_budget_staleness_both_directions():
    """Variant-set drift between budget and matrix is a finding either way,
    and a topology mismatch short-circuits everything else."""
    m = {"collectives": {}, "flatbuf": {"count": 0, "bytes": 0}, "flops": 1,
         "peak_bytes": 1, "donated_aliased": 0}
    budget = {"schema": 1, "tolerances": DEFAULT_TOLERANCES,
              "topology": {"device_count": jax.device_count()},
              "variants": {"only/in/budget": dict(m)}}
    findings = budget_diff({"only/in/matrix": dict(m)}, budget)
    locs = {f.location for f in findings}
    assert {f.rule for f in findings} == {"budget-stale"}
    assert locs == {"only/in/budget", "only/in/matrix"}

    stale_topo = {**budget, "topology": {"device_count":
                                         jax.device_count() + 7}}
    findings = budget_diff({"only/in/matrix": dict(m)}, stale_topo)
    assert len(findings) == 1 and "device_count" in findings[0].message


def test_committed_budget_matches_matrix_shape():
    """The committed analysis_budget.json names exactly the traced matrix's
    variants (staleness guard at the repo level, no tracing needed)."""
    from repro.analysis.invariants import EXPECTED_LAYOUT_COUNTS
    repo = pathlib.Path(__file__).parent.parent
    budget = load_budget(repo / "analysis_budget.json")
    assert budget is not None, "analysis_budget.json must be committed"
    names = set(budget["variants"])
    assert "serve_decode/rung2" in names
    # every train combo in the expected matrix has a budget entry
    for (impl, stats, params) in EXPECTED_LAYOUT_COUNTS:
        if impl == "serve_decode":
            continue
        assert f"{impl}/{stats}/{params}" in names, (impl, stats, params)
    for v in budget["variants"].values():
        assert {"collectives", "flatbuf", "flops", "peak_bytes",
                "donated_aliased"} <= set(v)


# -------------------------------------------------- engine lowered-HLO ----

def test_engine_lower_step_exposes_hlo_without_compiling():
    """`BucketedEngine.lower_step` hands layer 3 the lowered module (text
    with donation aliasing visible) while stats prove nothing compiled and
    the cache stayed empty."""
    from jax import set_mesh
    from repro.configs import get_smoke_config
    from repro.core.schedule import parse_ladder
    from repro.data.pipeline import MarkovTokens, make_batch
    from repro.distributed.engine import BucketedEngine
    from repro.distributed.train_step import make_accum_norm_step
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig, init_adamw

    cfg = get_smoke_config("llama3.2-1b")
    model = build_model(cfg)
    mesh = make_host_mesh(data=1, model=1)
    params = model.init(jax.random.PRNGKey(0))
    wrap, _, _ = make_accum_norm_step(model, AdamWConfig(), mesh,
                                      params_like=params)
    ladder = parse_ladder("2:1,2:2", workers=1)
    engine = BucketedEngine(wrap, ladder, mesh=mesh, params_like=params,
                            opt_like=init_adamw(params))
    src = MarkovTokens(vocab_size=cfg.vocab_size, seed=0)
    with set_mesh(mesh):
        batch = jax.tree.map(jnp.asarray, make_batch(src, 0, ladder[0], 16))
    lowered = engine.lower_step(batch)
    text = lowered.as_text()
    assert "func.func" in text and "tf.aliasing_output" in text
    attrs = main_arg_attrs(text)
    assert sum(1 for a in attrs if a.aliased) > 0
    assert engine.stats.compiles == 0 and engine.stats.hits == 0
