"""pim.autotune: cost-model-driven search, plan application, persistence.

Every tuned configuration must compute the identical integer GEMM — the
tuner changes speed, never results — and the pick can never lose to the
hardcoded default because the default is always in the timed race."""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.pim import autotune, engine


@pytest.fixture(autouse=True)
def _fresh():
    engine.clear_cache()        # also clears the tuner table + counters
    autotune.enable(False)
    yield
    engine.clear_cache()
    autotune.enable(False)


def _operands(k, m=2, o=4, n_bits=8, seed=0):
    rng = np.random.default_rng(seed)
    hi = np.uint64(1) << np.uint64(n_bits)
    return (rng.integers(0, hi, size=(m, k), dtype=np.uint64),
            rng.integers(0, hi, size=(o, k), dtype=np.uint64))


# --------------------------------------------------------------------------
# candidate enumeration
# --------------------------------------------------------------------------

def test_candidates_cover_the_search_grid_sorted():
    cands = autotune.candidates(24, 8, (2, 8), "raw")
    execable = [p for p in cands if p.chunk > 0]
    assert {p.model for p in execable} == set(autotune.PARTITIONED_MODELS)
    assert {p.n_cols for p in execable} == set(autotune.GEOMETRIES)
    assert {p.backend for p in execable} == set(autotune.STATE_BACKENDS)
    # serial multiplier algorithms rank in the race but cannot execute
    serial = {p.model for p in cands if p.chunk == 0}
    assert {"serial_fast", "compressor42", "baseline"} <= serial
    pred = [p.predicted_us for p in cands]
    assert pred == sorted(pred)


def test_pim_sim_candidates_are_callback_safe():
    """Inside jax.pure_callback only the jax-free interpreter may run."""
    cands = autotune.candidates(24, 8, (2, 8), "pim_sim")
    backends = {p.backend for p in cands if p.chunk > 0}
    assert backends == set(autotune.CALLBACK_BACKENDS) == {"numpy"}


def test_tune_key_buckets_batch_rows():
    k = autotune.tune_key(24, 8, "minimal", (5, 16), "raw")
    assert k == autotune.tune_key(24, 8, "minimal", (8, 16), "raw")
    assert k != autotune.tune_key(24, 8, "minimal", (9, 16), "raw")
    assert k != autotune.tune_key(24, 8, "minimal", (5, 16), "pim_sim")


# --------------------------------------------------------------------------
# the search
# --------------------------------------------------------------------------

def test_autotune_never_loses_to_the_default():
    plan = autotune.autotune(12, 8, (2, 4), "raw", trials=1, top_k=2)
    assert plan.source == "trial"
    assert plan.default_us > 0, "the default must have raced"
    assert plan.vs_default >= 1.0
    assert plan.trial_us <= plan.default_us


def test_autotune_caches_and_counts():
    p1 = autotune.autotune(12, 8, (2, 4), "raw", trials=0)
    info = engine.cache_info()
    assert info.tune_misses == 1 and info.tune_hits == 0
    p2 = autotune.autotune(12, 8, (2, 4), "raw", trials=0)
    assert p2 is p1
    info = engine.cache_info()
    assert info.tune_hits == 1
    # trials are counted through cache_info too
    engine.clear_cache()
    autotune.autotune(12, 8, (2, 4), "raw", trials=1, top_k=2)
    assert engine.cache_info().tune_trials >= 3  # top_k + default


def test_plan_attached_to_compiled_artifact():
    plan = autotune.autotune(12, 8, (2, 4), "raw", trials=0)
    art = engine.compile_matmul(min(plan.chunk, 12), 8, model=plan.model,
                                n_cols=plan.n_cols)
    assert art.plan is plan
    # cache hits carry the plan with them
    assert engine.compile_matmul(min(plan.chunk, 12), 8, model=plan.model,
                                 n_cols=plan.n_cols).plan is plan


def test_tuned_matmul_bit_exact_vs_default():
    x, w = _operands(12)
    want = x.astype(object) @ w.T.astype(object)
    default = engine.matmul_int(x, w, 8)
    for mode in ("raw", "pim_sim"):
        plan = autotune.autotune(12, 8, (2, 4), mode, trials=0)
        tuned = engine.matmul_int(x, w, 8, plan=plan)
        assert np.array_equal(tuned.astype(object), want), mode
        assert np.array_equal(tuned, default), mode


def test_tune_ctx_lookup_is_gated_on_enable():
    x, w = _operands(12)
    plan = autotune.autotune(12, 8, (2, 4), "pim_sim", trials=0)
    # disabled: lookup returns None, matmul takes the default path
    assert autotune.lookup(12, 8, shape=(2, 4), pim_mode="pim_sim") is None
    autotune.enable(True)
    got = autotune.lookup(12, 8, shape=(2, 4), pim_mode="pim_sim")
    assert got is plan
    before = engine.cache_info().tune_hits
    y = engine.matmul_int(x, w, 8, tune_ctx="pim_sim")
    assert engine.cache_info().tune_hits == before + 1
    assert np.array_equal(y, engine.matmul_int(x, w, 8))


def test_sim_linear_tuned_matches_untuned_bit_exactly():
    """The serving contract: a tuned pim_sim decode changes nothing."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 6)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(6, 4)).astype(np.float32))
    ref = np.asarray(engine.sim_linear(x, w))
    # sim_linear quantizes to 7 bits and multiplies at 8 (offset-shifted)
    autotune.autotune(6, 8, (2, 4), "pim_sim", trials=0)
    autotune.enable(True)
    tuned = np.asarray(engine.sim_linear(x, w))
    assert np.array_equal(tuned, ref)
    assert engine.cache_info().tune_hits >= 1
    # and under jit (the scheduler's decode path)
    jitted = np.asarray(jax.jit(engine.sim_linear)(x, w))
    assert np.array_equal(jitted, ref)


# --------------------------------------------------------------------------
# persistence
# --------------------------------------------------------------------------

def test_table_roundtrip_preserves_picks(tmp_path):
    p_raw = autotune.autotune(12, 8, (2, 4), "raw", trials=0)
    p_sim = autotune.autotune(12, 8, (2, 4), "pim_sim", trials=0)
    path = str(tmp_path / "table.json")
    assert autotune.save_table(path) == 2
    engine.clear_cache()
    assert autotune.table_info().size == 0
    assert autotune.load_table(path) == 2
    autotune.enable(True)
    for orig, mode in ((p_raw, "raw"), (p_sim, "pim_sim")):
        got = autotune.lookup(12, 8, shape=(2, 4), pim_mode=mode)
        assert got is not None and got.source == "table"
        assert (got.model, got.n_cols, got.chunk, got.backend) == \
            (orig.model, orig.n_cols, orig.chunk, orig.backend)
    # reloaded picks execute bit-exactly
    x, w = _operands(12)
    plan = autotune.lookup(12, 8, shape=(2, 4), pim_mode="raw")
    assert np.array_equal(engine.matmul_int(x, w, 8, plan=plan),
                          engine.matmul_int(x, w, 8))


def test_table_version_mismatch_raises(tmp_path):
    path = tmp_path / "stale.json"
    path.write_text(json.dumps({"version": 0, "entries": {}}))
    with pytest.raises(ValueError, match="version"):
        autotune.load_table(str(path))


def test_clear_cache_clears_the_tuner_table():
    autotune.autotune(12, 8, (2, 4), "raw", trials=0)
    assert autotune.table_info().size == 1
    engine.clear_cache()
    info = autotune.table_info()
    assert info.size == 0 and info.misses == 0 and info.trials == 0


def test_cache_info_merges_tune_counters():
    info = engine.cache_info()
    assert (info.tune_hits, info.tune_misses, info.tune_trials) == (0, 0, 0)
    autotune.autotune(12, 8, (2, 4), "raw", trials=0)
    autotune.autotune(12, 8, (2, 4), "raw", trials=0)
    info = engine.cache_info()
    assert info.tune_misses == 1 and info.tune_hits == 1


def test_summary_mentions_state_and_a_pick():
    assert autotune.summary().startswith("off, 0 plan(s)")
    autotune.enable(True)
    plan = autotune.autotune(12, 8, (2, 4), "raw", trials=0)
    s = autotune.summary()
    assert s.startswith("on, 1 plan(s)")
    assert plan.model in s and str(plan.n_cols) in s


# --------------------------------------------------------------------------
# warmup + the linear split rule
# --------------------------------------------------------------------------

def test_plan_for_params_walks_stacked_layer_leaves():
    params = {"stacked": np.zeros((3, 6, 8), np.float32),
              "flat": np.zeros((6, 8), np.float32),
              "other": np.zeros((12, 4), np.float32),
              "vec": np.zeros((5,), np.float32)}
    n = autotune.plan_for_params(params, max_batch=2, trials=0)
    assert n == 2   # (6, 8) deduplicates across the 2-D and 3-D leaves
    autotune.enable(True)
    assert autotune.lookup(6, 8, shape=(2, 8), pim_mode="pim_sim") is not None
    assert autotune.lookup(12, 8, shape=(2, 4),
                           pim_mode="pim_sim") is not None


def test_autotune_linear_races_the_int8_lowerings():
    plan = autotune.autotune_linear(4, 8, 8, trials=1)
    assert plan.kind == "linear"
    assert plan.model in ("quant", "quant_tp")
    assert plan.key == "linear:t4d8o8"
    assert plan.trial_us > 0
    # cached on the second ask
    assert autotune.autotune_linear(4, 8, 8) is plan


def test_autotune_linear_skips_quant_tp_only_without_a_model_axis():
    """quant_tp is left out of the race exactly when no active mesh has a
    "model" axis wider than 1 (it would *be* quant); with one, both run."""
    from repro.dist import context as dctx
    from repro.launch.mesh import make_mesh

    plan = autotune.autotune_linear(4, 8, 8, trials=1)
    assert plan.model == "quant"
    assert autotune.table_info().trials == 1
    with dctx.use_mesh(make_mesh((4,), ("model",))):
        plan = autotune.autotune_linear(4, 8, 8, trials=1, force=True)
    assert plan.model in ("quant", "quant_tp")
    assert autotune.table_info().trials == 3


def test_autotune_linear_propagates_a_failing_lowering(monkeypatch):
    """A lowering that fails to run is an error, not a dropped candidate."""
    from repro.models import layers

    def broken(x, w, mode=None):
        raise RuntimeError(f"{mode} lowering failed")

    monkeypatch.setattr(layers, "linear", broken)
    with pytest.raises(RuntimeError, match="quant lowering failed"):
        autotune.autotune_linear(4, 8, 8, trials=1)
