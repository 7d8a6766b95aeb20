"""Cost-model-driven partition autotuner: the engine's configuration planner.

The paper's central trade-off — partition count vs. peripheral/control
overhead — means the fastest crossbar configuration depends on the
workload shape.  This module turns ``pim/cost_model.py`` from descriptive
seed code into the engine's decision maker: for each compile key
``(n_terms, n_bits, model, shape, pim_mode)`` it

1. enumerates candidate configurations — partition model
   (``minimal``/``standard``/``unlimited``), crossbar geometry
   (:class:`~repro.core.operation.PartitionConfig` widened via
   ``scaled(n=...)``: a wider row fits more dot terms per chunk but pays
   more control bits per message), the implied inner-dimension chunking
   (``matmul.max_dot_terms``), the execution backend (scan / pallas /
   numpy; the quant-vs-quant_tp split rule races through
   :func:`autotune_linear`), and the multiplier algorithm (every
   ``kind="mult"`` registry entry — the NOR serial baseline plus
   ``serial_fast`` and ``compressor42`` — priced in the same race even
   though only partitioned models lower to executable dot programs);
2. scores every candidate with ``cost_model.gemm_cost`` /
   ``cost_model.mult_cost`` (predicted device latency);
3. breaks ties among the top predicted candidates with short timed trials
   on clipped operands — the hardcoded default configuration is ALWAYS in
   the trial set, so the pick can never be slower than the default on the
   machine that tuned it (``picked_vs_default >= 1.0`` by construction,
   the ``--suite autotune`` gate);
4. caches the winner: in the in-process table (hit on the next
   :func:`lookup`), attached to the ``CompiledPim`` artifact
   (``artifact.plan``), and — via :func:`save_table` /
   :func:`load_table` — in a JSON file so serving warmup
   (``serve.py --autotune-table``) reloads picks instead of re-searching.

Every tuned configuration computes the same exact integer GEMM (the
quant / quant_tp / pim_sim bit-exactness contract), so plans change
speed, never results.  ``engine.clear_cache()`` clears the table and its
counters; ``engine.cache_info()`` exposes them (``tune_hits`` /
``tune_misses`` / ``tune_trials``).
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "TunedPlan",
    "TuneInfo",
    "autotune",
    "autotune_linear",
    "lookup",
    "default_plan",
    "enable",
    "enabled",
    "clear",
    "save_table",
    "load_table",
    "table_info",
    "summary",
    "plan_for_params",
]

# executable dot-program partition models (build_dot lowers these)
PARTITIONED_MODELS = ("minimal", "standard", "unlimited")
# crossbar geometries raced (cfg.scaled(n=...)); wider rows fit more terms
GEOMETRIES = (1024, 2048, 4096)
# state backends raced outside a host callback; inside jax.pure_callback
# ("pim_sim") only the jax-free numpy interpreter may run.  "unrolled" is
# excluded: its XLA compile time grows with program length, so a trial
# would measure compilation, not steady state.
STATE_BACKENDS = ("scan", "pallas", "numpy")
CALLBACK_BACKENDS = ("numpy",)


@dataclasses.dataclass(frozen=True)
class TunedPlan:
    """One tuned configuration pick (see the table JSON format in
    ``benchmarks/check.py``'s header)."""

    key: str
    kind: str               # "gemm" | "linear"
    model: str              # partition model (gemm) / lowering mode (linear)
    n_cols: int
    chunk: int              # dot terms per program (0: n/a)
    backend: str            # execution backend / lowering name
    predicted_us: float     # cost-model device latency
    trial_us: float = 0.0   # measured trial wall time (0: untried)
    default_us: float = 0.0  # the default config's time in the same race
    source: str = "cost_model"  # "cost_model" | "trial" | "table"

    @property
    def vs_default(self) -> float:
        """default_time / picked_time (>= 1.0 when trials ran)."""
        if self.trial_us > 0 and self.default_us > 0:
            return self.default_us / self.trial_us
        return 1.0

    def to_json(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: Dict) -> "TunedPlan":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


@dataclasses.dataclass(frozen=True)
class TuneInfo:
    hits: int
    misses: int
    trials: int
    size: int
    enabled: bool


_table: Dict[str, TunedPlan] = {}
_lock = threading.Lock()
_hits = 0
_misses = 0
_trials = 0
_enabled = False


def enable(on: bool = True) -> None:
    """Turn ambient plan lookup on/off (``matmul_int(tune_ctx=...)``)."""
    global _enabled
    _enabled = on


def enabled() -> bool:
    return _enabled


def clear() -> None:
    """Drop every pick and zero the counters (leaves ``enabled`` alone)."""
    global _hits, _misses, _trials
    with _lock:
        _table.clear()
        _hits = _misses = _trials = 0


def table_info() -> TuneInfo:
    with _lock:
        return TuneInfo(hits=_hits, misses=_misses, trials=_trials,
                        size=len(_table), enabled=_enabled)


def _bucket_m(m: int) -> int:
    """Batch rows bucket to the next power of two: decode batch sizes churn
    as requests come and go, and re-tuning per transient M would thrash."""
    return 1 << max(0, int(m - 1).bit_length())


def tune_key(n_terms: int, n_bits: int, model: str, shape: Tuple[int, int],
             pim_mode: str) -> str:
    m, o = shape
    return f"gemm:k{n_terms}b{n_bits}m{model}x{_bucket_m(m)}o{o}@{pim_mode}"


def _allowed_backends(pim_mode: str,
                      backends: Optional[Sequence[str]]) -> Tuple[str, ...]:
    if backends is not None:
        return tuple(backends)
    return CALLBACK_BACKENDS if pim_mode == "pim_sim" else STATE_BACKENDS


def default_plan(n_terms: int, n_bits: int, shape: Tuple[int, int],
                 pim_mode: str = "raw", model: str = "minimal") -> TunedPlan:
    """The hardcoded configuration tuned calls are raced against: the
    engine's defaults (minimal model, 1024-column crossbar, max chunking,
    scan — or the callback-safe numpy interpreter under pim_sim)."""
    from repro.pim.cost_model import gemm_cost
    from repro.pim.matmul import max_dot_terms

    chunk = min(n_terms, max_dot_terms(n_bits, 1024))
    backend = "numpy" if pim_mode == "pim_sim" else "scan"
    cost = gemm_cost(shape[0], n_terms, shape[1], n_bits, model,
                     n_cols=1024, chunk=chunk)
    return TunedPlan(key=tune_key(n_terms, n_bits, model, shape, pim_mode),
                     kind="gemm", model=model, n_cols=1024, chunk=chunk,
                     backend=backend, predicted_us=cost.time_s * 1e6)


def candidates(n_terms: int, n_bits: int, shape: Tuple[int, int],
               pim_mode: str = "raw",
               backends: Optional[Sequence[str]] = None
               ) -> List[TunedPlan]:
    """Every raced configuration, cost-model-scored, fastest predicted
    first.  Serial multiplier algorithms (``kind="mult"`` registry entries)
    are priced with ``chunk=0``/no backend — they rank in the race but
    cannot lower to a dot program, so :func:`autotune` never picks them for
    execution (on these shapes the partitioned models win the prediction
    anyway, reproducing the paper's ~9x)."""
    from repro.pim import engine
    from repro.pim.cost_model import gemm_cost
    from repro.pim.matmul import max_dot_terms

    m, o = shape
    out: List[TunedPlan] = []
    key_of = lambda model: tune_key(n_terms, n_bits, model, shape, pim_mode)
    for model in PARTITIONED_MODELS:
        for n_cols in GEOMETRIES:
            chunk = min(n_terms, max_dot_terms(n_bits, n_cols))
            if chunk <= 0:
                continue
            cost = gemm_cost(m, n_terms, o, n_bits, model,
                             n_cols=n_cols, chunk=chunk)
            for backend in _allowed_backends(pim_mode, backends):
                out.append(TunedPlan(
                    key=key_of(model), kind="gemm", model=model,
                    n_cols=n_cols, chunk=chunk, backend=backend,
                    predicted_us=cost.time_s * 1e6))
    for name in engine.backends():
        if engine.backend_kind(name) != "mult" or name == "serial":
            continue
        cost = gemm_cost(m, n_terms, o, n_bits, name, n_cols=1024)
        out.append(TunedPlan(key=key_of(name), kind="gemm", model=name,
                             n_cols=1024, chunk=0, backend="",
                             predicted_us=cost.time_s * 1e6))
    # the NOR serial baseline, for the race report
    cost = gemm_cost(m, n_terms, o, n_bits, "baseline", n_cols=1024)
    out.append(TunedPlan(key=key_of("baseline"), kind="gemm",
                         model="baseline", n_cols=1024, chunk=0, backend="",
                         predicted_us=cost.time_s * 1e6))
    out.sort(key=lambda p: p.predicted_us)
    return out


def _trial_time(plan: TunedPlan, n_terms: int, n_bits: int,
                shape: Tuple[int, int], trials: int,
                rng: np.random.Generator) -> float:
    """Median-of-``trials`` wall microseconds for one tuned GEMM call.

    Operands are clipped (M<=8, O<=64 rows) so warmup stays cheap; the
    full inner dimension is kept — chunking is what the race is about.
    Runs through ``matmul_int(plan=...)``, so the winning artifact lands
    in the compile cache and its session pool primed for serving.
    """
    from repro.pim import engine

    m = min(shape[0], 8)
    o = min(shape[1], 64)
    hi = np.uint64(1) << np.uint64(n_bits)
    x = rng.integers(0, hi, size=(m, n_terms), dtype=np.uint64)
    w = rng.integers(0, hi, size=(o, n_terms), dtype=np.uint64)
    engine.matmul_int(x, w, n_bits, plan=plan)  # warm: compile + upload
    best = []
    for _ in range(max(1, trials)):
        t0 = time.perf_counter()
        engine.matmul_int(x, w, n_bits, plan=plan)
        best.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(best))


def autotune(n_terms: int, n_bits: int, shape: Tuple[int, int],
             pim_mode: str = "raw", *, model: str = "minimal",
             trials: int = 1, top_k: int = 3,
             backends: Optional[Sequence[str]] = None,
             force: bool = False) -> TunedPlan:
    """Search (or fetch) the fastest configuration for one compile key.

    Cost-model scores every candidate; the ``top_k`` predicted-fastest
    executable candidates plus the hardcoded default then race in timed
    trials (set ``trials=0`` for a pure cost-model pick).  The winner is
    cached in the table, attached to its ``CompiledPim`` artifact, and
    returned.
    """
    global _hits, _misses, _trials
    key = tune_key(n_terms, n_bits, model, shape, pim_mode)
    with _lock:
        plan = _table.get(key)
        if plan is not None and not force:
            _hits += 1
            _attach(plan, n_terms, n_bits)
            return plan
        _misses += 1

    cands = candidates(n_terms, n_bits, shape, pim_mode, backends)
    execable = [p for p in cands if p.chunk > 0]
    default = default_plan(n_terms, n_bits, shape, pim_mode, model)
    picked = execable[0] if execable else default
    if trials > 0 and execable:
        race = execable[:top_k]
        if not any(p.model == default.model and p.n_cols == default.n_cols
                   and p.chunk == default.chunk
                   and p.backend == default.backend for p in race):
            race.append(default)
        rng = np.random.default_rng(0)
        timed: List[Tuple[float, TunedPlan]] = []
        for p in race:
            t = _trial_time(p, n_terms, n_bits, shape, trials, rng)
            timed.append((t, p))
            with _lock:
                _trials += 1
        t_default = next(t for t, p in timed
                         if (p.model, p.n_cols, p.chunk, p.backend) ==
                         (default.model, default.n_cols, default.chunk,
                          default.backend))
        t_best, best = min(timed, key=lambda tp: tp[0])
        picked = dataclasses.replace(best, key=key, trial_us=t_best,
                                     default_us=t_default, source="trial")
    else:
        picked = dataclasses.replace(picked, key=key, source="cost_model")

    with _lock:
        _table[key] = picked
    _attach(picked, n_terms, n_bits)
    return picked


def _attach(plan: TunedPlan, n_terms: int, n_bits: int) -> None:
    """Pin the pick on its ``CompiledPim`` artifact (cache hits carry it)."""
    if plan.kind != "gemm" or plan.chunk <= 0:
        return
    from repro.pim import engine

    art = engine.compile_matmul(min(plan.chunk, n_terms), n_bits,
                                model=plan.model, n_cols=plan.n_cols)
    if art.plan is not plan:
        object.__setattr__(art, "plan", plan)


def lookup(n_terms: int, n_bits: int, *, shape: Tuple[int, int],
           pim_mode: str, model: str = "minimal") -> Optional[TunedPlan]:
    """Table-only fetch for the hot path (``matmul_int(tune_ctx=...)``):
    returns the cached pick or None — a miss never triggers a search."""
    global _hits, _misses
    if not _enabled:
        return None
    key = tune_key(n_terms, n_bits, model, shape, pim_mode)
    with _lock:
        plan = _table.get(key)
        if plan is None:
            _misses += 1
        else:
            _hits += 1
        return plan


# ==========================================================================
# the quant vs quant_tp split rule
# ==========================================================================

def autotune_linear(tokens: int, d_in: int, d_out: int, *,
                    trials: int = 2, force: bool = False) -> TunedPlan:
    """Race the int8 linear lowerings — single-rank ``quant`` vs the
    shard_mapped ``quant_tp`` tile — for one (tokens, d_in, d_out) shape.

    Bit-identical integer accumulation is the PR 5 contract, so the pick
    is purely a speed decision: quant_tp only pays off once the mesh's
    "model" axis is wide enough to beat its dispatch overhead.  Without an
    active mesh whose "model" axis exceeds 1, quant_tp *is* quant (see
    ``tp_quant_linear``), so only quant runs; any failure of a lowering
    that does run propagates.  Runs eagerly jitted.
    """
    global _hits, _misses, _trials
    key = f"linear:t{_bucket_m(tokens)}d{d_in}o{d_out}"
    with _lock:
        plan = _table.get(key)
        if plan is not None and not force:
            _hits += 1
            return plan
        _misses += 1

    import jax
    import jax.numpy as jnp

    from repro.dist import context as dctx
    from repro.models import layers

    mesh, ax = dctx.current_mesh(), dctx.tp_axis()
    modes = ["quant"]
    if mesh is not None and ax is not None and mesh.shape[ax] > 1:
        modes.append("quant_tp")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((tokens, d_in)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d_in, d_out)), jnp.float32)
    timed: List[Tuple[float, str]] = []
    for mode_name in modes:
        fn = jax.jit(lambda x, w, m=mode_name: layers.linear(x, w, mode=m))
        fn(x, w).block_until_ready()  # warm: trace + compile
        times = []
        for _ in range(max(1, trials)):
            t0 = time.perf_counter()
            fn(x, w).block_until_ready()
            times.append((time.perf_counter() - t0) * 1e6)
        timed.append((float(np.median(times)), mode_name))
        with _lock:
            _trials += 1
    t_best, best = min(timed)
    t_default = timed[0][0]     # "quant" always runs first
    plan = TunedPlan(key=key, kind="linear", model=best, n_cols=0, chunk=0,
                     backend=best, predicted_us=0.0, trial_us=t_best,
                     default_us=t_default, source="trial")
    with _lock:
        _table[key] = plan
    return plan


# ==========================================================================
# persistence + warmup helpers
# ==========================================================================

TABLE_VERSION = 1


def save_table(path: str) -> int:
    """Write every pick to ``path`` (JSON; format in benchmarks/check.py).
    Returns the number of entries written."""
    with _lock:
        entries = {k: p.to_json() for k, p in sorted(_table.items())}
    doc = {"version": TABLE_VERSION, "entries": entries}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    return len(entries)


def load_table(path: str, *, merge: bool = True) -> int:
    """Load picks from ``path``; returns the number of entries loaded.

    Loaded plans are stamped ``source="table"`` — the hit counters then
    show serving warmup reusing picks instead of re-searching.  With
    ``merge=False`` the current table is replaced.
    """
    with open(path) as f:
        doc = json.load(f)
    if doc.get("version") != TABLE_VERSION:
        raise ValueError(f"tuning table {path!r} has version "
                         f"{doc.get('version')!r}, expected {TABLE_VERSION}")
    loaded = {k: dataclasses.replace(TunedPlan.from_json(v), source="table")
              for k, v in doc.get("entries", {}).items()}
    with _lock:
        if not merge:
            _table.clear()
        _table.update(loaded)
    return len(loaded)


def summary() -> str:
    """One-line state for launcher echoes (``serve.py``'s ``[autotune]``)."""
    info = table_info()
    with _lock:
        picks = [p for p in _table.values() if p.kind == "gemm"]
    pick = ""
    if picks:
        p = max(picks, key=lambda p: p.chunk)
        pick = (f"; e.g. {p.key}: model={p.model} n_cols={p.n_cols} "
                f"chunk={p.chunk} backend={p.backend} "
                f"({p.vs_default:.2f}x vs default)")
    return (f"{'on' if info.enabled else 'off'}, {info.size} plan(s), "
            f"{info.hits} hits / {info.misses} misses, "
            f"{info.trials} trials{pick}")


def plan_for_params(params, max_batch: int, *, bits: int = 7,
                    pim_mode: str = "pim_sim", trials: int = 1) -> int:
    """Tune every distinct linear shape in a model's parameter tree.

    Walks the pytree for the trailing ``(K, O)`` dims of 2-D leaves and of
    3-D layer-stacked leaves ``(n_layers, K, O)`` — the weight shapes
    ``sim_linear`` hands the engine.  Each distinct shape is planned at
    the serving batch bucket.  ``sim_linear`` quantizes to ``bits`` and
    multiplies at ``bits+1`` (offset-shifted unsigned), hence the
    ``n_bits`` below.  Returns the number of shapes planned (table hits
    count, so a reloaded table makes this free).
    """
    import jax

    shapes = set()
    for leaf in jax.tree_util.tree_leaves(params):
        shp = getattr(leaf, "shape", None)
        if shp is not None and len(shp) in (2, 3):
            shapes.add((int(shp[-2]), int(shp[-1])))
    for k_dim, o in sorted(shapes):
        autotune(k_dim, bits + 1, (max_batch, o), pim_mode, trials=trials)
    return len(shapes)
