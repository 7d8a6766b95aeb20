"""Find a cell's files by name and turn them into what the run needs.

Layout (all under the benchmark directory, ``benchmarks/serve``):

* ``BENCHMARK.json`` at the repository root names the cells
  (``workloads``), their configuration and traffic, and the metrics;
* ``configs/<config>.json``: the published model configuration as run,
  named by the ``file`` of its ``configs`` entry.  Its published keys
  (``HF_KEYS``) are set over the registry entry it names (``registry``);
  ``reference`` names the plain reference that decides ``correct``,
  ``reference/<reference>.py`` (default ``model``; its interface is in
  :mod:`servebench.check`);
* ``traffic/<traffic>.json``: the parameters of one traffic mix;
* ``cells/<workload>.json``: the lowering, ``ServingConfig`` values and
  the correctness sample and limit of one cell; ``mesh``,
  ``{"model": <chips>}``, splits the model over the cell's chips on the
  program's "model" axis: the weights are made under the program's own
  shardings and the scheduler runs inside the mesh.  Without it the
  program runs on one device, with no mesh;
* ``metrics/<metric>.py``: one reader per metric, keyed by the metric's
  name before its first ``.``.

A later cell, mix or metric is a new file and a new entry; nothing here
changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
REFERENCE_DIR = os.path.join(BENCH_DIR, "reference")

#: published (Hugging Face ``config.json``) key -> ModelConfig field
HF_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "sliding_window": "sliding_window",
    "hidden_act": "activation",
    "qkv_bias": "qkv_bias",
    # GPTBigCode's names
    "n_embd": "d_model",
    "n_inner": "d_ff",
    "n_layer": "n_layers",
    "n_head": "n_heads",
    "layer_norm_epsilon": "norm_eps",
}


def load_module(path: str, name: str):
    """The module of the Python file at ``path``, loaded as ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod     # a dataclass looks its module up there
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Layout:
    """Where the benchmark's files are: the repository root (holding
    ``BENCHMARK.json``), the directory of cells, configurations and
    traffic, and those of the metric readers and the references."""

    root: str = REPO_ROOT
    bench_dir: str = BENCH_DIR
    metrics_dir: str = os.path.join(BENCH_DIR, "metrics")
    reference_dir: str = REFERENCE_DIR

    def benchmark(self) -> Dict[str, Any]:
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            return json.load(f)

    def _json(self, *parts: str) -> Dict[str, Any]:
        path = os.path.join(self.bench_dir, *parts)
        with open(path) as f:
            return json.load(f)

    def traffic(self, name: str) -> Dict[str, Any]:
        return self._json("traffic", f"{name}.json")

    def cell_file(self, name: str) -> Dict[str, Any]:
        return self._json("cells", f"{name}.json")

    def metric_reader(self, metric: str):
        """The ``read`` function of ``metrics/<base>.py``, where ``base`` is
        the metric's name before its first ``.``."""
        base = metric.split(".", 1)[0]
        return load_module(os.path.join(self.metrics_dir, f"{base}.py"),
                           f"servebench_metric_{base}").read


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with everything its files say."""

    name: str
    chips: int
    config: Dict[str, Any]         # the configuration file as run
    traffic: Dict[str, Any]
    cell: Dict[str, Any]           # cells/<name>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def mesh(self) -> Optional[Dict[str, int]]:
        """The cell file's mesh axes and sizes, or ``None``."""
        return self.cell.get("mesh")

    @property
    def model_ranks(self) -> int:
        """Chips that split each weight: the "model" axis's size."""
        return int((self.mesh or {}).get("model", 1))


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    names = metric.get("workloads")
    return names is None or workload in names


def load_cell(workload: str, layout: Optional[Layout] = None) -> Cell:
    layout = layout or Layout()
    bench = layout.benchmark()
    ws = [w for w in bench["workloads"] if w["name"] == workload]
    if not ws:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = ws[0]
    cfgs = [c for c in bench["configs"] if c["name"] == w["config"]]
    if not cfgs:
        raise KeyError(f"workload {workload!r}: no config {w['config']!r}")
    with open(os.path.join(layout.root, cfgs[0]["file"])) as f:
        config = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    cell = Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=layout.traffic(w["traffic"]),
                cell=layout.cell_file(workload), end_to_end=e2e,
                per_layer=per_layer)
    if cell.mesh is not None and (set(cell.mesh) != {"model"}
                                  or cell.model_ranks != cell.chips):
        raise ValueError(f"workload {workload!r}: mesh {cell.mesh} must "
                         f"split the model over its {cell.chips} chips, "
                         f'{{"model": {cell.chips}}}')
    return cell


def make_mesh(cell: Cell, devices):
    """The program's mesh over the cell's chips, as its serving entry point
    makes one (``ElasticMesh``), or ``None`` for a cell with no ``mesh``."""
    if cell.mesh is None:
        return None
    from repro.runtime.fault_tolerance import ElasticMesh

    mesh = ElasticMesh(model_parallel=cell.model_ranks).make(
        list(devices)[:cell.chips])
    if mesh.shape["model"] != cell.model_ranks:
        raise ValueError(f"{cell.name}: mesh {dict(mesh.shape)} over "
                         f"{cell.chips} devices, asked for {cell.mesh}")
    return mesh


def model_config(cell: Cell):
    """The program's ``ModelConfig`` for this cell: the registry entry the
    configuration file names, with every published key of the file set
    over it (``multi_query`` as one KV head), then the cell's serving
    horizon and linear lowering."""
    import repro.configs as configs

    c = cell.config
    cfg = configs.get(c["registry"])
    kw = {HF_KEYS[k]: v for k, v in c.items() if k in HF_KEYS}
    if c.get("use_sliding_window") is False:
        kw["sliding_window"] = None
    if c.get("multi_query"):
        kw["n_kv_heads"] = 1
    if "torch_dtype" in c:
        kw["dtype"] = c["torch_dtype"]
    kw["max_seq_len"] = int(cell.cell["max_seq_len"])
    kw["pim_mode"] = cell.cell["pim_mode"]
    return cfg.scaled(**kw)


def serving_config(cell: Cell):
    from repro.serving import ServingConfig

    return ServingConfig(**cell.cell["serving"])
