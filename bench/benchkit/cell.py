"""One cell of ``BENCHMARK.json``, with the files it names, found by name.

  * ``bench/configs/<config>.json``  the configuration as it is run
  * ``bench/traffic/<traffic>.json`` the serving mix or training job
  * ``bench/limits/<workload>.json`` the limit of each number compared
  * ``bench/metrics/<metric>.py``    one reader per per-layer metric
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# configuration-file key -> ModelConfig field of the program
PROGRAM_FIELDS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta", "sliding_window": "window",
    "tie_word_embeddings": "tie_embeddings", "use_bias": "use_bias",
}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load(workload: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfgs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / cfgs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = load_json(root / "bench" / "limits" / f"{workload}.json")

    def here(m: Dict[str, Any]) -> bool:
        return workload in m.get("workloads", [workload])

    e2e = [m for m in spec["end_to_end"] if here(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if here(m) and m["moves"] in reported]
    return Cell(workload, int(w["chips"]), config, traffic, limits["limits"], e2e, per_layer)


def program_config(config: Dict[str, Any]) -> Any:
    """The program's ModelConfig for a configuration file: the repo config it
    names, with every number the file gives put in its place."""
    from repro.configs import get_config

    base = get_config(config["repo_config"])
    changes = {field: config[key] for key, field in PROGRAM_FIELDS.items() if key in config}
    changes["window"] = int(changes.get("window") or 0)
    changes["dtype"] = config["dtype"]
    cfg = dataclasses.replace(base, **changes).validate()
    want_norm = "layernorm" if config.get("norm_type") == "layer_norm" else "layernorm_np"
    want_mlp = "swiglu" if config["hidden_act"] == "silu" else "gelu_mlp"
    if (cfg.norm, cfg.mlp) != (want_norm, want_mlp) or cfg.hd * cfg.n_heads != cfg.d_model:
        raise SystemExit(f"repo config {base.name} is not the file's architecture: "
                         f"norm {cfg.norm}, mlp {cfg.mlp}, head dim {cfg.hd}")
    return cfg


def reader(metric: str) -> Callable[[Dict[str, Any]], Any]:
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
