"""What a cell is made of, found by the names in ``BENCHMARK.json``.

  configs/<config>.json   the model configuration as run (Hugging Face
                          key names), its drafter, and the keys changed
                          from the source (``reduced``)
  traffic/<traffic>.json  one traffic mix: loop kind, arrivals, length
                          distributions, the engine settings it is served
                          with, and the limit of its correctness check
  metrics/<metric>.py     one reader per per-layer metric

Adding a configuration, a traffic mix or a metric is adding its file and
its ``BENCHMARK.json`` entry; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"unknown workload {workload!r}; choose from "
                     f"{[w['name'] for w in bench['workloads']]}")


def load_config(name: str, bench_dir: Path = BENCH) -> dict:
    with open(bench_dir / "configs" / f"{name}.json") as f:
        return json.load(f)


def load_traffic(name: str, bench_dir: Path = BENCH) -> dict:
    with open(bench_dir / "traffic" / f"{name}.json") as f:
        return json.load(f)


def model_dims(c: dict) -> dict:
    """Canonical sizes of one model of a configuration file."""
    return {"num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "d_ff": c["intermediate_size"],
            "vocab_size": c["vocab_size"], "rope_theta": c["rope_theta"],
            "norm_eps": c["rms_norm_eps"],
            "tie_embeddings": c["tie_word_embeddings"],
            "dtype": c["torch_dtype"]}


def target_and_drafter(config: dict):
    """(target dims, drafter dims); the drafter shares the vocabulary."""
    t = model_dims(config)
    d = model_dims({**config, **config["drafter"]})
    return t, d


def metric_reader(name: str, bench_dir: Path = BENCH):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """Metric entries this cell reports: its end-to-end metrics untraced,
    its per-layer metrics traced. An entry without ``workloads`` applies to
    every cell."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]


def read_metrics(names, ctx, bench_dir: Path = BENCH) -> dict:
    """Each named per-layer metric's reading; None where its reader found
    nothing to read."""
    return {n: metric_reader(n, bench_dir)(ctx) for n in names}
