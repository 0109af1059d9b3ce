"""``BENCHMARK.json`` and the files it names, found by name.

- a configuration: its entry's ``file`` (``perfbench/configs/<name>.json``),
  whose ``system`` names ``perfbench/systems/<system>.py`` (the deployment
  under test) and whose ``corpus`` names ``perfbench/corpora/<corpus>.py``
  (its data, the embedding its queries share, and the plain reference);
- a traffic mix: ``perfbench/traffic/<traffic>.json``, whose ``generator``
  names ``perfbench/traffic/<generator>.py`` (the arrivals) and whose
  ``requests`` names ``perfbench/requests/<requests>.py`` (what is sent);
- a cell's limits for ``correct``: ``perfbench/limits/<workload>.json``;
- a metric: ``perfbench/metrics/<name>.py`` with ``read(run)``.

A new configuration, mix, arrival pattern, request kind, cell or metric is
new files and a new entry in ``BENCHMARK.json``; nothing here changes.  A
new deployment is a corpus module, a configuration that names it, and,
where its query text is not agent-history words (``lib/traffic.text``
draws only those), a request kind of its own that draws its text.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]
PKG = ROOT / "perfbench"


def load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    def __init__(self, root: Path = ROOT):
        self.root = root
        self.doc = json.loads((root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config_entry(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return json.loads((self.root / self.config_entry(name)["file"]).read_text())

    def traffic_path(self, name: str) -> Path:
        return self.root / "perfbench" / "traffic" / f"{name}.json"

    def traffic(self, name: str) -> dict:
        return json.loads(self.traffic_path(name).read_text())

    def generator_path(self, generator: str) -> Path:
        return self.root / "perfbench" / "traffic" / f"{generator}.py"

    def generator(self, traffic: dict) -> ModuleType:
        name = traffic["generator"]
        return load_module(self.generator_path(name), f"perfbench_generator_{name}")

    def requests_path(self, requests: str) -> Path:
        return self.root / "perfbench" / "requests" / f"{requests}.py"

    def requests(self, traffic: dict) -> ModuleType:
        name = traffic["requests"]
        return load_module(self.requests_path(name), f"perfbench_requests_{name}")

    def limits_path(self, workload: str) -> Path:
        return self.root / "perfbench" / "limits" / f"{workload}.json"

    def limits(self, workload: str) -> Dict[str, float]:
        doc = json.loads(self.limits_path(workload).read_text())
        return {k: float(v["limit"]) for k, v in doc["limits"].items()}

    def system_path(self, system: str) -> Path:
        return self.root / "perfbench" / "systems" / f"{system}.py"

    def corpus_path(self, corpus: str) -> Path:
        return self.root / "perfbench" / "corpora" / f"{corpus}.py"

    def corpus(self, cfg: dict) -> ModuleType:
        """The configuration's corpus module (``embedding``, ``generate``,
        ``reference``)."""
        name = cfg["corpus"]
        return load_module(self.corpus_path(name), f"perfbench_corpus_{name}")

    def metric_path(self, name: str) -> Path:
        return self.root / "perfbench" / "metrics" / f"{name}.py"

    def metrics_for(self, workload: str, kind: str) -> List[dict]:
        """The cell's ``end_to_end`` or ``per_layer`` entries."""
        return [m for m in self.doc[kind]
                if "workloads" not in m or workload in m["workloads"]]

    def reader(self, name: str) -> ModuleType:
        return load_module(self.metric_path(name), f"perfbench_metric_{name.replace('.', '_')}")


def read_metrics(bench: Benchmark, entries: List[dict], run) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` for each entry whose reader finds a
    value; a reader that finds nothing returns None and is left out."""
    out: Dict[str, dict] = {}
    for m in entries:
        value: Optional[float] = bench.reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
