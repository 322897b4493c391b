"""``BENCHMARK.json`` and the files it names.

The manifest is the only list: a cell is an entry of ``workloads``, a
configuration an entry of ``configs`` whose ``file`` holds it as it is run,
a traffic mix is ``benchmark/traffic/<name>.json``, and a per-layer metric
is an entry of ``per_layer`` whose reader is ``benchmark/readers/<name>.py``.
A configuration ``<config>`` has to bring ``costs/<config>.py`` (``per_unit``)
and ``checks/<config>.py`` (``validate``, ``compare``). It may bring
``inputs/<config>.py`` (``resident_batch(rng, shape, dtype)``: what a resident
cell's groups hold; without it, seeded bytes) and ``references/<config>.py``
(``features(params, config, check_path)``: the benchmark's plain reference,
handed the parameter tree the window ran and the configuration, and
``control(params, config, check_path)``, the same in the nearest lower
precision, which ``compare()`` has to fail; without the file, the program's
float32 twin at the configuration's ``reference_keys``). A corpus block may
name a ``kind`` (absent: ``video``), whose writer is ``corpora/<kind>.py``
(``SUFFIX``, ``GEOMETRY``, ``write(path, frames, spec, rng)``). Each of these
is chosen by the presence of the file that carries the name, never by the
name itself.
A metric belongs to a cell when it has no ``workloads`` list or the list
names the cell. An entry carries one ``moves``, so where cells with different
end-to-end metrics want the same per-layer reading, a second entry named
``<tag>.<name>`` names the other metric and is read by ``<name>``'s file.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

#: the checkout: ``benchmark/vftbench/manifest.py`` -> two levels up
ROOT = Path(__file__).resolve().parents[2]
NAME_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")


class ManifestError(ValueError):
    """The manifest or a file it names is missing or inconsistent."""


def read_json(path: Path) -> Any:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except OSError as e:
        raise ManifestError(f"cannot read {path}: {e}") from e
    except ValueError as e:
        raise ManifestError(f"{path} is not JSON: {e}") from e


def load_manifest(root: Path = ROOT) -> Dict[str, Any]:
    return read_json(Path(root) / "BENCHMARK.json")


def _one(entries: List[dict], name: str, what: str) -> dict:
    hits = [e for e in entries if e.get("name") == name]
    if len(hits) != 1:
        raise ManifestError(
            f"{what} {name!r}: {len(hits)} entries in BENCHMARK.json "
            f"(known: {sorted(e.get('name', '?') for e in entries)})")
    return hits[0]


def metrics_of(manifest: dict, section: str, cell: str) -> List[dict]:
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


def bench_dir(manifest: dict, root: Path) -> Path:
    """The directory of ``paths`` that holds the command's program."""
    return (Path(root) / manifest["command"][-1]).parent


def load_module(path: Path) -> ModuleType:
    """The Python file at ``path``, loaded by path: the file's name is the
    configuration's, the kind's or the metric's exact name, dots and all."""
    if not path.is_file():
        raise ManifestError(f"{path} does not exist")
    mod_name = "vftbench_file_" + re.sub(r"[^A-Za-z0-9]", "_", path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_function(path: Path, attr: str) -> Callable:
    """``attr`` of the Python file at ``path``."""
    fn = getattr(load_module(path), attr, None)
    if not callable(fn):
        raise ManifestError(f"{path} defines no function {attr}()")
    return fn


class Cell:
    """One entry of ``workloads`` with everything it resolves to."""

    def __init__(self, manifest: dict, name: str, root: Path = ROOT) -> None:
        self.root = Path(root)
        self.entry = _one(manifest["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        self.bench = bench_dir(manifest, self.root)
        cfg_entry = _one(manifest["configs"], self.entry["config"], "config")
        self.config_name = cfg_entry["name"]
        self.config = read_json(self.root / cfg_entry["file"])
        self.traffic_name = self.entry["traffic"]
        self.traffic = read_json(
            self.bench / "traffic" / f"{self.traffic_name}.json")
        self.end_to_end = metrics_of(manifest, "end_to_end", name)
        e2e_names = {m["name"] for m in self.end_to_end}
        # a per-layer metric is reported only where the metric it moves is
        self.per_layer = [m for m in metrics_of(manifest, "per_layer", name)
                          if m["moves"] in e2e_names]

    def config_function(self, kind: str, attr: str) -> Callable:
        """``benchmark/<kind>/<config>.py`` -> ``attr``."""
        return load_function(
            self.bench / kind / f"{self.config_name}.py", attr)

    def optional_config_function(self, kind: str, attr: str
                                 ) -> Optional[Callable]:
        """``benchmark/<kind>/<config>.py`` -> ``attr`` where the file
        exists, else ``None``: the caller then does what it always did. A
        file that is there without the function is an error, not a
        fallback."""
        path = self.bench / kind / f"{self.config_name}.py"
        return load_function(path, attr) if path.is_file() else None

    def corpus_kind(self, block: Dict[str, Any]) -> ModuleType:
        """``benchmark/corpora/<kind>.py`` of a ``corpus`` / ``check_video``
        block; a block that names no ``kind`` holds videos."""
        path = self.bench / "corpora" / f"{block.get('kind', 'video')}.py"
        kind = load_module(path)
        if not (isinstance(getattr(kind, "SUFFIX", None), str)
                and isinstance(getattr(kind, "GEOMETRY", None), dict)
                and callable(getattr(kind, "write", None))):
            raise ManifestError(f"{path} has to define SUFFIX, GEOMETRY and "
                                "write(path, frames, spec, rng)")
        return kind

    def reader(self, metric: str) -> Callable:
        """``read`` of ``readers/<metric>.py``; a metric ``<tag>.<name>``
        with no file of its own is read by ``readers/<name>.py``."""
        path = self.bench / "readers" / f"{metric}.py"
        if not path.is_file() and "." in metric:
            path = path.with_name(metric.split(".", 1)[1] + ".py")
        return load_function(path, "read")

    def corpus_spec(self) -> Dict[str, Any]:
        """The corpus block of this cell's traffic, or of the mix it borrows
        one from (``corpus_from``), with the name of the mix that owns it."""
        owner, traffic = self.traffic_name, self.traffic
        if "corpus_from" in traffic:
            owner = traffic["corpus_from"]
            traffic = read_json(self.bench / "traffic" / f"{owner}.json")
        if "corpus" not in traffic:
            raise ManifestError(f"traffic {owner!r} has no corpus block")
        return {"owner": owner, **traffic["corpus"]}


def check_manifest(manifest: dict, root: Path = ROOT) -> List[str]:
    """Every problem a run would hit later, found without JAX: names,
    files that have to exist, and ``moves`` that point at nothing."""
    problems: List[str] = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e.get("name", "") for e in manifest.get(section, [])]
        problems += [f"{section}: bad name {n!r}" for n in names
                     if not NAME_RE.match(n)]
        problems += [f"{section}: name {n!r} used twice"
                     for n in sorted(set(names)) if names.count(n) > 1]
    for w in manifest.get("workloads", []):
        try:
            cell = Cell(manifest, w["name"], root)
            for kind, attr in (("costs", "per_unit"), ("checks", "compare")):
                cell.config_function(kind, attr)
            cell.optional_config_function("inputs", "resident_batch")
            for attr in ("features", "control"):
                cell.optional_config_function("references", attr)
            if "driver" not in cell.traffic:
                problems.append(f"{cell.traffic_name}: no driver")
            if cell.traffic["driver"] != "resident":
                cell.corpus_kind(cell.corpus_spec())
            else:
                cell.corpus_kind(cell.traffic["check_video"])
            reported = {m["name"] for m in cell.end_to_end}
            if "setup_s" not in reported or len(reported) < 2:
                problems.append(f"{cell.name}: reports {sorted(reported)}; "
                                "needs setup_s and one more")
            if not cell.per_layer:
                problems.append(f"{cell.name}: no per-layer metric")
            for m in cell.per_layer:
                cell.reader(m["name"])
        except (ManifestError, KeyError) as e:
            problems.append(f"{w.get('name')}: {e}")
    for m in manifest.get("per_layer", []):
        e2e = {e["name"]: e for e in manifest.get("end_to_end", [])}
        if m.get("moves") not in e2e:
            problems.append(f"{m['name']}: moves {m.get('moves')!r}, which "
                            "is no end-to-end metric")
            continue
        for cell in m.get("workloads",
                          [w["name"] for w in manifest["workloads"]]):
            moved = e2e[m["moves"]]
            if "workloads" in moved and cell not in moved["workloads"]:
                problems.append(f"{m['name']}: moves {m['moves']!r}, which "
                                f"cell {cell!r} does not report")
    used = {w["config"] for w in manifest.get("workloads", [])}
    problems += [f"config {c['name']!r} is used by no cell"
                 for c in manifest.get("configs", []) if c["name"] not in used]
    return problems
