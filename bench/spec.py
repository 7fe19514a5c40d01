"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by name:

* ``BENCHMARK.json`` ``configs[].file``: the configuration (model sizes,
  source, which reference module computes it);
* ``bench/traffic/<traffic>.json``: the traffic mix (fleet and job);
* ``bench/workloads/<cell>.json``: the cell's learning rate and the limits
  of its correctness numbers;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric, a
  ``read(ctx)`` that returns a number or None;
* ``bench/references/<name>.py``: a configuration's plain reference;
* ``bench/families/<model.family>.py``: what the harness needs to know of
  a configuration's model family: ``weight_shapes(model, n_clients)``,
  ``make_samples(model, traffic, rng)``, ``kept(model, width)`` and
  ``round_work(model, traffic, groups, avail)`` (``bench/families/vit.py``
  documents each);
* ``bench/peaks.json``: the chips' peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class SpecError(Exception):
    pass


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file
    traffic: dict         # the traffic mix file
    cell: dict            # the cell file (lr, limits)
    end_to_end: list      # BENCHMARK.json metric entries of this cell
    per_layer: list
    root: str = ROOT      # the checkout the files were found in

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def precision(self) -> str:
        """The matmul precision the configuration states for the model's
        float32 matmuls: "default" (what the backend picks; one bfloat16
        pass on a TPU), "high" (three passes) or "highest" (exact)."""
        return self.config.get("matmul_precision", "default")

    @property
    def aggregation_precision(self):
        """The matmul precision the configuration states for the Eq. 6/8
        aggregation, or None where it states none (the model's applies)."""
        return self.config.get("aggregation_precision")

    @property
    def control(self):
        """(dtype, precision) of the lower-precision control: the nearest
        step below the model's stated matmul precision."""
        return {"highest": ("float32", "high"),
                "high": ("float32", "default")}.get(
                    self.precision, ("bfloat16", "default"))

    def reference(self):
        return _module(os.path.join(self.root, "bench", "references",
                                    self.config["reference"] + ".py"),
                       "bench_reference_" + self.config["reference"])

    def family(self):
        name = self.model["family"]
        return _module(os.path.join(self.root, "bench", "families",
                                    name + ".py"), "bench_family_" + name)

    def reader(self, metric: str):
        return _module(os.path.join(self.root, "bench", "metrics",
                                    metric + ".py"),
                       "bench_metric_" + metric.replace(".", "_"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{[w['name'] for w in bench['workloads']]}")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=_load_json(os.path.join(root, cfg["file"])),
        traffic=_load_json(os.path.join(root, "bench", "traffic",
                                        entry["traffic"] + ".json")),
        cell=_load_json(os.path.join(root, "bench", "workloads",
                                     name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def peaks(device_kind: str) -> dict:
    table = _load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]
