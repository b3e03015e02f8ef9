"""The benchmark's entry point.

    python3 -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found BY NAME from ``BENCHMARK.json``
and the directories beside this file, so a later PR adds a configuration
(``configs/<config>.json``), a traffic mix (``traffic/<traffic>.json``), a
traffic driver (``drivers/<driver>.py``), a per-layer metric
(``layer_metrics/<metric>.py``) or a cell's limits (``limits/<cell>.json``)
as new files plus new entries in ``BENCHMARK.json``, and edits no file that
is there.

The compile cache is ``<checkout>/.jax_cache`` (:func:`keep_compile_cache`).
One process holds the chip. Without an accelerator, or with fewer chips
than the cell asks for, the run exits 2 and prints no result.
``--rehearse`` is the one exception: a toy-size walk through the same
control flow on whatever backend is there, for the tests; its line says
``"rehearsal": true`` in ``device`` and carries no device metric.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is charged from here: imports included

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# Sources whose numbers exist only on the chip; a rehearsal prints none.
DEVICE_SOURCES = ("host_clock", "device_trace", "program_span")


def _load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT, rehearse: bool = False) -> Dict[str, Any]:
    """The cell ``name`` with its configuration, traffic, limits and the
    metrics it reports, all looked up by the names in ``BENCHMARK.json``."""
    manifest = _load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    cell = dict(cells[name])
    config_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = _load_json(root, config_entry["file"])
    traffic = _load_json(HERE, "traffic", cell["traffic"] + ".json")
    limits = _load_json(HERE, "limits", name + ".json")
    if rehearse:  # toy sizes live beside the real ones, in the same files
        config.update(config.get("rehearsal", {}))
        traffic.update(traffic.get("rehearsal", {}))
    cell.update(
        config_data=config,
        traffic_data=traffic,
        limits=limits,
        end_to_end=[m for m in manifest["end_to_end"] if name in m.get("workloads", [name])],
        per_layer=[m for m in manifest["per_layer"] if name in m.get("workloads", [name])],
    )
    return cell


def load_reader(metric: str):
    """``layer_metrics/<metric>.py`` — loaded by path, since a metric's
    name may hold a dot."""
    path = os.path.join(HERE, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("benchmarks.layer_metrics._" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def keep_compile_cache(jax, root: str = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path inside the checkout,
    whatever the environment says, holding every program however quickly it
    compiled and never evicting one: after a checkout's first run of a cell
    every program of that cell is found there. (A cache shared through
    ``JAX_COMPILATION_CACHE_DIR`` and capped near one cell's 200 MB evicted
    in a cycle: every run of PR 24's fourth chip call compiled anew.)"""
    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_report(devices, rehearse: bool) -> Dict[str, Any]:
    report = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if rehearse:
        report["rehearsal"] = True
    return report


def per_layer_metrics(cell, outcome, summary, device_kind: str, rehearse: bool,
                      notes: List[str]) -> Dict[str, Dict[str, Any]]:
    """Each reader takes its metric from counters, spans or the trace; one
    that finds nothing to read returns None and is left out of the line."""
    ctx = {
        "config": cell["config_data"], "traffic": cell["traffic_data"],
        "window": outcome["window"], "counters": outcome["counters"],
        "trace": summary, "device_kind": device_kind, "notes": notes,
    }
    out = {}
    for m in cell["per_layer"]:
        if rehearse and m["source"] in DEVICE_SOURCES:
            continue
        value = load_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="benchmarks.run")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="toy sizes, any backend, no device metric (tests)")
    args = parser.parse_args(argv)
    cell = load_cell(args.workload, rehearse=args.rehearse)

    import jax

    devices = jax.devices()
    if not args.rehearse and (devices[0].platform == "cpu" or len(devices) < cell["chips"]):
        print(f"benchmarks.run: {cell['name']} needs {cell['chips']} accelerator chip(s); "
              f"JAX found {len(devices)} x {devices[0].platform!r}. No result.",
              file=sys.stderr)
        return 2
    from benchmarks import arith, trace

    if not args.rehearse:
        arith.peaks(devices[0].device_kind)  # an unknown chip is an error up front

    if not args.rehearse:  # a rehearsal shares its process with other tests
        print(f"compile cache: {keep_compile_cache(jax)}", file=sys.stderr)
    driver = importlib.import_module("benchmarks.drivers." + cell["traffic_data"]["driver"])
    outcome = driver.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace) and not args.rehearse, devices=devices[:cell["chips"]])

    device = device_report(devices, args.rehearse)
    device["memory_peak_bytes"] = outcome["memory_peak_bytes"]
    notes: List[str] = list(outcome.get("notes", []))
    result: Dict[str, Any] = {
        "correct": bool(outcome["correct"]), "attempted": outcome["attempted"],
        "failed": outcome["failed"],
    }
    if args.trace:
        summary = None
        if outcome.get("trace_dir"):
            summary = trace.summarize(outcome["trace_dir"], remove=True)
            device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
            result["breakdown"] = {"device_ops": summary["device_ops"][:10],
                                   "idle_gaps": summary["idle_gaps"][:5]}
        result["metrics"] = per_layer_metrics(
            cell, outcome, summary, devices[0].device_kind, args.rehearse, notes)
    else:
        values = dict(outcome["end_to_end"], setup_s=outcome["window_started_at"] - _T0)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"]
            if m["name"] in values and not (args.rehearse and m["source"] in DEVICE_SOURCES)
        }
    result["device"] = device
    result["compared"] = outcome["compared"]  # last: each number beside its limit

    for note in notes:
        print(note, file=sys.stderr)
    for name, pair in outcome["compared"].items():
        print(f"compared {name}: {pair['value']!r} (limit {pair['limit']!r})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
