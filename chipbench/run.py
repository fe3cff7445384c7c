"""One run of one cell of BENCHMARK.json:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: loads the cell's data files by name, builds the system under
test, warms only the cell's own shapes (set-up), measures for ``--seconds``,
checks what the timed path produced against the plain reference, and prints
one JSON object as the last line of standard output.  With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics (a profiler trace of a short steady window is reduced for them).

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.  Everything a cell is made of is data:
``configs/<config>.json`` (sizes, and a builder for each system it can be:
``train``, ``serve``), ``traffic/<traffic>.json`` (names the system it drives
and its generator as ``module:function``), ``layer_metrics/<metric>.json``
(names its reader the same way).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import pathlib
import shutil
import sys
from typing import Any, Dict, Optional, Sequence

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from chipbench import trace as trace_mod  # noqa: E402
from chipbench.common import (HERE, Cell, CompileMeter, Outcome,  # noqa: E402
                              load_json, resolve)
from chipbench.peaks import peaks_for  # noqa: E402


def enable_compile_cache() -> str:
    """Where ``JAX_COMPILATION_CACHE_DIR`` says, else a fixed directory in
    the checkout (the path is part of the cache's key)."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _merge(base: Dict[str, Any], patch: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in (patch or {}).items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def load_traffic(name: str) -> Dict[str, Any]:
    return load_json(HERE / "traffic" / f"{name}.json")


def device_line(chips: int) -> Dict[str, Any]:
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": min(chips, len(devices))}


def make_cell(workload: str, seed: int, seconds: float, trace: bool = False,
              config_patch: Optional[Dict[str, Any]] = None,
              traffic_patch: Optional[Dict[str, Any]] = None, fault: Any = None) -> Cell:
    """The cell as its data files describe it (the patches are the tests')."""
    entry = next((w for w in load_json(ROOT / "BENCHMARK.json")["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    return Cell(
        name=workload,
        config=_merge(load_json(HERE / "configs" / f"{entry['config']}.json"), config_patch),
        traffic=_merge(load_traffic(entry["traffic"]), traffic_patch),
        chips=entry["chips"], seed=int(seed), seconds=float(seconds), trace=trace,
        trace_dir=ROOT / ".chipbench_trace" / workload, meter=CompileMeter(), fault=fault,
    )


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, config_patch: Optional[Dict[str, Any]] = None,
             traffic_patch: Optional[Dict[str, Any]] = None,
             fault: Any = None) -> Dict[str, Any]:
    """Everything but argument parsing and printing.  The keyword arguments
    are for the tests under ``chipbench/tests`` (toy widths on the CPU, a
    fault planted under the timed path); the command line cannot set them."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = make_cell(workload, seed, seconds, trace, config_patch, traffic_patch, fault)
    line = device_line(cell.chips)
    if require_tpu and (line["platform"] != "tpu" or len(jax.devices()) < cell.chips):
        raise SystemExit(f"{workload} needs {cell.chips} TPU chip(s); jax found "
                         f"{len(jax.devices())} x {line['platform']}")
    if require_tpu:
        peaks_for(line["kind"])     # an unknown chip is an error before any work
    enable_compile_cache()
    shutil.rmtree(cell.trace_dir, ignore_errors=True)
    system = cell.config[cell.traffic["system"]]      # the mix says train or serve
    builder = importlib.import_module(
        "chipbench.builders." + system["builder"].replace("-", "_"))
    out: Outcome = builder.run(cell)

    correct = all(c.ok for c in out.checks) and out.failed == 0
    compared = {c.name: {"value": c.value, "limit": c.limit} for c in out.checks}
    result: Dict[str, Any] = {"correct": correct, "attempted": out.attempted,
                              "failed": out.failed}
    device = dict(line, memory_peak_bytes=out.memory_peak_bytes)
    wanted = bench["per_layer"] if trace else bench["end_to_end"]
    wanted = [x for x in wanted if workload in x.get("workloads", [workload])]
    if trace:
        reduced = trace_mod.reduce_dir(cell.trace_dir)
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        facts = dict(out.facts, trace=reduced, end_to_end=out.end_to_end,
                     memory_peak_bytes=out.memory_peak_bytes, cell=cell,
                     peaks=peaks_for(line["kind"]) if require_tpu else None)
        metrics = {}
        for spec in wanted:
            reader = load_json(HERE / "layer_metrics" / f"{spec['name']}.json")
            value = resolve(reader["reader"])(facts, **reader.get("args", {}))
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
            elif not reader.get("optional"):
                # BENCHMARK.json says this cell reports it: a reader that no
                # longer finds its kernel or counter has lost sight of the
                # work, and a silent gap would hide that.  A metric that may
                # be absent here says ``"optional": true`` in its own file.
                raise RuntimeError(
                    f"{spec['name']}: its reader found nothing to read in {workload}")
        result["breakdown"] = reduced["breakdown"]
    else:
        metrics = {x["name"]: {"value": out.end_to_end[x["name"]], "unit": x["unit"]}
                   for x in wanted}
    if "notes" in out.facts:        # the builder's own account of the window, for a reader
        result["notes"] = out.facts["notes"]
    result.update(metrics=metrics, device=device, compared=compared)
    return result


def main(argv: Optional[Sequence[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, c in result["compared"].items():
        print(f"compared {name}: value {c['value']:.6g} limit {c['limit']:.6g}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
