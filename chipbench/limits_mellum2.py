"""The readings the ``spmd-train-moe`` builder's limits are set from, taken on
the chip at the cell's own size (``limits.py`` imports ``builders.spmd_train``
by name; this is its twin for the new builder):

    python3 chipbench/limits_mellum2.py --workload <cell> --seeds 101,102 [--faults 1]
    python3 chipbench/limits_mellum2.py --workload <cell> --seeds 103 --trace 1 [--seconds 20]
    python3 chipbench/limits_mellum2.py --workload <cell> --seeds 104 --draw 7

For each seed one JSON line with the CONTROL's numbers (the reference put in
the program's place at fp8 precision) and those of the half-batch fault
planted in the reference; ``--faults 1`` adds the reference with one
mechanism of the model left out or altered (``FAULTS``).  The program's own
numbers (the lower readings) are printed by every run of ``run.py`` under
``compared``.  ``--trace 1`` runs the cell itself, traced, and writes every
device operation's time and the device time by the program's scopes beside
the chip tool's other outputs.  ``--draw`` puts another draw of the weights
and the token ids in the place of the configuration's (``draw.seed``), which
every seed of the cell relabels: the readings over fresh draws come from here.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import pathlib
import shutil
import sys
from typing import Any, Callable, Dict

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import run as run_mod  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402
from chipbench import weights_mellum2  # noqa: E402
from chipbench.common import Cell  # noqa: E402
from chipbench.limits import half_batch  # noqa: E402

FULL = "full_attention"


def _full_rope(m: Dict[str, Any], **patch: Any) -> Dict[str, Any]:
    ropes = m["rope_parameters"]
    return dict(m, rope_parameters=dict(ropes, **{FULL: dict(ropes[FULL], **patch)}))


# A mechanism of the model left out or altered, as a change to the
# configuration the REFERENCE is given (``chipbench/tests/test_mellum2.py``
# plants the same in the program's config objects at toy width).
FAULTS: Dict[str, Callable[[Dict[str, Any]], Dict[str, Any]]] = {
    "no_window": lambda m: dict(m, sliding_window=None),
    "no_yarn_frequencies": lambda m: _full_rope(m, factor=1.0),
    "no_yarn_factor": lambda m: _full_rope(m, attention_factor=1.0),
    "no_renormalisation": lambda m: dict(m, norm_topk_prob=False),
    "held_shifted": lambda m: dict(m, held_first=m["held_first"] + 1),
}

# Device time is charged to the first of these that an operation's scope
# path (stat ``tf_op`` of its event's metadata) holds.
SCOPES = ("optimizer", "attn.window", "attn.full", "moe.route", "moe.experts")


def train_readings(cell: Cell, faults: bool) -> Dict[str, Any]:
    from chipbench.builders import spmd_train_moe as b

    m, tr = cell.config, cell.config["train"]
    pool = weights_mellum2.token_batches(m, cell.traffic, cell.seed, tr["batch"], tr["seq"])
    steps, opt = tr["reference_steps"], tr["optimizer"]
    ref = b.reference_readings(m, cell.seed, pool, steps, opt)
    planted = {"control_fp8": lambda: b.reference_readings(m, cell.seed, pool, steps, opt, low=True),
               "fault_half_batch": lambda: b.reference_readings(
                   m, cell.seed, half_batch(pool), steps, opt)}
    if faults:
        planted.update({f"fault_{name}": (lambda alter=alter: b.reference_readings(
            alter(m), cell.seed, pool, steps, opt)) for name, alter in FAULTS.items()})
    out: Dict[str, Any] = {}
    for name, got in planted.items():
        gc.collect()
        readings = got()
        out[name] = {c.name: c.value for c in b.compare(m, readings, ref, tr["limits"])}
        out[name]["worst_leaves"] = b.worst_leaves(m, readings, ref)
        print(json.dumps({"seed": cell.seed, name: out[name]}), file=sys.stderr, flush=True)
    out["reference_losses"] = ref["losses"]
    return out


def scope_seconds(xplane: pathlib.Path) -> Dict[str, float]:
    """Device seconds by the program's scopes, summed over the chips: the
    scope path of an operation is stat ``tf_op`` of its event's METADATA,
    which ``jax.profiler.ProfileData`` does not show, so the ``XSpace``
    proto is read as it is."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(xplane.read_bytes())
    seconds: Dict[str, float] = collections.defaultdict(float)
    for plane in space.planes:
        if not plane.name.startswith(trace_mod.DEVICE_PREFIX):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        for line in plane.lines:
            if line.name != trace_mod.OPS_LINE:
                continue
            for event in line.events:
                meta = plane.event_metadata[event.metadata_id]
                if trace_mod.short_name(meta.name)[1] in trace_mod.CONTAINERS:
                    continue
                path = next((s.str_value or stat_names.get(s.ref_value, "") for s in meta.stats
                             if stat_names.get(s.metadata_id) == "tf_op"), "")
                scope = next((s for s in SCOPES if s in path), None)
                if scope is None and meta.name.startswith("%ragged-dot"):
                    scope = "moe.experts"       # the compiler's grouped product carries no path
                if scope is None:
                    scope = "block.other" if "/tick/" in path else "outside the blocks"
                seconds[scope] += event.duration_ps * 1e-12
    return dict(seconds)


def traced_readings(cell: Cell) -> Dict[str, Any]:
    from chipbench.builders import spmd_train_moe as b

    out = b.run(cell)
    xplane = trace_mod.find_xplane(cell.trace_dir)
    reduced = trace_mod.reduce_events(trace_mod.read_events(xplane))
    scopes = scope_seconds(xplane)
    shutil.rmtree(cell.trace_dir, ignore_errors=True)
    table = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])
    path = ROOT / "chiprun_out" / f"ops.{cell.name}.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(
        {"window_s": reduced["window_s"], "busy_s": reduced["busy_s"], "scope_seconds": scopes,
         "ops": [[k, v, reduced["op_calls"][k]] for k, v in table]}, indent=0))
    return {"compared": {c.name: c.value for c in out.checks}, "end_to_end": out.end_to_end,
            "memory_peak_bytes": out.memory_peak_bytes, "window_s": reduced["window_s"],
            "busy_s": reduced["busy_s"], "scope_seconds": scopes,
            "device_ops": reduced["breakdown"]["device_ops"],
            "facts": {k: v for k, v in out.facts.items() if k.startswith(("moe_", "notes"))}}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--faults", type=int, choices=(0, 1), default=0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--draw", type=int, default=None)
    args = parser.parse_args()
    patch = None if args.draw is None else {"draw": {"seed": args.draw}}
    run_mod.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = run_mod.make_cell(args.workload, seed, args.seconds, bool(args.trace), patch)
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
        got = traced_readings(cell) if args.trace else train_readings(cell, bool(args.faults))
        print(json.dumps({"workload": args.workload, "seed": seed, **got}), flush=True)


if __name__ == "__main__":
    main()
