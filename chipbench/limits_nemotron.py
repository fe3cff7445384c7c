"""The readings the ``engine-nemotron`` builder's limits are set from, taken on
the chip at the cell's own size (``limits_trinity.py``'s twin for this
builder), and the faults both it and ``tests/test_nemotron.py`` plant:

    python3 chipbench/limits_nemotron.py --workload <cell> --seeds 101,102 [--seconds 44]
        [--faults all|<name>,...] [--control 0] [--trace 1]
    python3 chipbench/limits_nemotron.py --workload <cell> --seeds 103 --program-fault no_D

For each seed one JSON line with the program's served logit gaps and the
CONTROL's (the reference put in the program's place at fp8 precision), over
the same sample of the window's finished requests: the two compared numbers
(99th percentile and mean gap), the widest gap and further quantiles.
``--faults`` adds the gaps of the SAME served tokens below a reference with
one mechanism left out or altered (``reference_nemotron.FAULTS``, all or those
named: the state kept in bfloat16 among them).  ``--program-fault`` plants one of
``PROGRAM_FAULTS`` under the timed path itself and reads the run's own gaps.
``--trace 1`` adds the traced window's per-program device times and writes
every device operation's time to ``chiprun_out/ops.<cell>.json``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import shutil
import sys
from typing import Any, Callable, Dict, Optional, Sequence

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import reference_nemotron  # noqa: E402
from chipbench import run as run_mod  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402
from chipbench.common import Cell  # noqa: E402
from chipbench.limits_latent_moe import gap_readings  # noqa: E402

# A mechanism left out of or altered in the PROGRAM: ``Cell.fault``'s form,
# ``(point, value) -> value`` at the builder's taps.
_CONFIG_FAULTS: Dict[str, Callable[[Any, Any], Any]] = {
    "no_bias": lambda cfg, moe: (cfg, dataclasses.replace(moe, select="none")),
    "no_route_scale": lambda cfg, moe: (cfg, dataclasses.replace(moe, route_scale=1.0)),
    "held_shifted": lambda cfg, moe: (
        cfg, dataclasses.replace(moe, held=(moe.held[0] + 1, moe.held[1]))),
}


def _no_d(flat: Any) -> Any:
    return [dict(p, D=0.0 * p["D"]) if "D" in p else p for p in flat]


def program_fault(name: str) -> Callable[[str, Any], Any]:
    if name == "no_D":
        return lambda point, value: _no_d(value) if point == "weights" else value
    alter = _CONFIG_FAULTS[name]
    return lambda point, value: alter(*value) if point == "program_config" else value


PROGRAM_FAULTS = tuple(_CONFIG_FAULTS) + ("no_D",)
assert set(PROGRAM_FAULTS) <= set(reference_nemotron.FAULTS)


def readings(cell: Cell, faults: Sequence[str] = (), control: bool = True) -> Dict[str, Any]:
    from chipbench.builders import engine_nemotron as b

    w = b.window(cell)
    got, rec = w["got"], w["rec"]
    sample = b.sample_finished(cell, got["finished"], w["recycled"])
    args = (cell, w["flat"], sample, rec["served"])
    facts = got["facts"]
    out = {
        "program": gap_readings(b.served_logit_gaps(*args)),
        "checked_contexts": [len(r.prompt) + r.new_tokens for r in sample],
        "checked_tokens": sum(r.new_tokens for r in sample),
        "checked_recycled": sum(r.rid in w["recycled"] for r in sample),
        "finished": len(got["finished"]), "admitted": facts["admitted"],
        "compiled_in_window": rec["compiled_in_window"],
        "memory_peak_bytes": w["peak"],
        "serve_tokens_per_s": got["end_to_end"]["serve_tokens_per_s"],
        "setup_s": got["end_to_end"]["setup_s"],
        "step_wall_ms": facts["step_wall_ms"],
        "prefill_steps": rec["prefill_steps"], "decode_steps": rec["decode_steps"],
        **{k: facts.get(k) for k in ("kv_live_bytes_by_kind", "kv_pool_bytes_by_kind",
                                     "decode_experts_touched", "decode_state_bytes",
                                     "decode_rows_read", "decode_step_bytes")},
        "moe": {k: facts[k] for k in ("moe_routed_assignments", "moe_held_assignments",
                                      "moe_expert_tokens_max", "moe_expert_tokens_mean")},
    }
    if control and cell.fault is None:
        out["control_fp8"] = gap_readings(b.served_logit_gaps(*args, low=True))
    for name in faults:
        out[f"fault_{name}"] = gap_readings(b.served_logit_gaps(*args, leave_out=(name,)))
        print(json.dumps({"seed": cell.seed, name: out[f"fault_{name}"]}),
              file=sys.stderr, flush=True)
    if cell.trace:
        reduced = trace_mod.reduce_dir(cell.trace_dir)
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
        out["program_ms"] = {name: 1e3 * sum(ds) / len(ds)
                             for name, ds in reduced["modules"].items() if ds}
        out["busy_s"], out["window_s"] = reduced["busy_s"], reduced["window_s"]
        out["device_ops"] = reduced["breakdown"]["device_ops"]
        table = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])
        path = ROOT / "chiprun_out" / f"ops.{cell.name}.json"
        path.parent.mkdir(exist_ok=True)
        path.write_text(json.dumps(
            {"window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
             "ops": [[k, v, reduced["op_calls"][k]] for k, v in table]}, indent=0))
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=44.0)
    parser.add_argument("--faults", default="")
    parser.add_argument("--control", type=int, choices=(0, 1), default=1)
    parser.add_argument("--program-fault", choices=PROGRAM_FAULTS, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()
    run_mod.enable_compile_cache()
    fault: Optional[Callable[[str, Any], Any]] = (
        program_fault(args.program_fault) if args.program_fault else None)
    faults = (reference_nemotron.FAULTS if args.faults == "all"
              else [f for f in args.faults.split(",") if f])
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = run_mod.make_cell(args.workload, seed, args.seconds, bool(args.trace),
                                 fault=fault)
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program_fault": args.program_fault,
                          **readings(cell, faults, bool(args.control))}),
              flush=True)


if __name__ == "__main__":
    main()
