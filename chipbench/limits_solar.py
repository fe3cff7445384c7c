"""The readings the ``engine-solar`` builder's limits are set from, taken on the
chip at the cell's own size (``limits_nemotron.py``'s twin for this builder),
and the faults both it and ``tests/test_solar.py`` plant:

    python3 chipbench/limits_solar.py --workload <cell> --seeds 101,102 [--seconds 44]
        [--faults all|<name>,...] [--control 0] [--trace 1]
    python3 chipbench/limits_solar.py --workload <cell> --seeds 103 --program-fault beta_no_neg

For each seed one JSON line with the program's served logit gaps and the
CONTROL's (the reference put in the program's place at fp8 precision), over
the same sample of the window's finished requests: the two compared numbers
(99th percentile and mean gap), the widest gap and further quantiles.
``--faults`` adds the gaps of the SAME served tokens below a reference with
one mechanism left out or altered (``reference_solar.FAULTS``, all or those
named).  ``--program-fault`` plants one of ``PROGRAM_FAULTS`` under the timed
path itself and reads the run's own gaps.  ``--trace 1`` adds the traced
window's per-program device times and the device seconds by named scope (the
``kda.*`` scopes among them), and writes every device operation's time to
``chiprun_out/ops.<cell>.json``.
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

from chipbench import reference_solar  # noqa: E402
from chipbench import run as run_mod  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402
from chipbench.common import Cell  # noqa: E402
from chipbench.limits_latent_moe import gap_readings  # noqa: E402

# A mechanism left out of the PROGRAM: ``Cell.fault``'s form, ``(point,
# value) -> value`` at the builder's ``program_config`` tap.
_CONFIG_FAULTS: Dict[str, Callable[[Any, Any], Any]] = {
    "beta_no_neg": lambda cfg, moe: (dataclasses.replace(
        cfg, linear_attn=dataclasses.replace(cfg.linear_attn, neg_eigval=False)), moe),
    "no_attn_gate": lambda cfg, moe: (dataclasses.replace(cfg, attn_gate=False), moe),
}


def program_fault(name: str) -> Callable[[str, Any], Any]:
    alter = _CONFIG_FAULTS[name]
    return lambda point, value: alter(*value) if point == "program_config" else value


PROGRAM_FAULTS = tuple(_CONFIG_FAULTS)
assert set(PROGRAM_FAULTS) <= set(reference_solar.FAULTS)


def scope_seconds(trace_dir: pathlib.Path, prefix: str = "kda.") -> Dict[str, float]:
    """Device seconds by the program's named scopes that begin ``prefix``
    (``kda.proj`` ... ``kda.out``), summed over the chips, containers left
    out as ``trace.reduce_events`` leaves them out: the scope path of an
    operation is stat ``tf_op`` of its event's METADATA, which
    ``jax.profiler.ProfileData`` does not show, so the ``XSpace`` proto is
    read as it is (``limits_mellum2.scope_seconds``' reading)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(trace_mod.find_xplane(trace_dir).read_bytes())
    out: Dict[str, float] = {}
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
                path = next((st.str_value or stat_names.get(st.ref_value, "")
                             for st in meta.stats
                             if stat_names.get(st.metadata_id) == "tf_op"), "")
                scope = next((part for part in path.split("/") if part.startswith(prefix)),
                             None)
                if scope is not None:
                    out[scope] = out.get(scope, 0.0) + event.duration_ps * 1e-12
    return out


def readings(cell: Cell, faults: Sequence[str] = (), control: bool = True) -> Dict[str, Any]:
    from chipbench.builders import engine_solar as b

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
        out["kda_scope_s"] = scope_seconds(cell.trace_dir)
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
    faults = (reference_solar.FAULTS if args.faults == "all"
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
