"""The readings the ``engine-trinity`` builder's limits are set from, taken on
the chip at the cell's own size (``limits_latent_moe.py``'s twin for this
builder), and the faults both it and ``tests/test_trinity.py`` plant:

    python3 chipbench/limits_trinity.py --workload <cell> --seeds 101,102 [--seconds 44]
        [--faults 1] [--control 0] [--trace 1]
    python3 chipbench/limits_trinity.py --workload <cell> --seeds 103 --program-fault ring_unmasked
    python3 chipbench/limits_trinity.py --workload <cell> --seeds 104 --patch '{"serve": {...}}'

For each seed one JSON line with the program's served logit gaps and the
CONTROL's (the reference put in the program's place at fp8 precision), over
the same sample of the window's finished requests: the two compared numbers
(99th percentile and mean gap), the widest gap and further quantiles.
``--faults 1`` adds the gaps of the SAME served tokens below a reference with
one mechanism of the block left out or altered (``reference_trinity.FAULTS``:
a fault in the reference's place reads what the same fault in the program's
would, at the cost of a forward and not of a window).  ``--program-fault``
plants one of ``PROGRAM_FAULTS`` under the timed path itself and reads the
run's own gaps; ``ring_unmasked`` exists only there (the reference has no
ring).  ``--patch`` merges a JSON object into the configuration (the readings
behind ``num_slots`` and ``prefill_chunk``).  ``--trace 1`` adds the traced
window's per-program device times and writes every device operation's time
beside the chip tool's other outputs.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import shutil
import sys
from typing import Any, Callable, Dict, Optional

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import reference_trinity  # noqa: E402
from chipbench import run as run_mod  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402
from chipbench.common import Cell  # noqa: E402
from chipbench.limits_latent_moe import gap_readings  # noqa: E402


def _entries(cfg: Any, full: bool, **patch: Any) -> Any:
    """``cfg`` with the window (or the full) entries of its period altered."""
    return dataclasses.replace(cfg, attn_layers=tuple(
        dataclasses.replace(e, **patch) if (e.window is None) == full else e
        for e in cfg.attn_layers))


def ring_unmasked(real: Callable[..., Any]) -> Callable[..., Any]:
    """The program's cache attention reading a ring WITHOUT the mask of the
    position a row holds: the band widened to the ring's own length, so the
    rows the ring keeps beyond the window (and has not yet overwritten) are
    attended as if they were inside it."""

    def attend(q, ck, cv, pos0, window, *args, ring=False, **kwargs):
        if ring:
            window = ck.shape[1] - q.shape[1] + 1
        return real(q, ck, cv, pos0, window, *args, ring=ring, **kwargs)

    return attend


# A mechanism left out of or altered in the PROGRAM: ``Cell.fault``'s form,
# ``(point, value) -> value`` at the builder's taps.
_CONFIG_FAULTS: Dict[str, Callable[[Any, Any], Any]] = {
    "no_window": lambda cfg, moe: (_entries(cfg, False, window=None), moe),
    "full_rotated": lambda cfg, moe: (_entries(cfg, True, rope=True), moe),
    "no_gate": lambda cfg, moe: (dataclasses.replace(cfg, attn_gate=False), moe),
    "no_post_norms": lambda cfg, moe: (dataclasses.replace(cfg, sandwich_norm=False), moe),
    "no_bias": lambda cfg, moe: (cfg, dataclasses.replace(moe, select="none")),
    "no_route_scale": lambda cfg, moe: (cfg, dataclasses.replace(moe, route_scale=1.0)),
    "held_shifted": lambda cfg, moe: (
        cfg, dataclasses.replace(moe, held=(moe.held[0] + 1, moe.held[1]))),
}


def program_fault(name: str) -> Callable[[str, Any], Any]:
    if name == "ring_unmasked":
        return lambda point, value: ring_unmasked(value) if point == "attend_chunk" else value
    alter = _CONFIG_FAULTS[name]
    return lambda point, value: alter(*value) if point == "program_config" else value


PROGRAM_FAULTS = tuple(_CONFIG_FAULTS) + ("ring_unmasked",)
assert set(_CONFIG_FAULTS) == set(reference_trinity.FAULTS)


def readings(cell: Cell, faults: bool, control: bool = True) -> Dict[str, Any]:
    from chipbench.builders import engine_trinity as b

    w = b.window(cell)
    got, rec = w["got"], w["rec"]
    sample = b.sample_finished(cell, got["finished"])
    args = (cell, w["flat"], sample, rec["served"])
    facts = got["facts"]
    out = {
        "program": gap_readings(b.served_logit_gaps(*args)),
        "checked_contexts": [len(r.prompt) + r.new_tokens for r in sample],
        "checked_tokens": sum(r.new_tokens for r in sample),
        "ring_wrapped": b.wrapped(cell, sample),
        "finished": len(got["finished"]), "admitted": facts["admitted"],
        "compiled_in_window": rec["compiled_in_window"],
        "memory_peak_bytes": w["peak"],
        "serve_tokens_per_s": got["end_to_end"]["serve_tokens_per_s"],
        "setup_s": got["end_to_end"]["setup_s"],
        "step_wall_ms": facts["step_wall_ms"],
        "prefill_steps": rec["prefill_steps"], "decode_steps": rec["decode_steps"],
        "kv_live_bytes_by_kind": facts.get("kv_live_bytes_by_kind"),
        "kv_pool_bytes_by_kind": facts["kv_pool_bytes_by_kind"],
        "attend_rows_read": facts.get("attend_rows_read"),
        "moe": {k: facts[k] for k in ("moe_routed_assignments", "moe_held_assignments",
                                      "moe_expert_tokens_max", "moe_expert_tokens_mean")},
    }
    if control and cell.fault is None:
        out["control_fp8"] = gap_readings(b.served_logit_gaps(*args, low=True))
    if faults:
        for name in reference_trinity.FAULTS:
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
    parser.add_argument("--faults", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", type=int, choices=(0, 1), default=1)
    parser.add_argument("--program-fault", choices=PROGRAM_FAULTS, default=None)
    parser.add_argument("--patch", type=json.loads, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()
    run_mod.enable_compile_cache()
    fault: Optional[Callable[[str, Any], Any]] = (
        program_fault(args.program_fault) if args.program_fault else None)
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = run_mod.make_cell(args.workload, seed, args.seconds, bool(args.trace),
                                 args.patch, fault=fault)
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program_fault": args.program_fault, "patch": args.patch,
                          **readings(cell, bool(args.faults), bool(args.control))}),
              flush=True)


if __name__ == "__main__":
    main()
