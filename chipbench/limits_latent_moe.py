"""The readings the ``engine-latent-moe`` builder's limit is set from, taken
on the chip at the cell's own size (``limits.py`` imports ``builders.engine``
by name; this is its twin for the new builder):

    python3 chipbench/limits_latent_moe.py --workload <cell> --seeds 101,102 [--seconds 20]
        [--trace 1]

For each seed one JSON line with the program's served logit gaps and the
CONTROL's (the reference put in the program's place at fp8 precision, see
``reference.py``), over the same sample of the window's finished requests:
the two compared numbers (99th percentile and mean gap), the widest gap and
further quantiles.
``--trace 1`` adds the traced window's per-program device times and writes every
device operation's time beside the chip tool's other outputs.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import run as run_mod  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402
from chipbench.common import Cell  # noqa: E402


def gap_readings(gaps) -> dict:
    """The compared numbers (99th percentile and mean gap) and, for choosing
    between statistics, the widest gap, further quantiles and the share of
    tokens with a gap."""
    q = np.quantile(gaps, [0.5, 0.9, 0.99])
    return {"served_logit_gap_p99": float(q[2]), "served_logit_gap_mean": float(gaps.mean()),
            "served_logit_gap_max": float(gaps.max()), "p50": float(q[0]), "p90": float(q[1]),
            "share_nonzero": float((gaps > 0).mean()), "tokens": int(gaps.size)}


def readings(cell: Cell) -> dict:
    from chipbench.builders import engine_latent_moe as b

    w = b.window(cell)
    got, rec = w["got"], w["rec"]
    sample = b.sample_finished(got["finished"], cell.seed, cell.config["serve"]["checked_requests"])
    args = (cell, w["flat"], w["requests"], sample, rec["served"])
    out = {
        "program": gap_readings(b.served_logit_gaps(*args)),
        "control_fp8": gap_readings(b.served_logit_gaps(*args, low=True)),
        "checked_requests": len(sample),
        "checked_tokens": sum(r.new_tokens for r in sample),
        "finished": len(got["finished"]),
        "compiled_in_window": rec["compiled_in_window"],
        "memory_peak_bytes": w["peak"],
        "serve_tokens_per_s": got["end_to_end"]["serve_tokens_per_s"],
        "step_wall_ms": got["facts"]["step_wall_ms"],
        "prefill_steps": rec["prefill_steps"], "decode_steps": rec["decode_steps"],
    }
    if cell.trace:
        reduced = trace_mod.reduce_dir(cell.trace_dir)
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
        out["program_ms"] = {name: 1e3 * sum(ds) / len(ds)
                             for name, ds in reduced["modules"].items() if ds}
        out["device_ops"] = reduced["breakdown"]["device_ops"]
        # Every operation's seconds and calls, for a reader of the run: too
        # long for the line, so beside the chip tool's other outputs.
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
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()
    run_mod.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = run_mod.make_cell(args.workload, seed, args.seconds, bool(args.trace))
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
        print(json.dumps({"workload": args.workload, "seed": seed, **readings(cell)}),
              flush=True)


if __name__ == "__main__":
    main()
