"""The readings a limit is set from, taken on the chip at the cell's own size:

    python3 chipbench/limits.py --workload <cell> --seeds 101,102,103 [--seconds 20]

For each seed one JSON line with the CONTROL's numbers (the reference put in
the program's place at fp8 precision, see ``reference.py``) and, for a
training cell, those of the half-batch fault planted in the reference.  The
program's own numbers (the lower readings) are printed by every run of
``run.py`` under ``compared``.  ``chipbench/tests`` keeps the same control
and faults at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
from typing import Any, Dict, Sequence

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import run as run_mod  # noqa: E402
from chipbench.common import Cell, resolve  # noqa: E402


def half_batch(pool: np.ndarray) -> np.ndarray:
    """Half of every batch left out, the mean taken over the rest: the first
    half's rows twice over give the same loss and gradient."""
    half = pool.shape[1] // 2
    return np.concatenate([pool[:, :half], pool[:, :half]], axis=1)


def train_readings(cell: Cell, devices: Sequence[Any]) -> Dict[str, Any]:
    from chipbench.builders import spmd_train as b

    m, tr = cell.config, cell.config["train"]
    pool = resolve(cell.traffic["generator"])(
        cell.traffic, cell.seed, tr["batch"], tr["seq"], m["vocab_size"])
    args = (m, cell.seed, pool, tr["reference_steps"], tr["optimizer"], devices)
    ref = b.reference_readings(*args)
    out = {}
    for name, got in (("control_fp8", lambda: b.reference_readings(*args, low=True)),
                      ("fault_half_batch", lambda: b.reference_readings(
                          m, cell.seed, half_batch(pool), *args[3:]))):
        gc.collect()
        out[name] = {c.name: c.value for c in b.compare(got(), ref, tr["limits"])}
    out["reference_losses"] = ref["losses"]
    return out


def serve_readings(cell: Cell) -> Dict[str, Any]:
    from chipbench.builders import engine as b

    eng, flat = b.build(cell)
    requests = resolve(cell.traffic["generator"])(
        cell.traffic, cell.seed, cell.seconds, cell.config["vocab_size"])
    rec = b.drive(cell, eng, requests)
    del eng
    gc.collect()
    got = b.measure(cell, requests, rec)
    sample = b.sample_finished(got["finished"], cell.seed, cell.config["serve"]["checked_requests"])
    return {
        "program": {"served_logit_gap": b.served_logit_gap(cell, flat, requests, sample, rec["served"])},
        "control_fp8": {"served_logit_gap": b.served_logit_gap(
            cell, flat, requests, sample, rec["served"], low=True)},
        "checked_requests": len(sample),
        "checked_tokens": sum(r.new_tokens for r in sample),
        "finished": len(got["finished"]),
        "serve_tokens_per_s": got["end_to_end"]["serve_tokens_per_s"],
        "step_wall_ms": got["facts"]["step_wall_ms"],
        "kv_live_bytes": got["facts"]["kv_live_bytes"],
    }


def readings(cell: Cell) -> Dict[str, Any]:
    if cell.traffic["system"] == "train":
        return train_readings(cell, jax.devices()[:cell.chips])
    return serve_readings(cell)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    args = parser.parse_args()
    run_mod.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = run_mod.make_cell(args.workload, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed, **readings(cell)}), flush=True)


if __name__ == "__main__":
    main()
