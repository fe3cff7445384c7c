#!/usr/bin/env python
"""Cross-rank hang postmortem over flight-recorder dumps.

The CLI face of :mod:`torchgpipe_tpu.obs.postmortem`: merge the per-rank
JSON dumps a stalled :class:`~torchgpipe_tpu.distributed.gpipe.
DistributedGPipe` run left behind (crash dump, stall watchdog, SIGTERM
hook), replay the blocking-FIFO simulation from the recorded frontier,
and print the named blocking edge(s) plus the straggler table::

    python tools/postmortem.py /tmp/run/rank*.json
    python tools/postmortem.py /tmp/run/rank*.json --chrome merged.json

``--chrome`` additionally writes the merged multi-rank Perfetto trace
(one process per rank, clock-aligned timestamps).

``--ci`` is the **postmortem-verify** gate (``tools/ci_lint.py`` step
7): it induces a REAL hang — a 2-rank LocalTransport pipeline whose
``('forward', 1)`` send blocks forever via
:class:`~torchgpipe_tpu.resilience.faults.FaultyTransport`'s
``hang_at`` — inside a bounded-timeout subprocess (a hung thread cannot
be killed; the process can), collects the crash/watchdog dumps, and
requires the analyzer to name EXACTLY the injected edge: rank 1 waiting
on recv (stage 1, mb 1, fwd) from rank 0.  Exit 0 iff it does.
"""

from __future__ import annotations

import argparse
import os
import pathlib
import subprocess
import sys
from typing import Dict, Optional, Sequence

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# The induced-hang fixture (the --ci child).  Rank 0 runs its forward in
# a daemon thread and hangs forever inside the ('forward', 1) send;
# rank 1's bounded recv raises, crash-dumps its ring, and the main
# thread dumps rank 0's ring (readable even while its owner is hung —
# that is the point of a ring buffer).  A StallWatchdog shadows rank 0
# so the gate also exercises the watchdog dump path.
_HANG_FIXTURE = r"""
import pathlib, sys, threading
import jax, jax.numpy as jnp
from torchgpipe_tpu.distributed import DistributedGPipe, LocalTransport
from torchgpipe_tpu.obs.flightrec import (
    FlightRecorder, StallWatchdog, align_clocks,
)
from torchgpipe_tpu.obs.registry import MetricsRegistry
from torchgpipe_tpu.ops import dense
from torchgpipe_tpu.resilience.faults import FaultyTransport

out = pathlib.Path(sys.argv[1])
inner = LocalTransport()
transport = FaultyTransport(inner, hang_at=("forward", 1))
layers = [dense(8, name="a"), dense(8, name="b")]
workers = ["w0", "w1"]
recs, ranks, boxes = [], [], []
for r in range(2):
    box = inner.register(workers[r])
    rec = FlightRecorder(rank=r, worker=workers[r],
                         dump_path=str(out / f"rank{r}.json"))
    recs.append(rec); boxes.append(box)
    ranks.append(DistributedGPipe(
        layers, r, workers, [1, 1], chunks=2,
        transport=transport, mailbox=box, recorder=rec,
        recv_timeout=10.0,
    ))
ths = [threading.Thread(target=align_clocks,
                        args=(inner, boxes[r], r, workers, recs[r]))
       for r in range(2)]
[t.start() for t in ths]; [t.join() for t in ths]
ps = [rk.init(jax.random.PRNGKey(0),
              jax.ShapeDtypeStruct((4, 8), jnp.float32)) for rk in ranks]
x = jnp.ones((4, 8))
reg = MetricsRegistry()
watchdog = StallWatchdog(recs[0], timeout=4.0, registry=reg).start()
t0 = threading.Thread(
    target=lambda: ranks[0].forward(ps[0][0], ps[0][1], x), daemon=True
)
t0.start()
try:
    ranks[1].forward(ps[1][0], ps[1][1], None)  # blocks on mb 1 forever
    raise SystemExit("UNEXPECTED: the hung pipeline completed")
except TimeoutError:
    pass  # rank 1 crash-dumped inside the recv path
recs[0].dump()  # rank 0's ring, dumped from the main thread
watchdog.stop()
print("HANG_FIXTURE_DONE", flush=True)
"""


def _subproc_env() -> Dict[str, str]:
    """CPU-pinned child env (the tools/ copy of tests/subproc_env.py):
    the children are separate processes and a chip belongs to one process
    at a time, so none of them may reach for it."""
    env = dict(
        os.environ,
        PYTHONPATH=str(REPO),
        JAX_PLATFORMS="cpu",
        TF_CPP_MIN_LOG_LEVEL="3",
    )
    return env


def run_ci(timeout: float = 300.0, verbose: bool = False) -> int:
    """The postmortem-verify gate: induce the hang, analyze the dumps,
    require the exact injected edge.  See the module docstring."""
    import json
    import tempfile

    import jax

    # Platform pin BEFORE the analysis stack loads: a gate needs no
    # accelerator and must never hold one.
    jax.config.update("jax_platforms", "cpu")

    from torchgpipe_tpu.obs.flightrec import load_dump
    from torchgpipe_tpu.obs.postmortem import postmortem

    with tempfile.TemporaryDirectory() as d:
        tmp = pathlib.Path(d)
        script = tmp / "hang_fixture.py"
        script.write_text(_HANG_FIXTURE)
        proc = subprocess.Popen(
            [sys.executable, str(script), str(tmp)],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            env=_subproc_env(),
        )
        try:
            proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            print(
                f"[postmortem-verify] FAILED: fixture exceeded its "
                f"{timeout:.0f}s budget",
                file=sys.stderr, flush=True,
            )
            return 1
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        paths = [tmp / "rank0.json", tmp / "rank1.json"]
        missing = [str(p) for p in paths if not p.exists()]
        if missing:
            print(
                f"[postmortem-verify] FAILED: no dump(s) at {missing} "
                f"(fixture rc={proc.returncode})",
                file=sys.stderr, flush=True,
            )
            return 1
        report = postmortem([load_dump(str(p)) for p in paths])
        if verbose:
            print(report.summary(), flush=True)
        ok = (
            report.hang_suspected
            and report.blocking[0].root
            and report.blocking[0].rank == 1
            and report.blocking[0].event.cell == (1, 1, "fwd")
            and report.blocking[0].channel == ("forward", 1)
            and report.blocking[0].peer_rank == 0
        )
        # The watchdog must have flagged rank 0's silence in its dump.
        rank0 = load_dump(str(paths[0]))
        stalled = any(e.kind == "stall_suspected" for e in rank0.events)
        if ok and stalled:
            print(
                "[postmortem-verify] OK: analyzer named the injected "
                f"edge — {report.blocking[0].describe()}",
                flush=True,
            )
            return 0
        print(
            "[postmortem-verify] FAILED: "
            + ("watchdog never flagged the hung rank; " if not stalled
               else "")
            + "expected root edge rank 1 / (stage 1, mb 1, fwd) / "
            f"channel ('forward', 1) from rank 0, got:\n"
            + json.dumps([b.describe() for b in report.blocking],
                         indent=2),
            file=sys.stderr, flush=True,
        )
        return 1


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="merge flight-recorder dumps, name the blocking edge"
    )
    ap.add_argument("dumps", nargs="*", metavar="DUMP.json",
                    help="per-rank flight-recorder dump files")
    ap.add_argument("--chrome", metavar="OUT.json",
                    help="also write the merged multi-rank Perfetto "
                         "trace (per-rank pids, aligned timestamps)")
    ap.add_argument("--ci", action="store_true",
                    help="run the postmortem-verify gate (induced hang "
                         "in a bounded subprocess; exit 0 iff the "
                         "analyzer names the injected edge)")
    ap.add_argument("--ci-timeout", type=float, default=300.0)
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    if args.ci:
        return run_ci(timeout=args.ci_timeout, verbose=args.verbose)
    if not args.dumps:
        ap.error("no dump files given (or use --ci)")

    import jax

    jax.config.update("jax_platforms", "cpu")

    from torchgpipe_tpu.obs.flightrec import load_dump, merged_chrome_trace
    from torchgpipe_tpu.obs.postmortem import postmortem

    loaded = [load_dump(p) for p in args.dumps]
    if args.chrome:
        merged_chrome_trace(loaded, args.chrome)
        print(f"merged chrome trace: {args.chrome} "
              "(open in ui.perfetto.dev)", flush=True)
    report = postmortem(loaded)
    print(report.summary(), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
