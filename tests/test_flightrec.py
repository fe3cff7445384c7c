"""Flight recorder + cross-rank postmortem tests.

The acceptance spine: an INDUCED hang (``FaultyTransport(hang_at=...)``)
on a real 2-rank LocalTransport pipeline must leave dumps from which
``obs.postmortem`` names the exact injected blocking edge — rank, stage,
micro-batch, phase, peer's last event — and the frontier replay must
name edges on both the fill-drain and 1F1B graphs.  A clean run's dumps
must replay to completion (slow, not stuck).  Subprocess variants
(TcpTransport two-process hang, the ``postmortem-verify`` CI gate) are
slow-marked; the fast tests share one module-scoped clean run.
"""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from torchgpipe_tpu.analysis import events as ev
from torchgpipe_tpu.analysis import schedule as sched
from torchgpipe_tpu.distributed import DistributedGPipe, LocalTransport
from torchgpipe_tpu.distributed.context import Mailbox
from torchgpipe_tpu.obs.flightrec import (
    FlightEvent,
    FlightRecorder,
    StallWatchdog,
    align_clocks,
    dump_from_dict,
    load_dump,
    merged_chrome_trace,
)
from torchgpipe_tpu.obs.postmortem import postmortem
from torchgpipe_tpu.obs.registry import MetricsRegistry
from torchgpipe_tpu.ops import dense
from torchgpipe_tpu.resilience import faults
from torchgpipe_tpu.resilience.faults import FaultyTransport, SendFault

from tests.subproc_env import cpu_subproc_env

WORKERS = ["w0", "w1"]
LAYERS = lambda: [dense(8, name="a"), dense(8, name="b")]  # noqa: E731
X_SPEC = jax.ShapeDtypeStruct((4, 8), jnp.float32)


def mse(out, tgt):
    return jnp.mean((out - tgt) ** 2)


def _build_two_ranks(transport_outer, inner, *, recv_timeout=None,
                     dump_dir=None, chunks=2):
    recs, ranks, boxes = [], [], []
    for r in range(2):
        box = inner.register(WORKERS[r])
        rec = FlightRecorder(
            rank=r, worker=WORKERS[r],
            dump_path=(os.path.join(dump_dir, f"rank{r}.json")
                       if dump_dir else None),
        )
        recs.append(rec)
        boxes.append(box)
        ranks.append(DistributedGPipe(
            LAYERS(), r, WORKERS, [1, 1], chunks=chunks,
            transport=transport_outer, mailbox=box, recorder=rec,
            recv_timeout=recv_timeout,
        ))
    return ranks, recs, boxes


# --------------------------------------------------------------------- #
# ring buffer / dump format units                                       #
# --------------------------------------------------------------------- #


def test_ring_buffer_bounded_and_ordered():
    rec = FlightRecorder(capacity=8, rank=0, worker="w0")
    for i in range(20):
        rec.record("send", channel=("forward", i), peer="w1")
    evs = rec.events()
    assert len(evs) == 8  # fixed-size: old events evicted
    assert [e.channel[1] for e in evs] == list(range(12, 20))
    assert [e.seq for e in evs] == sorted(e.seq for e in evs)
    assert rec.last_event().channel == ("forward", 19)


def test_dump_round_trip_preserves_channels_and_meta(tmp_path):
    rec = FlightRecorder(rank=1, worker="w1",
                         dump_path=str(tmp_path / "d.json"))
    rec.set_meta(engine="distributed", workers=WORKERS, chunks=2,
                 checkpoint="except_last", skips=[])
    rec.clock_offset = 0.25
    rec.record("fwd", stage=1, mb=0, dur=0.001)
    # Tuple-kind mailbox keys (skip channels) must survive JSON.
    rec.record("recv_wait", channel=(("skip", "k"), 3), peer="w0")
    path = rec.dump()
    d = load_dump(path)
    assert (d.rank, d.worker, d.clock_offset) == (1, "w1", 0.25)
    assert d.meta["workers"] == WORKERS
    assert d.events[0].kind == "fwd" and d.events[0].dur == 0.001
    assert d.events[1].channel == (("skip", "k"), 3)
    assert d.aligned(d.events[0].t) == d.events[0].t + 0.25


def test_flight_event_dict_round_trip():
    e = FlightEvent(3, 1.5, "mail_put", channel=("backward", 2),
                    detail="depth=1")
    assert FlightEvent.from_dict(json.loads(json.dumps(e.to_dict()))) == e


def test_dump_survives_non_json_channel_keys(tmp_path):
    """Skip channels carry arbitrary key objects; the dump serializes
    them as their str (the event-graph spelling for skip channels) and
    a crash_dump must NEVER raise in place of the original failure."""
    class NsKey:  # a namespaced skip key: not a JSON type
        def __str__(self):
            return "<ns>.enc3"

    rec = FlightRecorder(rank=0, worker="w0",
                         dump_path=str(tmp_path / "skip.json"))
    rec.set_meta(engine="distributed", workers=WORKERS, chunks=2,
                 checkpoint="except_last", skips=[], odd=NsKey())
    rec.record("send", channel=(("skip", NsKey()), 1), peer="w1")
    assert rec.crash_dump("recv_timeout") is not None
    d = load_dump(str(tmp_path / "skip.json"))
    sends = [e for e in d.events if e.kind == "send"]
    assert sends[0].channel == (("skip", "<ns>.enc3"), 1)
    assert d.meta["odd"] == "<ns>.enc3"
    # An unwritable destination still never raises out of crash_dump.
    rec.dump_path = str(tmp_path / "no" / "such" / "dir" / "x.json")
    assert rec.crash_dump("again") is None


def test_mailbox_records_arrivals_with_depth():
    box = Mailbox("w1")
    rec = FlightRecorder(rank=1, worker="w1")
    box.recorder = rec
    box.put("forward", 0, {"x": 1})
    box.put("forward", 0, {"x": 2})
    evs = [e for e in rec.events() if e.kind == "mail_put"]
    assert [e.detail for e in evs] == ["depth=1", "depth=2"]
    assert box.depth("forward", 0) == 2
    box.get("forward", 0, timeout=1)
    assert box.depth("forward", 0) == 1
    assert box.depth("never", 9) == 0


# --------------------------------------------------------------------- #
# stall watchdog                                                        #
# --------------------------------------------------------------------- #


def test_watchdog_flags_silence_then_clears(tmp_path):
    """The alarm's tick, driven with an injected clock: no thread and no
    sleeping, so a loaded worker cannot miss the clear before the
    silence returns."""
    now = [100.0]
    rec = FlightRecorder(rank=0, worker="w0", clock=lambda: now[0],
                         dump_path=str(tmp_path / "wd.json"))
    rec.record("forward_begin")
    reg = MetricsRegistry()
    stalls = []
    wd = StallWatchdog(rec, timeout=0.15, poll=0.03, registry=reg,
                       on_stall=stalls.append)
    now[0] += 0.1
    wd._tick()
    assert not wd.stalled                       # inside the timeout
    now[0] += 0.1
    wd._tick()
    assert wd.stalled and stalls == [pytest.approx(0.2)]
    assert reg.get("hang_suspected").value(rank="0") == 1.0
    # The dump fired and carries the watchdog's own evidence (which
    # must NOT have reset the silence it measured).
    d = load_dump(str(tmp_path / "wd.json"))
    assert any(e.kind == "stall_suspected" for e in d.events)
    now[0] += 1.0
    wd._tick()
    assert wd.stalled and len(stalls) == 1      # once per episode
    # Activity resumes -> the gauge clears.
    rec.record("fwd", stage=0, mb=0, dur=0.001)
    wd._tick()
    assert not wd.stalled
    assert reg.get("hang_suspected").value(rank="0") == 0.0
    assert [e.kind for e in rec.events()][-2:] == ["fwd", "stall_cleared"]


def test_watchdog_thread_starts_and_stops(tmp_path):
    rec = FlightRecorder(rank=0, worker="w0")
    with StallWatchdog(rec, timeout=30.0, poll=0.01) as wd:
        assert wd._thread is not None and wd._thread.is_alive()
        thread = wd._thread
    assert wd._thread is None and not thread.is_alive()


def test_preemption_hook_dumps_the_ring(tmp_path):
    from torchgpipe_tpu.resilience.preemption import PreemptionHandler

    rec = FlightRecorder(rank=0, worker="w0",
                         dump_path=str(tmp_path / "term.json"))
    rec.record("forward_begin")
    handler = PreemptionHandler()
    handler.add_callback(rec.dump)  # the SIGTERM drain hook
    handler.simulate()
    d = load_dump(str(tmp_path / "term.json"))
    assert any(e.kind == "forward_begin" for e in d.events)


# --------------------------------------------------------------------- #
# hang_at fault                                                         #
# --------------------------------------------------------------------- #


def test_hang_at_blocks_until_released():
    inner = LocalTransport()
    box = inner.register("w1")
    transport = FaultyTransport(inner, hang_at=("forward", 1))
    transport.send("w1", "forward", 0, {"x": 1})  # non-matching passes
    assert box.get("forward", 0, timeout=1) == {"x": 1}
    done = threading.Event()

    def hung_send():
        transport.send("w1", "forward", 1, {"x": 2})
        done.set()

    t = threading.Thread(target=hung_send, daemon=True)
    t.start()
    assert not done.wait(0.3), "hang_at send returned without release"
    assert ("hang", "w1", "forward", 1) in transport.log
    transport.release()
    assert done.wait(5.0)
    # The hung message was never delivered; the channel stays empty.
    assert box.depth("forward", 1) == 0
    # Other fault rules still compose on the same wrapper.
    transport.add(SendFault(action="lose", kind="forward", index=2))
    transport.send("w1", "forward", 2, {"x": 3})
    assert box.depth("forward", 2) == 0


def test_hang_at_is_inert_for_program_caches():
    # Transport-level hangs trace nothing: the compiled-program cache
    # token must stay None (same contract as preempt-only plans).
    transport = FaultyTransport(LocalTransport(), hang_at=("forward", 0))
    assert faults.plan_token() is None
    with faults.inject(preempt_at_step=3):
        assert faults.plan_token() is None
    del transport


# --------------------------------------------------------------------- #
# guard error series (labeled kind + offending rank)                    #
# --------------------------------------------------------------------- #


def test_guard_records_error_kind_and_offending_rank():
    from torchgpipe_tpu.distributed.context import PeerDiedError
    from torchgpipe_tpu.resilience.guard import GuardPolicy, StepGuard

    reg = MetricsRegistry()

    def dead_step(params, opt_state):
        raise PeerDiedError(2, "w2")

    guard = StepGuard(dead_step, registry=reg, sleep=lambda _s: None)
    with pytest.raises(PeerDiedError):
        guard({}, {})
    assert reg.get("guard_errors").value(
        classification="fatal", error="PeerDiedError") == 1
    assert reg.get("guard_peer_died").value(rank="2") == 1

    calls = [0]

    def flaky_step(params, opt_state):
        calls[0] += 1
        if calls[0] <= 2:
            raise ConnectionError("transient link")
        return (jnp.float32(0.0), params, opt_state)

    reg2 = MetricsRegistry()
    guard2 = StepGuard(flaky_step, registry=reg2,
                       policy=GuardPolicy(max_retries=3),
                       sleep=lambda _s: None)
    guard2({}, {})
    assert reg2.get("guard_errors").value(
        classification="transient", error="ConnectionError") == 2
    assert guard2.stats.retries == 2


# --------------------------------------------------------------------- #
# frontier replay on fill-drain AND 1F1B graphs                         #
# --------------------------------------------------------------------- #


def test_replay_frontier_names_edge_fill_drain():
    g = ev.mpmd_fill_drain_events(2, 4)
    # Rank 0 ran fwd mb0..1; its ('act', 1) hand-off was lost in
    # transport; only ('act', 0) arrived.  Rank 1 progresses one cell
    # then blocks at fwd mb1 — the named edge.
    progressed, blocked = sched.replay_frontier(
        g, [2, 0], {("act", 0, 0, 1): 1}
    )
    assert ev.Event(1, 1, 0, ev.FWD) in progressed
    by_rank = {b.rank: b for b in blocked}
    b1 = by_rank[1]
    assert b1.event.cell == (1, 1, "fwd")
    assert [(t.channel.kind, t.channel.index) for t in b1.waiting] == [
        ("act", 1)
    ]


def test_replay_frontier_names_edge_1f1b():
    g = ev.mpmd_1f1b_events(2, 4)
    # Rank 1 completed fwd/bwd mb0 but its ('grad', 0) cotangent back to
    # rank 0 was lost; rank 0 (already past its warmup forwards and the
    # mb0 backward's receive point) blocks at bwd mb0.
    cursors = [2, 2]  # r0: fwd0,fwd1 done; r1: fwd0,bwd0 done
    progressed, blocked = sched.replay_frontier(g, cursors, {})
    by_rank = {b.rank: b for b in blocked}
    assert by_rank[0].event.cell == (0, 0, "bwd")
    assert [(t.channel.kind, t.channel.index)
            for t in by_rank[0].waiting] == [("grad", 0)]
    # With the in-flight messages delivered (the cotangent AND rank 0's
    # already-sent mb1 activation), the replay completes instead.
    progressed2, blocked2 = sched.replay_frontier(
        g, cursors, {("grad", 0, 1, 0): 1, ("act", 1, 0, 1): 1}
    )
    assert blocked2 == [] and len(progressed2) == sum(
        len(o) for o in g.order
    ) - sum(cursors)


def test_replay_frontier_validates_cursors():
    g = ev.mpmd_fill_drain_events(2, 2)
    with pytest.raises(ValueError, match="cursors"):
        sched.replay_frontier(g, [0], {})


# --------------------------------------------------------------------- #
# the clean-run fixture (shared by postmortem + chrome tests)           #
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def clean_run(tmp_path_factory):
    """ONE clean 2-rank LocalTransport run with recorders + clock
    alignment, serially driven in-process; yields the loaded dumps."""
    tmp = str(tmp_path_factory.mktemp("flight"))
    inner = LocalTransport()
    ranks, recs, boxes = _build_two_ranks(inner, inner, dump_dir=tmp)
    ths = [
        threading.Thread(
            target=align_clocks,
            args=(inner, boxes[r], r, WORKERS, recs[r]),
        )
        for r in range(2)
    ]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    ps = [rk.init(jax.random.PRNGKey(0), X_SPEC) for rk in ranks]
    x = jnp.ones((4, 8))
    ranks[0].forward(ps[0][0], ps[0][1], x)
    outs = ranks[1].forward(ps[1][0], ps[1][1], None)
    _, gouts, _ = ranks[1].loss_grads(outs, x, mse)
    ranks[1].backward(gouts)
    ranks[0].backward(None)
    paths = [recs[r].dump() for r in range(2)]
    return [load_dump(p) for p in paths], paths, recs


def test_clean_run_records_the_full_step(clean_run):
    dumps, _, _ = clean_run
    for d in dumps:
        kinds = {e.kind for e in d.events}
        assert {"forward_begin", "forward_end", "backward_begin",
                "backward_end", "fwd", "bwd", "clock_align"} <= kinds
        cells = [e for e in d.events if e.kind in ("fwd", "bwd")]
        assert all(e.dur is not None and e.dur >= 0 for e in cells)
        assert len(cells) == 4  # 2 micro-batches x fwd+bwd
    # Sender-side sends pair with receiver-side arrivals.
    sends = [e.channel for e in dumps[0].events
             if e.kind == "send" and e.channel[0] == "forward"]
    arrivals = [e.channel for e in dumps[1].events
                if e.kind == "mail_put" and e.channel[0] == "forward"]
    assert sends == arrivals


def test_postmortem_clean_run_is_not_a_hang(clean_run):
    dumps, _, _ = clean_run
    report = postmortem(dumps)
    assert not report.hang_suspected
    assert report.cursors == [
        len(report.graph.order[r]) for r in range(2)
    ]
    # Straggler table covers both ranks and both phases.
    assert {(s.rank, s.phase) for s in report.stragglers} == {
        (0, "fwd"), (0, "bwd"), (1, "fwd"), (1, "bwd"),
    }
    for s in report.stragglers:
        assert s.n == 2 and s.median_s > 0 and s.p99_s >= s.median_s
        assert s.skew > 0
    assert "not structurally stuck" in report.summary()


def test_merged_chrome_overlay_round_trip(clean_run, tmp_path):
    """Satellite: the merged two-rank timeline round-trips through
    tools/trace_report.py --chrome with per-rank pids and aligned
    timestamps."""
    from tools.trace_report import main as trace_main

    _dumps, paths, _ = clean_run
    out = os.path.join(tmp_path, "merged.json")
    rc = trace_main(["--dumps", *paths, "--chrome", out])
    assert rc == 0
    with open(out) as f:
        doc = json.load(f)
    events = [e for e in doc["traceEvents"]]
    assert {e["pid"] for e in events} == {0, 1}
    names = {e["args"]["name"] for e in events
             if e["name"] == "process_name"}
    assert names == {"rank 0 (w0)", "rank 1 (w1)"}
    slices = [e for e in events if e["ph"] == "X"]
    assert {s["name"] for s in slices if s["tid"] == 0} >= {
        "fwd(s0,mb0)", "bwd(s1,mb1)",
    }
    # Aligned, re-zeroed timestamps: everything non-negative, and rank
    # 1's first forward lands after rank 0's (the pipeline ordering
    # survives the merge).
    assert all(e["ts"] >= 0 for e in events if "ts" in e)

    def first_fwd(pid):
        return min(s["ts"] for s in slices
                   if s["pid"] == pid and s["name"].startswith("fwd"))

    assert first_fwd(1) > first_fwd(0)


def test_postmortem_cli_report_mode(clean_run, tmp_path, capsys):
    from tools.postmortem import main as pm_main

    _dumps, paths, _ = clean_run
    out = os.path.join(tmp_path, "m.json")
    rc = pm_main([*paths, "--chrome", out])
    assert rc == 0
    printed = capsys.readouterr().out
    assert "postmortem: distributed/gpipe" in printed
    assert "not structurally stuck" in printed
    with open(out) as f:
        assert json.load(f)["traceEvents"]


def test_align_clocks_offsets_are_small_in_process(clean_run):
    dumps, _, recs = clean_run
    assert recs[0].clock_offset == 0.0  # rank 0 IS the reference
    # Same process, same clock: the handshake's estimate is sub-ms.
    assert abs(dumps[1].clock_offset) < 5e-3


# --------------------------------------------------------------------- #
# the induced hang, end to end (fast: in-process threads)               #
# --------------------------------------------------------------------- #


def test_induced_hang_postmortem_names_the_exact_edge(tmp_path):
    """Acceptance: hang_at=('forward', 1) on a real LocalTransport run
    -> rank 1's bounded recv crash-dumps -> the analyzer names rank 1
    waiting on (stage 1, mb 1, fwd) from rank 0 as the ROOT edge, with
    rank 0's last event attached."""
    inner = LocalTransport()
    transport = FaultyTransport(inner, hang_at=("forward", 1))
    ranks, recs, _ = _build_two_ranks(
        transport, inner, recv_timeout=1.5, dump_dir=str(tmp_path)
    )
    try:
        ps = [rk.init(jax.random.PRNGKey(0), X_SPEC) for rk in ranks]
        x = jnp.ones((4, 8))
        t0 = threading.Thread(
            target=lambda: ranks[0].forward(ps[0][0], ps[0][1], x),
            daemon=True,
        )
        t0.start()
        with pytest.raises(TimeoutError):
            ranks[1].forward(ps[1][0], ps[1][1], None)
        recs[0].dump()
        dumps = [load_dump(os.path.join(tmp_path, f"rank{r}.json"))
                 for r in range(2)]
        # Rank 1's dump came from the crash path: final events recorded
        # BEFORE the raise (the recv_timeout satellite's contract).
        kinds1 = [e.kind for e in dumps[1].events]
        assert kinds1[-2:] == ["recv_timeout", "crash"]
        report = postmortem(dumps)
        assert report.hang_suspected
        root = report.blocking[0]
        assert root.root
        assert (root.rank, root.event.cell) == (1, (1, 1, "fwd"))
        assert root.channel == ("forward", 1)
        assert root.peer_rank == 0 and root.peer_sent
        assert root.wait_s == pytest.approx(1.5, abs=0.5)
        text = root.describe()
        assert "rank 1 waiting on recv (stage 1, mb 1, fwd)" in text
        assert "from rank 0" in text and "last event" in text
        assert "ROOT" in report.summary()
    finally:
        transport.release()


def test_hang_after_clean_steps_still_names_the_edge(tmp_path):
    """The frontier is windowed to the CURRENT step: cells completed by
    EARLIER clean steps (same ring, reused mailbox keys) must not mask
    where the hung step actually is."""
    inner = LocalTransport()
    transport = FaultyTransport(inner)  # hang armed AFTER the clean step
    ranks, recs, _ = _build_two_ranks(
        transport, inner, recv_timeout=1.5, dump_dir=str(tmp_path)
    )
    try:
        ps = [rk.init(jax.random.PRNGKey(0), X_SPEC) for rk in ranks]
        x = jnp.ones((4, 8))
        # One fully clean training step first.
        ranks[0].forward(ps[0][0], ps[0][1], x)
        outs = ranks[1].forward(ps[1][0], ps[1][1], None)
        _, gouts, _ = ranks[1].loss_grads(outs, x, mse)
        ranks[1].backward(gouts)
        ranks[0].backward(None)
        # Step 2 hangs at ('forward', 1).
        transport.hang_at = ("forward", 1)
        t0 = threading.Thread(
            target=lambda: ranks[0].forward(ps[0][0], ps[0][1], x),
            daemon=True,
        )
        t0.start()
        with pytest.raises(TimeoutError):
            ranks[1].forward(ps[1][0], ps[1][1], None)
        recs[0].dump()
        dumps = [load_dump(os.path.join(tmp_path, f"rank{r}.json"))
                 for r in range(2)]
        report = postmortem(dumps)
        assert report.hang_suspected, report.summary()
        root = report.blocking[0]
        assert root.root
        assert (root.rank, root.event.cell) == (1, (1, 1, "fwd"))
        assert root.channel == ("forward", 1)
        assert root.peer_rank == 0 and root.peer_sent
    finally:
        transport.release()


def test_hang_at_first_forward_blames_the_right_channel(tmp_path):
    """A peer that wedges BEFORE its first data send (hang at
    ('forward', 0)): rank 1 has matched the meta receive but completed
    no cell — the analyzer must blame ('forward', 0), not the already
    -delivered meta message (matched-by-an-unfinished-event payloads
    stay available to the replay)."""
    inner = LocalTransport()
    transport = FaultyTransport(inner, hang_at=("forward", 0))
    ranks, recs, _ = _build_two_ranks(
        transport, inner, recv_timeout=1.5, dump_dir=str(tmp_path)
    )
    try:
        ps = [rk.init(jax.random.PRNGKey(0), X_SPEC) for rk in ranks]
        x = jnp.ones((4, 8))
        t0 = threading.Thread(
            target=lambda: ranks[0].forward(ps[0][0], ps[0][1], x),
            daemon=True,
        )
        t0.start()
        with pytest.raises(TimeoutError):
            ranks[1].forward(ps[1][0], ps[1][1], None)
        recs[0].dump()
        dumps = [load_dump(os.path.join(tmp_path, f"rank{r}.json"))
                 for r in range(2)]
        report = postmortem(dumps)
        assert report.hang_suspected
        root = report.blocking[0]
        assert root.root
        assert (root.rank, root.event.cell) == (1, (1, 0, "fwd"))
        assert root.channel == ("forward", 0), report.summary()
        assert root.peer_rank == 0
    finally:
        transport.release()


def test_merged_chrome_handles_rankless_dumps(tmp_path):
    """Transport-only recorders carry no rank: the merge must give each
    its own pid, and trace_report --dumps must not crash sorting."""
    from tools.trace_report import main as trace_main

    paths = []
    for i in range(2):
        rec = FlightRecorder(worker=f"t{i}",
                             dump_path=str(tmp_path / f"d{i}.json"))
        rec.record("connect_retry", channel=("forward", 0), peer="b",
                   detail="attempt=1")
        paths.append(rec.dump())
    out = str(tmp_path / "m.json")
    rc = trace_main(["--dumps", *paths, "--chrome", out])
    assert rc == 0
    with open(out) as f:
        doc = json.load(f)
    assert len({e["pid"] for e in doc["traceEvents"]}) == 2


# --------------------------------------------------------------------- #
# TcpTransport anatomy: connect-retry history in the ring               #
# --------------------------------------------------------------------- #


def test_tcp_connect_retries_are_recorded_before_the_raise():
    import socket

    from torchgpipe_tpu.distributed import TcpTransport

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    pa, pb = free_port(), free_port()
    rec = FlightRecorder(rank=0, worker="a")
    transport = TcpTransport(
        "a", {"a": ("127.0.0.1", pa), "b": ("127.0.0.1", pb)},
        connect_timeout=1.2, recorder=rec,
    )
    try:
        with pytest.raises(TimeoutError, match="could not reach"):
            transport.send("b", "forward", 0, {"x": jnp.ones((2,))})
    finally:
        transport.close()
    kinds = [e.kind for e in rec.events()]
    assert kinds.count("connect_retry") >= 1
    # The final flight event lands BEFORE the exception: a dump from a
    # half-dead pipeline shows the whole retry history.
    assert kinds[-1] == "connect_timeout"
    retries = [e for e in rec.events() if e.kind == "connect_retry"]
    assert all(e.peer == "b" and "attempt=" in e.detail for e in retries)


# --------------------------------------------------------------------- #
# subprocess variants (slow)                                            #
# --------------------------------------------------------------------- #

_TCP_RANK_SCRIPT = r"""
import pathlib, sys, threading, time
import jax, jax.numpy as jnp
from torchgpipe_tpu.distributed import DistributedGPipe, TcpTransport
from torchgpipe_tpu.obs.flightrec import (
    FlightRecorder, StallWatchdog, align_clocks,
)
from torchgpipe_tpu.ops import dense
from torchgpipe_tpu.resilience.faults import FaultyTransport

rank = int(sys.argv[1])
pa, pb = int(sys.argv[2]), int(sys.argv[3])
out = pathlib.Path(sys.argv[4])
workers = ["w0", "w1"]
addresses = {"w0": ("127.0.0.1", pa), "w1": ("127.0.0.1", pb)}
rec = FlightRecorder(rank=rank, worker=workers[rank],
                     dump_path=str(out / f"rank{rank}.json"))
tcp = TcpTransport(workers[rank], addresses, connect_timeout=120.0,
                   recorder=rec)
transport = (
    FaultyTransport(tcp, hang_at=("forward", 1)) if rank == 0 else tcp
)
layers = [dense(8, name="a"), dense(8, name="b")]
pipe = DistributedGPipe(
    layers, rank, workers, [1, 1], chunks=2,
    transport=transport, mailbox=tcp.mailbox, recorder=rec,
    recv_timeout=30.0,
)
align_clocks(tcp, tcp.mailbox, rank, workers, rec, timeout=120.0)
params, state = pipe.init(
    jax.random.PRNGKey(0), jax.ShapeDtypeStruct((4, 8), jnp.float32)
)
if rank == 0:
    # The ('forward', 1) send hangs forever, so forward runs on a
    # daemon thread; the stall watchdog is what writes rank 0's dump —
    # exactly the production path for a rank hung in transport.
    watchdog = StallWatchdog(rec, timeout=4.0).start()
    threading.Thread(
        target=lambda: pipe.forward(params, state, jnp.ones((4, 8))),
        daemon=True,
    ).start()
    deadline = time.monotonic() + 120
    while not watchdog.stalled and time.monotonic() < deadline:
        time.sleep(0.2)
    watchdog.stop()
else:
    try:
        pipe.forward(params, state, None)
        raise SystemExit("UNEXPECTED: hung pipeline completed")
    except TimeoutError:
        pass  # crash dump already written by the recv path
(out / f"done{rank}").touch()
"""


@pytest.mark.slow  # two real OS processes + sockets + jax imports
def test_tcp_two_process_hang_postmortem(tmp_path):
    """The TcpTransport variant of the acceptance hang: rank 0 hangs in
    its ('forward', 1) send in one OS process (its STALL WATCHDOG
    writes its dump — a hung main thread cannot), rank 1's bounded
    recv crash-dumps in another; the merged dumps, clock-aligned by
    the TCP handshake, name the same injected edge."""
    import socket

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    pa, pb = free_port(), free_port()
    script = tmp_path / "tcp_rank.py"
    script.write_text(_TCP_RANK_SCRIPT)
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(r), str(pa), str(pb),
             str(tmp_path)],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            env=cpu_subproc_env(),
        )
        for r in range(2)
    ]
    try:
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if ((tmp_path / "done0").exists()
                    and (tmp_path / "done1").exists()):
                break
            time.sleep(0.5)
        assert (tmp_path / "done0").exists(), "rank 0 watchdog never fired"
        assert (tmp_path / "done1").exists(), "rank 1 never timed out"
        dumps = [load_dump(str(tmp_path / f"rank{r}.json"))
                 for r in range(2)]
        # Rank 0's dump came from the watchdog; rank 1's from the crash
        # path, its final events recorded before the raise.  Rank 0's
        # process exits once its watchdog fires, so rank 1's liveness
        # probe usually upgrades the timeout to peer_died — either
        # terminal event is the recv path's final record.
        assert any(e.kind == "stall_suspected" for e in dumps[0].events)
        assert any(e.kind in ("recv_timeout", "peer_died")
                   for e in dumps[1].events)
        report = postmortem(dumps)
        assert report.hang_suspected
        root = report.blocking[0]
        assert root.root
        assert (root.rank, root.event.cell) == (1, (1, 1, "fwd"))
        assert root.channel == ("forward", 1)
        assert root.peer_rank == 0 and root.peer_sent
        assert root.peer_last_t is not None  # clocks aligned over TCP
    finally:
        for p in procs:
            p.kill()
            p.wait()


@pytest.mark.slow  # spawns the full bounded-timeout CI fixture
def test_postmortem_verify_ci_gate(capsys):
    from tools.postmortem import main as pm_main

    rc = pm_main(["--ci"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "[postmortem-verify] OK" in out
    assert "rank 1 waiting on recv (stage 1, mb 1, fwd)" in out
