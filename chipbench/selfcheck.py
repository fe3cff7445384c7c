"""Checks of the yardstick itself, run by hand on the CPU (not by tier-1):

    JAX_PLATFORMS=cpu python3 chipbench/selfcheck.py

* the traffic generators give the same multiset for two seeds, in different
  orders, and the same N;
* the metric arithmetic: percentiles, the FLOP closed forms against a
  hand-counted two-layer case, the interval union;
* the trace reduction on the small recorded TPU trace in ``testdata/``;
* ``run.py`` end to end at toy width on the CPU: counts and correctness
  only, and no device metric under its name (a CPU has no peaks entry).
"""

from __future__ import annotations

import collections
import pathlib
import sys
from unittest import mock

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "chipbench" / "tests")]

from chipbench import peaks, trace, traffic  # noqa: E402
from chipbench.common import HERE, percentile, worst_leaf_gap  # noqa: E402
from chipbench.run import load_traffic, run_cell  # noqa: E402


def check(cond: bool, what: str) -> None:
    print(("ok   " if cond else "FAIL ") + what)
    if not cond:
        raise SystemExit(1)


def main() -> None:
    import toy
    mix = load_traffic("conv-backlog")
    steady = dict(mix, **{k: v for k, v in toy.OPEN_LOOP.items() if k not in toy.TOY_SIZES})
    seconds = steady["arrival_span_s"] + steady["drain_s"]
    a = traffic.open_loop_fixed_set(steady, 1, seconds, 32000)
    b = traffic.open_loop_fixed_set(steady, 2 ** 31 + 5, seconds, 32000)
    sizes = lambda rs: collections.Counter((len(r.prompt), r.new_tokens) for r in rs)
    lengths = lambda rs: [(len(r.prompt), r.new_tokens) for r in rs]
    check(len(a) == len(b) == mix["requests"], f"open loop: two seeds offer the same N = {len(a)}")
    check(sizes(a) == sizes(b), "open loop: two seeds serve the same multiset of (prompt_len, new_tokens)")
    check(lengths(a) != lengths(b), "open loop: in different orders")
    check(all(x.due_s <= y.due_s for x, y in zip(a, a[1:])) and a[-1].due_s <= steady["arrival_span_s"],
          "open loop: due times sorted, inside the arrival span")
    c, d = traffic.backlog(mix, 3, 44.0, 32000), traffic.backlog(mix, 2 ** 31 + 5, 44.0, 32000)
    check(sizes(c) == collections.Counter({k: v * mix["cycles"] for k, v in sizes(a).items()}),
          "the backlog is the same multiset, cycles times over, all due at 0")
    check(lengths(c) == lengths(d) and any((x.prompt[:8] != y.prompt[:8]).any() for x, y in zip(c, d)),
          "backlog: two seeds give the same sizes in the same order, and other token ids")
    check(max(p + o for p, o in lengths(c)) == mix["max_total"], "the longest context is max_total")
    prompts, outputs = sorted(len(r.prompt) for r in a), sorted(r.new_tokens for r in a)
    print(f"     multiset: N {len(a)}, prompt mean {sum(prompts) / len(a):.1f} median "
          f"{percentile(prompts, 50):.0f} p95 {percentile(prompts, 95):.1f} max {prompts[-1]}, "
          f"output mean {sum(outputs) / len(a):.1f} median {percentile(outputs, 50):.0f} "
          f"p95 {percentile(outputs, 95):.1f} max {outputs[-1]}")

    check(percentile([1, 2, 3, 4, 5], 50) == 3 and abs(percentile(range(101), 95) - 95) < 1e-9,
          "percentiles")
    check(trace.covered([(0, 2), (1, 3), (5, 6)]) == 4, "interval union")
    check(abs(worst_leaf_gap([1.1, 0.0], [1.0, 0.001]) - 0.1) < 1e-9,
          "worst leaf gap is measured against the larger of the leaf and the median leaf")
    # Two layers, hidden 8, 2 heads of 4, 1 KV head, intermediate 16, vocab 32,
    # sequence 4, by hand: a layer's matmul weights = 8*8 (q) + 2*8*4 (k,v)
    # + 8*8 (o) + 3*8*16 (mlp) = 576; head 8*32 = 256; mean keys of 4 causal
    # positions = (1+2+3+4)/4 = 2.5; attention forward per query
    # = 4 * 2 heads * 4 * 2.5 = 80.
    m = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
         "intermediate_size": 16, "vocab_size": 32, "sliding_window": None}
    check(peaks.layer_matmul_params(m) == 576 and peaks.head_matmul_params(m) == 256,
          "matmul weights of the hand-counted layer and head")
    check(peaks.train_flops_per_token(m, 2, 4) == 6 * (2 * 576 + 256) + 3 * 2 * 80,
          "train FLOPs per token = 6 x weights + 3 x attention forward")
    check(peaks.mean_keys(4, 2) == (1 + 2 + 2 + 2) / 4, "a sliding window caps the keys")
    check(peaks.flash_call(m, 1, 4, False)["flops"] == 2 * 2 * 2 * 4 * 10,
          "flash forward = two products over the causal pairs")

    reduced = trace.reduce_dir(HERE / "testdata")
    check(reduced["n_devices"] == 1 and len(reduced["modules"]) == 1
          and all(len(v) == 3 for v in reduced["modules"].values()),
          "recorded trace: one device, one program, three calls")
    check(0 < reduced["busy_s"] < reduced["window_s"], "recorded trace: 0 < busy < window")
    check(sum(reduced["op_calls"].values()) == 9 and "fusion bf16[]" in reduced["op_seconds"],
          "recorded trace: nine operations, names shortened")
    check(reduced["breakdown"]["idle_gaps"][0][0] == "cb.train_step",
          "recorded trace: the idle gaps fall under the benchmark's own span")
    # Two devices, by hand (no recorded four-chip trace is kept): each runs
    # one kernel call of 2 s and one of 1 s; a call's least time is made 0.3 s.
    kernel = "%k = (bf16[1,2,3]{2,1,0}, f32[1,2,1]{2,1,0}) custom-call(%a, %b, %c), custom_call_target=\"tpu_custom_call\""
    two = trace.reduce_events({"spans": [], "devices": {
        f"/device:TPU:{d}": {"ops": [(kernel, d, d + 2.0), (kernel, d + 2.5, d + 3.5)], "modules": []}
        for d in (0, 1)}})
    name = trace.short_name(kernel)[0]
    check(two["n_devices"] == 2 and two["op_calls"][name] == 4 and two["op_seconds"][name] == 6.0
          and two["breakdown"]["device_ops"][0] == [name, 3.0] and two["busy_s"] == 3.0,
          "two devices: calls and seconds are sums over the devices, the breakdown a device's mean")
    from chipbench import layers
    with mock.patch.object(peaks, "roofline_seconds", return_value={"seconds": 0.3}), \
            mock.patch.object(peaks, "flash_call", return_value={}):
        share = layers.kernel_roofline(
            {"trace": two, "peaks": {}, "rows": 8, "chunks": 8, "seq": 4, "cell": mock.Mock()},
            [r"tpu_custom_call/3$"], r"tpu_custom_call/3$", False)
    check(abs(share - 100.0 * 4 * 0.3 / 6.0) < 1e-9,
          "two devices: a kernel's roofline share is calls x least time over its seconds, both summed")

    for workload in (toy.TRAIN, toy.BACKLOG, toy.STEADY):
        result = run_cell(toy.cell_of(workload), 2 ** 31 + 11, 4.0 if workload == toy.STEADY else 1.0, False,
                          require_tpu=False, config_patch=toy.TOY_CONFIG,
                          traffic_patch=toy.traffic_patch(workload))
        check(result["correct"] and result["device"]["platform"] == "cpu",
              f"{workload} at toy width on the CPU: correct, device named as the CPU, "
              f"attempted {result['attempted']} failed {result['failed']}")
    try:
        run_cell(toy.TRAIN, 1, 1.0, False)
        check(False, "a run without a TPU is refused")
    except SystemExit as e:
        check("TPU" in str(e), "a run without a TPU is refused before any work")
    try:
        peaks.peaks_for("cpu")
        check(False, "an unknown device has no peaks")
    except KeyError:
        check(True, "an unknown device has no peaks: no device metric can be named on it")


if __name__ == "__main__":
    main()
