"""Benchmark entry point: one JSON line, measured in this process.

Metric: training samples/sec/chip on the BASELINE.json headline model
(AmoebaNet-D (18, 256)), compared against the reference torchgpipe's
published per-chip throughput: 132.413 samples/s on 8x Tesla P40 at
n=8, m=32 (reference: docs/benchmarks.rst:129-141) = 16.552 samples/s/chip.

The training step goes through the framework's own engine (GPipe with
activation checkpointing + micro-batching), not a raw jitted step, so the
number reflects the framework overhead the reference benchmarks measure.

One process imports jax, holds the chip and measures; there is no
supervisor, no child and no fallback.  Without a TPU the script exits
non-zero and prints no line.  The one exception is a caller who asks for
the CPU by name (``JAX_PLATFORMS=cpu python bench.py``): the same code
path then runs a toy model as a smoke test, its line says ``"platform":
"cpu"`` and carries no ``vs_baseline`` and no ``mfu`` — a CPU number is
never comparable with a chip's.  Every line names the device it ran on.
"""

from __future__ import annotations

import json
import os
import sys
import time

# Reference per-chip throughput: AmoebaNet-D (18,256), n=8 m=32, 8x P40.
BASELINE_SAMPLES_PER_SEC_PER_CHIP = 132.413 / 8


def _build_amoebanet(platform: str, n_stages: int):
    import jax.numpy as jnp

    from benchmarks.common import even_balance
    from torchgpipe_tpu.gpipe import GPipe
    from torchgpipe_tpu.models.amoebanet import amoebanetd

    if platform != "cpu":
        # bf16 compute (f32 masters/BN stats).  On one 16 GB v5e chip
        # batch 128 fits only the whole-step FUSED engine (no per-cell
        # residual arguments) — the best configuration the builders
        # measured before PR 1 (BENCH_NOTES.md); it needs every stage on
        # one device, so several chips take the per-cell engine at its
        # own best measured batch.  Choosing between the engines from
        # what the code observes is ROADMAP A7.
        num_layers, num_filters, image = 18, 256, 224
        fused = n_stages == 1
        batch, chunks = (128, 4) if fused else (64, 4)
        compute_dtype = jnp.bfloat16
    else:  # CPU smoke: same code path, toy size
        num_layers, num_filters, image = 3, 16, 32
        batch, chunks, fused = 8, 2, False
        compute_dtype = None
    layers = amoebanetd(num_classes=1000, num_layers=num_layers,
                        num_filters=num_filters)
    model = GPipe(layers, balance=even_balance(len(layers), n_stages),
                  chunks=chunks, checkpoint="except_last",
                  compute_dtype=compute_dtype, fused=fused)
    x = jnp.zeros((batch, image, image, 3), jnp.float32)
    y = jnp.zeros((batch,), jnp.int32)
    name = (f"amoebanetd-({num_layers},{num_filters})-pipeline{n_stages}"
            f"-b{batch}m{chunks}-except_last-{'fused' if fused else 'percell'}")
    return model, x, y, name


def main() -> None:
    cpu_asked = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    import jax
    import jax.numpy as jnp

    from benchmarks.common import sequential_step_flops
    from torchgpipe_tpu.utils.hw import chip_peak_bf16_flops

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not cpu_asked:
        sys.exit(
            f"bench.py needs a TPU; jax found {platform} "
            f"({devices[0].device_kind}).  JAX_PLATFORMS=cpu asks for the "
            "CPU smoke by name."
        )
    # Pipeline across the chips actually present.
    n_stages = min(8, len(devices))

    def loss_fn(out, tgt):
        logits = out.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        onehot = jax.nn.one_hot(tgt, logits.shape[-1], dtype=logp.dtype)
        return -jnp.mean(jnp.sum(onehot * logp, axis=-1))

    rng = jax.random.PRNGKey(1)
    model, x, y, name = _build_amoebanet(platform, n_stages)
    in_spec = jax.ShapeDtypeStruct(x.shape, x.dtype)

    def step(params, state, k):
        loss, grads, state, _ = model.value_and_grad(
            params, state, x, y, loss_fn, rng=k
        )
        return loss, grads, state

    params, state = model.init(jax.random.PRNGKey(0), in_spec)
    # Warm-up (compile), then one probe step to size the timed loop.
    loss, grads, _ = step(params, state, rng)
    jax.block_until_ready((loss, grads))
    t_probe = time.perf_counter()
    loss, grads, _ = step(params, state, jax.random.fold_in(rng, 999))
    jax.block_until_ready((loss, grads))
    step_time = time.perf_counter() - t_probe
    n_iters = max(3, min(30, int(30.0 / max(step_time, 1e-3))))

    t0 = time.perf_counter()
    for i in range(n_iters):
        loss, grads, _ = step(params, state, jax.random.fold_in(rng, i))
    jax.block_until_ready(grads)
    float(loss)  # the timed region ends on a value fetched to the host
    dt = time.perf_counter() - t0

    # Per-chip normalization: the pipeline spans n_stages chips.
    samples_per_sec = x.shape[0] * n_iters / dt / n_stages
    result = {
        "metric": f"train samples/sec/chip [{name}, {platform}]",
        "value": round(samples_per_sec, 3),
        "unit": "samples/sec/chip",
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    if platform == "tpu":
        # The published baseline is per accelerator chip, and MFU needs a
        # chip's peak: neither exists for the CPU smoke.
        result["vs_baseline"] = round(
            samples_per_sec / BASELINE_SAMPLES_PER_SEC_PER_CHIP, 3
        )
        peak = chip_peak_bf16_flops(devices[0])
        mfu = None
        if peak is not None:
            # MFU numerator: the UN-pipelined model's analytic work (fwd +
            # loss + bwd), so recomputation counts against utilization.
            step_flops = sequential_step_flops(
                model, params, state, x, y, loss_fn, rng
            )
            if step_flops is not None:
                mfu = round(step_flops * n_iters / dt / (n_stages * peak), 4)
        if mfu is not None and mfu > 1.0:
            sys.exit(
                f"bench: mfu {mfu} > 1 is impossible (the timed loop "
                "cannot have run every step); refusing to print a result"
            )
        result["mfu"] = mfu
    print(json.dumps(result), flush=True)


def _decode_serving_entry() -> None:
    """The ``decode-serving`` rung: tokens/sec through the continuous-
    batching engine vs the static run-to-longest baseline, at fixed slot
    counts (benchmarks/llama_serving.py — which owns the BENCH_NOTES.md
    measurement-integrity contract: tokens host-fetched INSIDE the timed
    region by construction, physical-floor refusal gate).  Emits its own
    one JSON line.

        python bench.py --decode-serving --preset 1b --slots 8   # TPU
        env JAX_PLATFORMS=cpu python bench.py --decode-serving   # CPU ref
    """
    sys.argv = [sys.argv[0]] + [
        a for a in sys.argv[1:] if a != "--decode-serving"
    ] + ["--json"]
    from benchmarks.llama_serving import main as serving_main

    serving_main()


def _megastep_entry() -> None:
    """The ``megastep`` rung: ms per optimizer step at megastep K over
    the canonical ladder (tune.megastep_options — K in {1, 4, 16}) on
    the CPU tiny llama preset (benchmarks/llama_megastep.py, which owns
    the measurement contract: warmup per K, block_until_ready-bounded
    windows, cross-K loss agreement asserted).  Emits one JSON line::

        env JAX_PLATFORMS=cpu python bench.py --megastep
    """
    import sys as _sys

    _sys.argv = [_sys.argv[0]] + [
        a for a in _sys.argv[1:] if a != "--megastep"
    ] + ["--json"]
    from benchmarks.llama_megastep import main as megastep_main

    raise SystemExit(megastep_main())


def _packing_entry() -> None:
    """The ``packing`` rung: one ragged CPU corpus (~50% natural
    padding) trained PACKED (utils.data.pack_documents, segment-aware
    attention) vs PADDED through the same SpmdGPipe — real tokens/s
    must move toward the 1/(1-pad_fraction) bound (>= 1.3x at this
    corpus), with per-document losses matched within the pinned
    tolerance (equivalence always gates) — plus a ragged bursty serving
    mix with the prefill bucket ladder on vs off, TTFT/TPOT percentiles
    reported for both (benchmarks/packing_speed.py).  Emits one JSON
    line::

        env JAX_PLATFORMS=cpu python bench.py --packing
    """
    import sys as _sys

    _sys.argv = [_sys.argv[0]] + [
        a for a in _sys.argv[1:] if a != "--packing"
    ] + ["--json"]
    from benchmarks.packing_speed import main as packing_main

    raise SystemExit(packing_main())


def _obs_overhead_entry() -> None:
    """The ``obs-overhead`` rung: CPU tiny-llama step time with the
    telemetry layer fully on (sync=False Timeline + MetricsRegistry +
    StepReporter) vs bare, interleaved A/B rounds, medians compared
    (benchmarks/obs_overhead.py).  Gated at <2% overhead — exits
    non-zero past the gate.  Emits one JSON line::

        env JAX_PLATFORMS=cpu python bench.py --obs-overhead
    """
    from benchmarks.obs_overhead import main as obs_overhead_main

    raise SystemExit(obs_overhead_main())


def _flightrec_overhead_entry() -> None:
    """The ``flightrec-overhead`` rung: 2-rank LocalTransport llama-block
    step time with the flight recorder + stall watchdog fully on vs
    bare, interleaved A/B rounds, medians compared
    (benchmarks/flightrec_overhead.py).  Gated at <2% overhead — exits
    non-zero past the gate.  Emits one JSON line::

        env JAX_PLATFORMS=cpu python bench.py --flightrec-overhead
    """
    from benchmarks.flightrec_overhead import main as flightrec_main

    raise SystemExit(flightrec_main())


def _fleet_entry() -> None:
    """The ``fleet`` rung: a seeded synthetic trace (ragged, bursty,
    shared-prefix tenants) through the replica router, the radix prefix
    cache, and speculative decoding (benchmarks/fleet_trace.py — which
    owns the measurement contract: all rungs must emit bitwise-identical
    streams before any number publishes, and the trace generator's
    skipped-request honesty counters ride in the same JSON line)::

        env JAX_PLATFORMS=cpu python bench.py --fleet
    """
    sys.argv = [sys.argv[0]] + [
        a for a in sys.argv[1:] if a != "--fleet"
    ] + ["--json"]
    from benchmarks.fleet_trace import main as fleet_main

    fleet_main()
    raise SystemExit(0)


def _elastic_entry() -> None:
    """The ``elastic`` rung: the SLO-priced fleet autoscaler vs static
    peak provisioning on the same bursty MMPP trace
    (benchmarks/elastic_autoscale.py — which owns the measurement
    contract: both rungs must emit bitwise-identical streams before any
    number publishes, the fleet must breathe BOTH ways above the floor,
    the autoscaled integral of in-rotation replicas over trace time
    must undercut the static peak bill, and the declared TPOT p95
    objective must hold while scaled)::

        env JAX_PLATFORMS=cpu python bench.py --elastic
    """
    sys.argv = [sys.argv[0]] + [
        a for a in sys.argv[1:] if a != "--elastic"
    ] + ["--json"]
    from benchmarks.elastic_autoscale import main as elastic_main

    elastic_main()
    raise SystemExit(0)


def _disagg_entry() -> None:
    """The ``disagg`` rung: phase-disaggregated serving (1 prefill + 1
    decode replica, KV migrated through the fixed-shape
    ``migrate_ingest`` program) vs a unified 2-replica fleet on the same
    prefill-heavy MMPP trace (benchmarks/disagg_trace.py — which owns
    the measurement contract: both rungs must emit bitwise-identical
    streams before any number publishes, TPOT is measured on per-replica
    step clocks so the figure is deterministic, and the headline gate is
    isolation — the disagg decode pool must hold the 1 step/token floor
    under the prefill burst while unified measurably degrades)::

        env JAX_PLATFORMS=cpu python bench.py --disagg
    """
    sys.argv = [sys.argv[0]] + [
        a for a in sys.argv[1:] if a != "--disagg"
    ] + ["--json"]
    from benchmarks.disagg_trace import main as disagg_main

    disagg_main()
    raise SystemExit(0)


def _moe_entry() -> None:
    """The ``moe`` rung: an E-expert top-k MoE llama vs a dense llama
    at MATCHED parameter count (dense MLP hidden = E x the expert
    hidden) through the same SpmdGPipe engine on the same token stream
    (benchmarks/moe_dense.py — which owns the measurement contract:
    dropless dispatch so per-step FFN work is exactly ``k*t`` expert
    rows, parameter counts asserted matched within 2% before any
    number publishes, tokens/s for both rungs and the active-parameter
    fraction in one JSON line)::

        env JAX_PLATFORMS=cpu python bench.py --moe
    """
    sys.argv = [sys.argv[0]] + [
        a for a in sys.argv[1:] if a != "--moe"
    ] + ["--json"]
    from benchmarks.moe_dense import main as moe_main

    raise SystemExit(moe_main())


def _rollout_entry() -> None:
    """The ``rollout`` rung: live weight rollouts under a mixed-tier
    MMPP trace — a 2-replica QoS fleet completes two rolling updates
    and one forced rollback mid-trace vs a no-rollout control on the
    same requests (benchmarks/rollout_trace.py — which owns the
    measurement contract: zero dropped streams, every stream bitwise
    the control's, interactive-tier TPOT p95 within 1.1x control on
    per-replica step clocks, timed region compile-free)::

        env JAX_PLATFORMS=cpu python bench.py --rollout
    """
    sys.argv = [sys.argv[0]] + [
        a for a in sys.argv[1:] if a != "--rollout"
    ] + ["--json"]
    from benchmarks.rollout_trace import main as rollout_main

    rollout_main()
    raise SystemExit(0)


def _plan_validate_entry() -> None:
    """The ``plan-validate`` rung: predicted-vs-measured rank-order check
    of the static planner on the CPU tiny-llama preset
    (benchmarks/plan_validate.py — the recompute axis, whose work
    differences a serialized CPU host CAN measure).  Emits one JSON line
    and exits non-zero when the planner's predicted best-to-worst order
    disagrees with the measured fastest-to-slowest order::

        env JAX_PLATFORMS=cpu python bench.py --plan-validate
    """
    from benchmarks.plan_validate import main as plan_validate_main

    raise SystemExit(plan_validate_main())


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--obs-overhead" in sys.argv:
        _obs_overhead_entry()
    elif "--flightrec-overhead" in sys.argv:
        _flightrec_overhead_entry()
    elif "--plan-validate" in sys.argv:
        _plan_validate_entry()
    elif "--fleet" in sys.argv:
        _fleet_entry()
    elif "--elastic" in sys.argv:
        _elastic_entry()
    elif "--disagg" in sys.argv:
        _disagg_entry()
    elif "--moe" in sys.argv:
        _moe_entry()
    elif "--rollout" in sys.argv:
        _rollout_entry()
    elif "--megastep" in sys.argv:
        _megastep_entry()
    elif "--packing" in sys.argv:
        _packing_entry()
    elif "--decode-serving" in sys.argv:
        _decode_serving_entry()
    else:
        main()
