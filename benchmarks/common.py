"""Shared benchmark-driver plumbing.

Mirrors the reference drivers' structure (timed epochs over synthetic data,
``HH:MM:SS | throughput`` progress lines — reference:
benchmarks/amoebanetd-speed/main.py:121-138, 235-265) on the TPU-native
engine: one :func:`run_speed` / :func:`run_memory` pair serves every model
family.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from torchgpipe_tpu.gpipe import GPipe
from torchgpipe_tpu.layers import Layer


def hr_time(seconds: float) -> str:
    m, s = divmod(int(seconds), 60)
    h, m = divmod(m, 60)
    return f"{h:02d}:{m:02d}:{s:02d}"


def even_balance(n_layers: int, n_stages: int) -> List[int]:
    base, rem = divmod(n_layers, n_stages)
    return [base + (1 if j >= n_stages - rem else 0) for j in range(n_stages)]


def softmax_xent(out, tgt):
    logits = out.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits.reshape(-1, logits.shape[-1]))
    return -jnp.mean(logp[jnp.arange(logp.shape[0]), tgt.reshape(-1)])


def mse(out, tgt):
    return jnp.mean((out.astype(jnp.float32) - tgt) ** 2)


def build_gpipe(
    layers: Sequence[Layer],
    balance: Optional[Sequence[int]],
    n_stages: int,
    chunks: int,
    checkpoint: str,
    devices=None,
    tracer=None,
    bf16: bool = False,
    deferred_batch_norm: bool = False,
) -> GPipe:
    if balance is None:
        balance = even_balance(len(layers), n_stages)
    return GPipe(
        list(layers), balance, chunks=chunks, checkpoint=checkpoint,
        devices=devices, tracer=tracer,
        compute_dtype=jnp.bfloat16 if bf16 else None,
        deferred_batch_norm=deferred_batch_norm,
    )


def bf16_option(fn):
    """Shared ``--bf16`` click option: bfloat16 compute with f32 masters
    (torchgpipe_tpu.precision; no reference counterpart — the reference
    trains float32 only)."""
    import click

    return click.option(
        "--bf16/--no-bf16", default=False,
        help="bfloat16 compute, float32 masters + norm statistics",
    )(fn)


def run_epoch_loop(
    step_fn: Callable,
    batch: int,
    *,
    epochs: int,
    steps_per_epoch: int,
    skip_epochs: int = 1,
    label: str = "experiment",
) -> float:
    """Timed training epochs over ``step_fn(global_step) -> (loss, block_on)``;
    returns steady-state samples/sec.

    Reference loop shape: benchmarks/amoebanetd-speed/main.py:235-265
    (first epoch discarded as warm-up/compile).  With a single epoch nothing
    can be discarded, so the warm-up epoch is measured rather than reporting
    zero.
    """
    skip = skip_epochs if epochs > skip_epochs else 0
    throughputs = []
    t_start = time.time()
    for epoch in range(epochs):
        t0 = time.time()
        for step in range(steps_per_epoch):
            loss, block_on = step_fn(epoch * steps_per_epoch + step)
        jax.block_until_ready(block_on)
        dt = time.time() - t0
        tput = batch * steps_per_epoch / dt
        if epoch >= skip:
            throughputs.append(tput)
        print(
            f"{hr_time(time.time() - t_start)} | {label} | epoch {epoch + 1}: "
            f"{tput:.1f} samples/sec, loss {float(loss):.4f}"
            + ("  (warm-up)" if epoch < skip else ""),
            flush=True,
        )
    return sum(throughputs) / max(1, len(throughputs))


def analytic_flops(step: Callable, *args) -> Optional[float]:
    """Model FLOPs of one ``step(*args)`` call from XLA's HLO cost
    analysis.  ``args`` may be arrays or ``ShapeDtypeStruct``s — lowering
    happens from abstract avals (``lower()`` only traces, no compile, and
    nothing executes).

    The program is lowered for the HOST CPU client whatever the default
    backend: the TPU client costs only compiled executables
    (``Lowered.cost_analysis()`` is ``None`` there — seen on the v5e
    with the stock client), and compiling a full-size step just to count
    it would cost minutes.  Analytic model FLOPs do not depend on the
    platform.  Attention is pinned to the dense path while tracing: a
    Pallas kernel is a custom call, which the analysis counts as zero
    FLOPs, and Mosaic does not lower for the CPU."""
    from torchgpipe_tpu.parallel.ring_attention import dense_attention_only

    specs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args
    )
    cpu = jax.local_devices(backend="cpu")[0]
    with dense_attention_only(), jax.default_device(cpu):
        cost = jax.jit(step).lower(*specs).cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if cost is None:
        return None
    flops = float(cost.get("flops", 0.0))
    return flops if flops > 0 else None


def print_mfu(
    step_flops, tput: float, batch: int, label: str, n_chips: int = 1,
    device=None,
) -> None:
    """One ``label | mfu …`` line when the default device has a published
    bf16 peak (``torchgpipe_tpu.utils.hw``); silent on host-CPU runs.

    ``step_flops`` is the per-step model FLOPs, or a zero-arg callable
    producing them — the callable is only invoked on a known chip, so
    host-CPU runs never pay the lowering.  ``tput`` is AGGREGATE
    samples/sec; ``n_chips`` divides the peak so a pipeline spanning n
    chips is graded against n chips' worth of FLOP/s (matching
    ``bench.py``'s ``n_chips * peak`` denominator).

    MFU convention matches ``bench.py``: the numerator is the
    UN-pipelined model's analytic work (fwd + loss + bwd, no recompute),
    so activation rematerialization counts *against* utilization rather
    than inflating it.  An MFU above 1.0 is physically impossible —
    the timed loop cannot have run every step it counted — so it is
    flagged as invalid rather than printed as a result, mirroring
    ``bench.py``'s refusal to print impossible numbers.

    ``device`` is the device the timed programs actually ran on (a model
    placed on explicit devices — e.g. a host-CPU debug run on a
    TPU-attached machine — must not be graded against the default
    device's peak); defaults to ``jax.devices()[0]``."""
    from torchgpipe_tpu.utils.hw import chip_peak_bf16_flops

    peak = chip_peak_bf16_flops(
        jax.devices()[0] if device is None else device
    )
    if peak is None or tput <= 0:
        return
    if callable(step_flops):
        step_flops = step_flops()
    if step_flops is None:
        return
    mfu = step_flops * tput / batch / (max(1, n_chips) * peak)
    if mfu > 1.0:
        print(
            f"MFU   | {label}: INVALID ({100 * mfu:.1f}% > 100% is "
            "physically impossible — the timed loop's programs cannot "
            "all have executed; do not publish this run)",
            flush=True,
        )
        return
    print(
        f"MFU   | {label}: {100 * mfu:.2f}% "
        f"(analytic model FLOPs {step_flops:.3e}/step over "
        f"{max(1, n_chips)}x {peak:.3g} peak bf16 FLOP/s)",
        flush=True,
    )


def distinct_chips(model: GPipe) -> int:
    """Number of distinct devices the model's stages are placed on."""
    return len({(d.platform, d.id) for d in model.devices})


def sequential_step_flops(model: GPipe, params, state, x, y,
                          loss_fn: Callable, rng) -> Optional[float]:
    """Analytic FLOPs of the equivalent un-pipelined training step of a
    :class:`GPipe` model (the MFU numerator — see :func:`print_mfu`).
    Losses returning ``(loss, aux)`` are reduced to the scalar.  Returns
    ``None`` when the backend's client costs no FLOPs for the step."""
    from torchgpipe_tpu.layers import sequential_apply

    flat_p = [p for stage in params for p in stage]
    flat_s = [s for stage in state for s in stage]

    def step(fp, xx, yy):
        def loss_of(fp):
            out, _ = sequential_apply(
                model.layers, fp, flat_s, xx, rng=rng, train=True
            )
            loss = loss_fn(out, yy)
            return loss[0] if isinstance(loss, tuple) else loss

        return jax.value_and_grad(loss_of)(fp)

    return analytic_flops(step, flat_p, x, y)


def run_speed(
    model: GPipe,
    x,
    y,
    loss_fn: Callable,
    *,
    epochs: int = 3,
    steps_per_epoch: int = 10,
    skip_epochs: int = 1,
    label: str = "experiment",
    after: Optional[Callable] = None,
    reporter=None,
) -> float:
    """Timed SGD epochs through the GPipe engine; steady-state samples/sec.

    ``after(params, state)`` (optional) runs on the trained values once the
    loop finishes — e.g. the MoE driver prints router balance stats.  On a
    chip with a known bf16 peak an ``MFU`` line follows the epoch lines
    (:func:`print_mfu`).

    ``reporter`` is a :class:`torchgpipe_tpu.obs.StepReporter` (one is
    created by default): every driver step ticks it, and one structured
    ``OBS |`` summary line (step-time p50/p95, samples/s, first-step
    compile time) closes the run — the telemetry every speed benchmark
    reports against.  Dispatch-granularity times: the loop blocks per
    epoch, so per-step figures include async overlap (throughput truth
    lives in the epoch lines; the percentiles catch recompiles and
    stragglers).
    """
    in_spec = jax.ShapeDtypeStruct(x.shape, x.dtype)
    params, state = model.init(jax.random.PRNGKey(0), in_spec)
    rng = jax.random.PRNGKey(1)
    carry = {"params": params, "state": state}

    if reporter is None:
        from torchgpipe_tpu.obs import StepReporter

        reporter = StepReporter(
            items_per_step=x.shape[0], items_label="samples",
            label=label, log_every=0,
        )

    # The input pipeline the drivers measure WITH, not around: batches
    # stream through the double-buffered prefetcher (utils.data), so the
    # host→device copy of batch k+1 overlaps step k's compute — the
    # hot-path wiring docs/tuning.md's input-pipeline section describes.
    from itertools import repeat

    from torchgpipe_tpu.utils.data import prefetch_to_pipe

    batches = prefetch_to_pipe(repeat((x, y)), model, size=2)

    def step_fn(global_step):
        key = jax.random.fold_in(rng, global_step)
        xb, yb = next(batches)
        loss, grads, new_state, _ = model.value_and_grad(
            carry["params"], carry["state"], xb, yb, loss_fn, rng=key
        )
        carry["params"] = tuple(
            jax.tree_util.tree_map(lambda p, g: p - 1e-4 * g, ps, gs)
            for ps, gs in zip(carry["params"], grads)
        )
        carry["state"] = new_state
        reporter.step()
        return loss, carry["params"]

    tput = run_epoch_loop(
        step_fn, x.shape[0], epochs=epochs, steps_per_epoch=steps_per_epoch,
        skip_epochs=skip_epochs, label=label,
    )
    print(reporter.line(), flush=True)
    print_mfu(
        lambda: sequential_step_flops(
            model, params, state, x, y, loss_fn, rng
        ),
        tput, x.shape[0], label, n_chips=distinct_chips(model),
        device=model.devices[0],
    )
    if after is not None:
        after(carry["params"], carry["state"])
    return tput


def run_memory(
    model: GPipe, x, y, loss_fn: Callable, *, label: str = "experiment"
) -> Tuple[int, List[int]]:
    """Parameter count + per-device peak memory for one training step.

    The reference reads ``torch.cuda.max_memory_*`` per device
    (benchmarks/unet-memory/main.py RESULT section); TPU equivalent is
    ``device.memory_stats()['peak_bytes_in_use']`` where available (real TPU),
    falling back to live params bytes on host platforms.
    """
    in_spec = jax.ShapeDtypeStruct(x.shape, x.dtype)
    params, state = model.init(jax.random.PRNGKey(0), in_spec)
    n_params = sum(
        leaf.size for leaf in jax.tree_util.tree_leaves(params)
    )
    loss, grads, state, _ = model.value_and_grad(
        params, state, x, y, loss_fn, rng=jax.random.PRNGKey(1)
    )
    jax.block_until_ready((loss, grads))

    peaks: List[int] = []
    for dev in dict.fromkeys(model.devices):
        stats = getattr(dev, "memory_stats", lambda: None)()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
        else:
            stage_bytes = 0
            for j, d in enumerate(model.devices):
                if d == dev:
                    stage_bytes += sum(
                        leaf.size * leaf.dtype.itemsize
                        for leaf in jax.tree_util.tree_leaves(params[j])
                    )
            peaks.append(stage_bytes)
    print(
        f"RESULT | {label} | parameters: {n_params / 1e6:.1f}M | "
        f"per-device peak bytes: {[f'{p / 2**20:.0f}MiB' for p in peaks]}",
        flush=True,
    )
    return n_params, peaks
