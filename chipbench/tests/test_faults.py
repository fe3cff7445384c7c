"""A whole run (everything but the look for a chip) with the timed path
broken underneath comes out ``correct: false``; unbroken it comes out true."""

from unittest import mock

import jax
import jax.numpy as jnp
import pytest

import toy
from chipbench.run import run_cell


def run(workload, fault=None, config=toy.TOY_CONFIG, seconds=1.0):
    return run_cell(toy.cell_of(workload), 7, seconds, False, require_tpu=False, config_patch=config,
                    traffic_patch=toy.traffic_patch(workload), fault=fault)


def state_unchanged(point, step):
    """A step that returns its state as it got it (a copy: the step donates)."""
    if point != "train_step":
        return step

    def unchanged(p, o, x, y):
        kept = jax.tree_util.tree_map(jnp.copy, (p, o))
        return (step(p, o, x, y)[0],) + kept
    return unchanged


def half_batch(point, step):
    """Half of the batch left out, the mean taken over the rest."""
    if point != "train_step":
        return step

    def halved(p, o, x, y):
        h = x.shape[0] // 2
        return step(p, o, jnp.concatenate([x[:h], x[:h]]), jnp.concatenate([y[:h], y[:h]]))
    return halved


def no_exchange(point, step):
    """The exchange between chips left out: the step is traced with every
    ``ppermute`` handing a stage its own output back."""
    if point != "train_step":
        return step

    def traced_without(*args):
        with mock.patch.object(jax.lax, "ppermute", lambda x, axis_name, perm: x):
            return step(*args)
    return traced_without


def altered_token(point, token):
    """A token altered where it is produced (every one, by one id)."""
    return (token + 1) % toy.TOY_CONFIG["vocab_size"] if point == "token" else token


@pytest.mark.parametrize("workload,config", [(toy.TRAIN, toy.TOY_CONFIG), (toy.STEADY, toy.TOY_CONFIG),
                                              (toy.BACKLOG, toy.TOY_CONFIG), (toy.PP4, toy.PP4_CONFIG)])
def test_sound_run_is_correct(workload, config):
    result = run(workload, config=config, seconds=4.0 if workload == toy.STEADY else 1.0)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("workload,config,fault", [
    (toy.TRAIN, toy.TOY_CONFIG, state_unchanged),
    (toy.TRAIN, toy.TOY_CONFIG, half_batch),
    (toy.PP4, toy.PP4_CONFIG, no_exchange),
    (toy.PP4, toy.PP4_CONFIG, half_batch),
    (toy.STEADY, toy.TOY_CONFIG, altered_token),
    (toy.BACKLOG, toy.TOY_CONFIG, altered_token),
])
def test_broken_run_is_not_correct(workload, config, fault):
    result = run(workload, fault=fault, config=config,
                 seconds=4.0 if workload == toy.STEADY else 1.0)
    assert not result["correct"], result["compared"]
