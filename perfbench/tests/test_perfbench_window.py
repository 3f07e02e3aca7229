"""The measured window: passes dispatched ahead of the one waited for,
every pass sent waited for and counted."""
from __future__ import annotations

import math

import pytest

from perfbench import harness


def _counter_call(kept: list):
    """A call whose answer counts the calls made so far."""
    import jax.numpy as jnp

    state = {"x": jnp.zeros(8, jnp.float32)}

    def run():
        state["x"] = state["x"] + 1
        return (state["x"],)

    return harness.Call(label="x", run=run, keep=kept.append,
                        nbytes={"hbm": 32}), state


@pytest.mark.parametrize("lead", (1, 5))
def test_window_waits_for_every_pass_it_sent(lead):
    kept: list = []
    call, state = _counter_call(kept)
    assert harness.warm_up([call], reps=2) > 0
    before = int(state["x"][0])
    win = harness.run_passes([call], 3, 0.05, lead=lead)
    sent = win.calls // 3
    assert sent >= 1 and len(win.passes) == sent
    assert win.nbytes["hbm"] == 32 * win.calls
    # the window ends at the last completion: its passes tile it
    assert math.isclose(sum(win.passes), win.seconds, rel_tol=1e-9)
    assert int(kept[-1][0][0]) == before + win.calls


def test_lead_is_seconds_of_passes_and_at_least_one():
    assert harness.lead_passes(0.0) == 1
    assert harness.lead_passes(2 * harness.LEAD_SECONDS) == 1
    assert harness.lead_passes(0.3) == math.ceil(harness.LEAD_SECONDS / 0.3)
