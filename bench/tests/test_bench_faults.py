"""``correct`` comes out false when the timed path is broken underneath,
and the fp8 control reads above the limit that sound runs stay under.

Each fault is planted in the engine the harness builds, at smoke width
on the CPU, past the harness's look for a chip.  A one-chip serving cell
can have three of the faults a run is checked for: a step that returns
its state unchanged, half of the batch left out, and a token altered
where it is produced.  It has no exchange between chips.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from bench import cells, measure


def stale_state(engine):
    """The step computes its logits but hands the old state back."""
    from repro.models import decode_step

    cfg = engine.cfg
    engine._step = jax.jit(lambda p, st, t: (decode_step(p, cfg, st, t)[0], st))


def half_batch(engine):
    """Lanes in the upper half get the lower half's logits."""
    step = engine._step
    half = engine.slots // 2

    def broken(p, st, t):
        logits, st = step(p, st, t)
        return jnp.concatenate([logits[:half]] * 2), st

    engine._step = broken


def altered_token(engine):
    """Every fifth step, lane 0's token is swapped for one far down its
    logits."""
    step = engine._step
    calls = [0]

    def broken(p, st, t):
        logits, st = step(p, st, t)
        calls[0] += 1
        if calls[0] % 5 == 0:
            row = logits[0, 0, :engine.cfg.vocab_size]
            far = (jnp.argmax(row) + engine.cfg.vocab_size // 2) % row.size
            logits = logits.at[0, 0, far].add(1e3)
        return logits, st

    engine._step = broken


def planted(fault):
    real = measure.build_engine

    def build(*a, **kw):
        engine = real(*a, **kw)
        fault(engine)
        return engine

    return build


CELLS = ["qwen3-4b.chat", "qwen3-moe-30b-a3b-8l.offline"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [stale_state, half_batch, altered_token])
def test_a_broken_timed_path_is_not_correct(smoke_root, peaks, monkeypatch,
                                            fault, name):
    monkeypatch.setattr(measure, "build_engine", planted(fault))
    cell = cells.load_cell(name, smoke_root)
    res = measure.measure(cell, 77, 1.0, False, jax.devices(), peaks,
                          time.perf_counter())
    assert res["correct"] is False
    assert any(c["value"] > c["max"] for c in res["checks"].values()
               if "max" in c)


@pytest.mark.parametrize("name", CELLS)
def test_fp8_control_fails_where_the_program_passes(smoke_root, peaks, name):
    cell = cells.load_cell(name, smoke_root)
    res = measure.measure(cell, 78, 1.0, False, jax.devices(), peaks,
                          time.perf_counter(), control=True)
    assert res["correct"] is True
    assert res["control"]["correct"] is False
    for name, limit in cell.config["check"]["limits"].items():
        assert res["numbers"][name] <= limit < res["control"][name]
