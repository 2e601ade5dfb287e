"""The check that decides ``correct``, rehearsed on the CPU at smoke widths.

A whole run (set-up, window, drain, reference replay) is driven without the
look for a chip.  A sound run must come out correct; the control (the
configuration's bfloat16 path) and each fault the cells can have, planted
in the timed path, must come out not correct:

* a step that returns its state unchanged;
* half of each batch left out, the mean taken over the rest;
* a token altered where it is produced (the synthesizer, host and device);
* an answer altered where it is produced (the loss a lane reports).

The cells run on one chip each, so there is no exchange between chips to
leave out.
"""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import testing as T  # noqa: E402


@pytest.mark.parametrize("cell", ["sc2-asha-scan", "sc2-random-perstep"])
def test_sound_run_is_correct(cell):
    out = T.run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compiles_in_window"] == 0


@pytest.mark.parametrize("cell", ["sc2-asha-scan", "sc2-random-perstep"])
def test_control_is_not_correct(cell):
    out = T.run_tiny(cell, control=True)
    assert not out["correct"], out["checks"]


def _unchanged_state(monkeypatch):
    from repro.train import population as P

    make = P.make_population_train_step

    def broken(tc, per_trial_batch=False):
        step = make(tc, per_trial_batch)

        def pop_step(pstate, batch, hp):
            new, metrics = step(pstate, batch, hp)
            inner = dict(new["inner"], params=pstate["inner"]["params"])
            return dict(new, inner=inner), metrics

        return pop_step

    monkeypatch.setattr(P, "make_population_train_step", broken)


def _half_batch(monkeypatch):
    from repro.train import loss as L

    ce = L.cross_entropy

    def broken(logits, targets, mask, z_loss=0.0):
        keep = (mask.shape[-1] + 1) // 2
        import jax.numpy as jnp

        half = mask * (jnp.arange(mask.shape[-1]) < keep)
        return ce(logits, targets, half, z_loss)

    monkeypatch.setattr(L, "cross_entropy", broken)


def _altered_token(monkeypatch):
    from repro.data import pipeline as D

    synth = D.synth_tokens

    def broken(xp, spec, rows_shape, *a, **k):
        toks = synth(xp, spec, rows_shape, *a, **k)
        pos = xp.arange(toks.shape[-1]) == 5
        return xp.where(pos, (toks + 1) % spec.vocab_size, toks)

    monkeypatch.setattr(D, "synth_tokens", broken)


def _altered_answer(monkeypatch):
    from repro.train import population as P

    make = P.make_population_train_step

    def broken(tc, per_trial_batch=False):
        step = make(tc, per_trial_batch)

        def pop_step(pstate, batch, hp):
            new, metrics = step(pstate, batch, hp)
            return dict(new, last_loss=new["last_loss"] * 1.01), metrics

        return pop_step

    monkeypatch.setattr(P, "make_population_train_step", broken)


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "altered_token": _altered_token, "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_timed_path_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = T.run_tiny("sc2-asha-scan")
    assert not out["correct"], (fault, out["checks"])


def test_altered_host_token_is_not_correct(monkeypatch):
    _altered_token(monkeypatch)
    out = T.run_tiny("sc2-random-perstep")
    assert not out["correct"], out["checks"]
