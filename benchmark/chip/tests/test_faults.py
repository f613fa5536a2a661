"""The comparison that decides ``correct`` has to fail what it is there
to catch.  Run by hand (not part of ``tests/``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/chip/tests -q

Each test drives a whole run of a rehearsal cell through ``run.run_cell``
(everything but the look for a chip), once sound and once with the timed
path broken underneath: a step that returns its state unchanged, half of
the batch left out with the mean taken over the rest, a token altered
where it is produced.  The lower-precision controls at this size are in
``test_controls.py``; at the cells' own size they run on the chip through
``readings.py`` (PERF.md has the readings).
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402


def _run(cell, seed=11, **kw):
    result, compared = run.run_cell(cell, seed, 2.0, rehearsal=True, **kw)
    return result, compared


def test_sound_training_run_is_correct():
    result, compared = _run("tiny_train")
    assert result["correct"], compared
    assert result["attempted"] > 0 and result["failed"] == 0


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from mxnet_tpu import parallel
    real = parallel.TrainStep.__call__

    def stuck(self, *batch):
        import jax.numpy as jnp
        keep = {n: jnp.copy(p._data._data) for n, p in self._params}
        states = {n: tuple(jnp.copy(a) for a in s)
                  for n, s in self._states.items()}
        loss = real(self, *batch)
        for n, p in self._params:
            p._data._data = keep[n]
        self._states = states
        return loss

    monkeypatch.setattr(parallel.TrainStep, "__call__", stuck)
    result, compared = _run("tiny_train")
    assert not result["correct"]
    assert not compared["update_norm_gap.median"]["ok"]
    assert compared["update_norm_gap.median"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    from mxnet_tpu import parallel
    real = parallel.TrainStep.__call__

    def half(self, x, y):
        n = x.shape[0] // 2
        return real(self, x[:n], y[:n])

    monkeypatch.setattr(parallel.TrainStep, "__call__", half)
    result, compared = _run("tiny_train")
    assert not result["correct"], compared


def test_sound_serving_runs_are_correct():
    for cell in ("tiny_backlog", "tiny_open"):
        result, compared = _run(cell)
        assert result["correct"], compared
        assert result["attempted"] > 0 and result["failed"] == 0


def test_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from mxnet_tpu import serve
    real = serve.WarmPool.run_decode

    def altered(self, page_table, lengths, tokens, active, **kw):
        out = real(self, page_table, lengths, tokens, active, **kw)
        return (out + 1) % self.cfg.vocab_size

    monkeypatch.setattr(serve.WarmPool, "run_decode", altered)
    result, compared = _run("tiny_backlog")
    assert not result["correct"]
    assert not compared["served_logit_gap"]["ok"]


def test_traced_run_means_the_same(monkeypatch):
    result, compared = _run("tiny_backlog", trace=True)
    assert result["correct"], compared
    # on the CPU there is no device plane: no device metric is reported
    assert "device_idle_pct.serve" not in result["metrics"]
    assert "busy_s" not in result["device"] or \
        result["device"]["busy_s"] is None
