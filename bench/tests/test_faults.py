"""A run whose timed path is broken underneath comes out not correct: the
whole harness path of a tiny serving cell on the CPU (no device check), with
one fault planted in the program for the run, once for each fault the cell
can have (one chip has no exchange between chips). The training mixes have
no limits yet, so for them the faults are read on the compared numbers."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests.test_rehearsal import execute


def test_sound_tiny_cell_is_correct(checkout):
    assert execute(checkout, "tiny.serve")["correct"] is True


def _alter_tokens(monkeypatch):
    from repro.runtime.serve_loop import ServeEngine
    orig = ServeEngine._decode_fn

    def bad(self, params, bank, cache, table, tokens, positions, users, live):
        tok, cache = orig(self, params, bank, cache, table, tokens, positions,
                          users, live)
        alter = positions % 3 == 2            # a token now and then
        return jnp.where(alter, (tok + 1) % self.cfg.vocab_size, tok), cache
    monkeypatch.setattr(ServeEngine, "_decode_fn", bad)


def _decode_keeps_state(monkeypatch):
    from repro.runtime.serve_loop import ServeEngine
    orig = ServeEngine._decode_fn

    def bad(self, params, bank, cache, table, tokens, positions, users, live):
        tok, _ = orig(self, params, bank, cache, table, tokens, positions,
                      users, live)
        return tok, cache                     # the KV written is dropped
    monkeypatch.setattr(ServeEngine, "_decode_fn", bad)


def _step_keeps_state(monkeypatch):
    from repro.core.session import ColaSession
    orig = ColaSession.step

    def bad(self, batch):
        keep = jax.tree.map(lambda a: a, self.offloader.adapters)
        keep_state = self.offloader.opt_state
        loss = orig(self, batch)
        self.offloader.adapters, self.offloader.opt_state = keep, keep_state
        self.adapters = self._on_server(keep)
        return loss
    monkeypatch.setattr(ColaSession, "step", bad)


def _half_batch(monkeypatch):
    from repro.core.session import ColaSession
    orig = ColaSession.step

    def bad(self, batch):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return orig(self, half)
    monkeypatch.setattr(ColaSession, "step", bad)


@pytest.mark.parametrize("workload,fault", [
    ("tiny.serve", _alter_tokens), ("tiny.serve", _decode_keeps_state)],
    ids=["serve-token-altered", "serve-state-unchanged"])
def test_fault_makes_the_run_not_correct(checkout, monkeypatch, workload,
                                         fault):
    fault(monkeypatch)
    out = execute(checkout, workload)
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("workload", ["tiny.fused", "tiny.offload"])
def test_training_faults_move_the_numbers(checkout, monkeypatch, workload):
    """The training mixes have no limits yet (PERF.md, Open questions), so
    this reads the numbers: a step that keeps its state reads 1 on the
    change and the first gradient; half of the batch left out moves the
    first gradient's difference far past a sound run's."""
    from bench import control, train
    from bench.tests.test_control import ctx_for
    sound = control.train_readings(ctx_for(checkout, workload, 31), train,
                                   program_only=True)["program"]
    _step_keeps_state(monkeypatch)
    kept = control.train_readings(ctx_for(checkout, workload, 31), train,
                                  program_only=True)["program"]
    monkeypatch.undo()
    _half_batch(monkeypatch)
    half = control.train_readings(ctx_for(checkout, workload, 31), train,
                                  program_only=True)["program"]
    assert kept["change_gap"] > 0.99 and kept["grad_gap"] > 0.99
    assert half["grad_err"] > 10 * sound["grad_err"]
