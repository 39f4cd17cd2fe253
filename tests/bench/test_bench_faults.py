"""A run whose timed path is broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a run
at a size the CPU holds, with one fault planted in the program's timed
path, under the cell's own limits."""
import _tiny_cells
import pytest

from bench.run import execute


def _train(hooks):
    return execute(_tiny_cells.train_cell(), _tiny_cells.env(seconds=1.0, hooks=hooks))


def test_sound_training_run_is_correct():
    out = _train({})
    assert out["correct"], out["compared"]
    assert out["device"]["memory_compiled_bytes"] > 0


def test_step_that_returns_its_state_unchanged_fails():
    def broken(step):
        def f(state, batch):
            _, met = step(state, batch)
            return state, met
        return f

    out = _train({"step_fn": broken})
    assert not out["correct"]
    assert out["compared"]["update_gap"]["value"] > out["compared"]["update_gap"]["limit"]


def test_half_the_batch_left_out_fails():
    def broken(step):
        def f(state, batch):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(state, half)
        return f

    out = _train({"step_fn": broken})
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("mix", [{}, {"arrivals": "poisson", "rate_per_s": 20.0}],
                         ids=["backlog", "open-loop"])
def test_sound_serving_run_is_correct(mix):
    out = execute(_tiny_cells.serve_cell("serve-phi4mini-docs", **mix),
                  _tiny_cells.env(seconds=1.5))
    assert out["correct"], out["compared"]
    assert out["device"]["memory_compiled_bytes"] > 0
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_token_altered_where_it_is_produced_fails():
    def alter(engine):
        decode = engine._decode

        def wrong(*args):
            logits, pool = decode(*args)
            # the served token becomes the one ranked last
            return -logits, pool

        engine._decode = wrong

    out = execute(_tiny_cells.serve_cell("serve-phi4mini-docs"),
                  _tiny_cells.env(seconds=1.5, hooks={"engine": alter}))
    assert not out["correct"], out["compared"]
