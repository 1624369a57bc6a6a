"""The check that decides `correct` fails a run whose timed path is broken
underneath: a token altered where it is produced, a step that returns its
state (the KV cache) unchanged, and half of the batch left out of the
step.  (One chip: no exchange between chips to leave out.)"""
import pytest

from tiny_cell import make_bench, run_harness


def _alter_token(mp):
    from repro.serving import engine

    greedy = engine.greedy
    mp.setattr(engine, "greedy",
               lambda logits: (greedy(logits) + 1) % logits.shape[-1])


def _state_unchanged(mp):
    from repro.serving import engine

    for name in ("decode_step", "mixed_step"):
        step = getattr(engine, name)

        def frozen(cfg, params, cache, *args, _step=step):
            logits, _ = _step(cfg, params, cache, *args)
            return logits, dict(cache)
        mp.setattr(engine, name, frozen)


def _half_batch(mp):
    from repro.serving import engine

    for name in ("decode_step", "mixed_step"):
        step = getattr(engine, name)

        def half(cfg, params, cache, tokens, *args, _step=step):
            # every other slot's row is left out (zeroed)
            return _step(cfg, params, cache, tokens.at[1::2].set(0), *args)
        mp.setattr(engine, name, half)


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _half_batch])
def test_broken_step_is_not_correct(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    rc, result, err = run_harness(make_bench(tmp_path), seed=11)
    assert rc == 0, err
    check = result["checks"]["widest_logit_gap"]
    assert result["correct"] is False, check
    assert check["value"] > check["limit"]
