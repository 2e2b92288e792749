"""Each cell's run with its timed path broken underneath must come out
not correct: the run skips the look for a chip and otherwise goes as on
the chip, at small sizes, with the cell's own limits. The faults are
``chipbench/faults.py``'s.

Training (one chip): a step that returns its state unchanged; half the
batch left out (the mean over the rest). Serving: a token altered where
it is produced; a serve step (prefill or decode) that returns the cache
unchanged.
"""
import sizes

from chipbench import faults


def test_sound_train_run_is_correct():
    res, _ = sizes.execute("gpt2.train_lp", sizes.TRAIN)
    assert res["correct"], res["checks"]


def test_train_state_unchanged(monkeypatch):
    faults.state_unchanged(monkeypatch.setattr)
    res, _ = sizes.execute("gpt2.train_lp", sizes.TRAIN)
    assert not res["correct"], res["checks"]


def test_train_half_batch(monkeypatch):
    faults.half_batch(monkeypatch.setattr)
    res, _ = sizes.execute("gpt2.train_lp", sizes.TRAIN)
    assert not res["correct"], res["checks"]


def test_sound_serve_run_is_correct():
    res, _ = sizes.execute("qwen3.serve_chat", sizes.SERVE, seconds=3.0)
    assert res["correct"], res["checks"]


def test_serve_token_altered(monkeypatch):
    faults.token_altered(monkeypatch.setattr,
                         sizes.SERVE["config"]["program"]["model"][
                             "vocab_size"])
    res, _ = sizes.execute("qwen3.serve_chat", sizes.SERVE, seconds=3.0)
    assert not res["correct"], res["checks"]


def test_serve_cache_unchanged(monkeypatch):
    faults.cache_unchanged(monkeypatch.setattr)
    res, _ = sizes.execute("qwen3.serve_chat", sizes.SERVE, seconds=3.0)
    assert not res["correct"], res["checks"]
