"""The check that decides ``correct`` has teeth.

Each test breaks the timed path underneath a whole run (at the program's
scaled-down size, on the CPU, past the harness's look for a chip) in one
way a served cell can go wrong, and sees ``correct`` come out false; the
last puts the fp8 control in the program's place on the same rows and
sees the harness's own ``judge`` find it not correct.
"""

import numpy as np
import pytest

import chip_paths  # noqa: F401  (before the modules below)
import run
from smallrun import small_run


def _decode_state_unchanged(monkeypatch):
    """A decode step returns its cache as it found it."""
    import jax
    from repro.serve.batcher import ServingEngine
    orig = ServingEngine.decode

    def decode(self, tok, caches, lens):
        saved = jax.tree.map(lambda x: x.copy(), caches)
        next_tok, _, wall = orig(self, tok, caches, lens)
        return next_tok, saved, wall

    monkeypatch.setattr(ServingEngine, "decode", decode)


def _half_batch_left_out(monkeypatch):
    """Only the first half of the slots is computed; the other half gets
    the first half's tokens."""
    from repro.serve.batcher import ServingEngine
    orig = ServingEngine.decode

    def decode(self, tok, caches, lens):
        next_tok, caches, wall = orig(self, tok, caches, lens)
        next_tok = np.array(next_tok)
        half = next_tok.shape[0] // 2
        next_tok[half:] = next_tok[:half]
        return next_tok, caches, wall

    monkeypatch.setattr(ServingEngine, "decode", decode)


def _token_altered(monkeypatch):
    """Slot 0's token is changed where the decode step produces it."""
    from repro.serve.batcher import ServingEngine
    orig = ServingEngine.decode

    def decode(self, tok, caches, lens):
        next_tok, caches, wall = orig(self, tok, caches, lens)
        next_tok = np.array(next_tok)
        next_tok[0] = (next_tok[0] + 1) % self.cfg.vocab_size
        return next_tok, caches, wall

    monkeypatch.setattr(ServingEngine, "decode", decode)


#: fault -> (cell, plant).  The cells run on one chip, so no exchange
#: between chips can be left out.
FAULTS = {
    "state_unchanged": ("chatglm3-6b.chat-decode", _decode_state_unchanged),
    "half_batch": ("chatglm3-6b.chat-decode", _half_batch_left_out),
    "token_altered": ("chatglm3-6b.code-completion", _token_altered),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    cell, plant = FAULTS[fault]
    plant(monkeypatch)
    res = small_run(cell, requests=8)
    gap = res["checks"]["logit_gap"]
    assert res["correct"] is False
    assert gap["value"] > gap["limit"]


def test_the_fp8_control_in_the_programs_place_is_not_correct():
    res = small_run("chatglm3-6b.chat-decode", requests=8, control=True)
    assert res["correct"] is True
    ctl = res["control"]
    gap = ctl["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    assert ctl["correct"] is False
    assert run.judge(ctl["checks"]) is False
