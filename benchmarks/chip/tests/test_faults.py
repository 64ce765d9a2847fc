"""The check that decides ``correct`` has teeth.

Each test breaks the timed path underneath a whole run (at the program's
scaled-down size, on the CPU, past the harness's look for a chip) in one
way a served cell can go wrong, and sees ``correct`` come out false; the
four-chip cell's exchange between chips is left out on four virtual CPU
devices, in a process of its own (``exchange.py``); the last puts the fp8
control in the program's place on the same rows and sees the harness's
own ``judge`` find it not correct.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

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


#: fault -> (cell, plant), on one device.
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


def test_the_exchange_between_chips_left_out_is_not_correct():
    here = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(here.parents[2] / "src"))
    res = subprocess.run([sys.executable, str(here / "exchange.py")],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"] is True
    gap = out["broken"]["checks"]["logit_gap"]
    assert out["broken"]["correct"] is False
    assert gap["value"] > gap["limit"]


def test_the_fp8_control_in_the_programs_place_is_not_correct():
    res = small_run("chatglm3-6b.chat-decode", requests=8, control=True)
    assert res["correct"] is True
    ctl = res["control"]
    gap = ctl["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    assert ctl["correct"] is False
    assert run.judge(ctl["checks"]) is False
