"""The check that decides ``correct`` must fail a broken timed path.  Each
test drives a whole tiny run on the CPU (the look for a chip skipped) with
the engine broken underneath, and sees ``correct`` come out false: a token
altered where it is sampled (in every row of a dispatch, or in its first
row only), a decode step that returns its K/V state unchanged, a prefill
step that does the same, and the control: the tiny
cell states float32 compute, and the program computing in bf16 (the
nearest precision below it) must fail the comparison.  On the chip the
cells state bf16, and their control is the program's own int8 path
(``arrayflex_w8a8``), measured there (PERF.md)."""
import numpy as np
import pytest

from conftest import run_tiny


def _alter_tokens(engine):
    sample = engine._sample
    engine._sample = lambda logits, temps: (sample(logits, temps) + 1) % 32768


def _alter_one_row(engine):
    sample = engine._sample

    def bad(logits, temps):
        out = np.array(sample(logits, temps))
        out[0] = (out[0] + 1) % 32768
        return out
    engine._sample = bad


def _stale_decode(engine):
    step = engine._decode_paged
    engine._decode_paged = lambda p, c, t, pos, bt: (step(p, c, t, pos, bt)[0],
                                                     c)


def _stale_prefill(engine):
    step = engine._prefill_paged
    engine._prefill_paged = lambda p, c, t, pos, n, bt: (
        step(p, c, t, pos, n, bt)[0], c)


@pytest.mark.parametrize("fault", [_alter_tokens, _alter_one_row,
                                   _stale_decode, _stale_prefill],
                         ids=["token_altered", "one_row_altered",
                              "decode_state_unchanged",
                              "prefill_state_unchanged"])
def test_a_broken_timed_path_is_not_correct(tiny_home, fault):
    home, bench = tiny_home
    r = run_tiny(home, bench, seed=77, fault=fault)
    assert r["correct"] is False
    c = r["compared"]
    assert c["logit_gap_mean"]["value"] > c["logit_gap_mean"]["limit"]


def test_the_lower_precision_control_is_not_correct(tiny_home):
    home, bench = tiny_home
    r = run_tiny(home, bench, seed=78,
                 overrides={"compute_dtype": "bfloat16"})
    assert r["correct"] is False
    c = r["compared"]
    assert c["logit_gap_mean"]["value"] > c["logit_gap_mean"]["limit"]
