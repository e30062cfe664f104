"""A stall inside the window shows in what a user would see: the rate
is taken over all the time of the window, and the tails are the tails of
all requests.  Rehearsal sizes; the stalls are planted in the program."""

import time

from benchmark.lib import spec
from benchmark.tests import helpers

TRAIN = "train_gpt2m_b8x1024"
SERVE = "serve_gpt2l_closed8_decode"


def value(line, name):
    return line["metrics"][name]["value"]


def test_a_stall_between_steps_moves_train_tok_s(monkeypatch):
    from deeplearning4j_tpu.models.lm_fit import CausalLM

    sound = helpers.execute(TRAIN, seconds=1.5)
    real = CausalLM.fit_backprop

    def stalled(self, data, **kw):
        time.sleep(0.25)
        real(self, data, **kw)

    monkeypatch.setattr(CausalLM, "fit_backprop", stalled)
    slow = helpers.execute(TRAIN, seconds=1.5)
    assert slow["correct"] and slow["attempted"] >= 2
    # a call now takes a quarter of a second and more
    tr = spec.load_cell(TRAIN, rehearse=True).traffic
    assert value(slow, "train_tok_s") < (tr["steps_per_call"] * tr["rows"]
                                         * tr["seq_len"] / 0.25)
    assert value(slow, "train_tok_s") < 0.6 * value(sound, "train_tok_s")
    # the stall before the window is set-up, and shows there
    assert value(slow, "setup_s") > 0.75


def test_a_stall_in_the_decode_round_moves_the_tails(monkeypatch):
    from deeplearning4j_tpu.serving.decode import DecodeEngine

    sound = helpers.execute(SERVE, seconds=1.5)
    sound_tails = helpers.execute(SERVE, seconds=1.5, trace=True)
    real = DecodeEngine.advance

    def stalled(self, bucket):
        time.sleep(0.03)
        return real(self, bucket)

    monkeypatch.setattr(DecodeEngine, "advance", stalled)
    slow = helpers.execute(SERVE, seconds=1.5)
    assert slow["correct"], slow["compared"]
    assert value(slow, "serve_tok_s") < 0.6 * value(sound, "serve_tok_s")
    # the callers' tails are read in the traced run
    tails = helpers.execute(SERVE, seconds=1.5, trace=True)
    assert value(tails, "itl_p99_ms") >= 30.0 > value(sound_tails,
                                                        "itl_p99_ms")
    assert value(tails, "ttft_p90_ms") > value(sound_tails, "ttft_p90_ms")
