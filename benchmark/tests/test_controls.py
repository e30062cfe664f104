"""``correct`` has to come out false when it should: the float8 control,
and each fault a cell can have, planted under the harness with the look
for a chip skipped.  Rehearsal sizes; the chip readings the limits were
set from are in PERF.md."""

import numpy as np
import pytest

from benchmark.tests import helpers

TRAIN = "train_gpt2m_b8x1024"
SERVE = "serve_gpt2l_closed8_decode"


def over(line):
    return [k for k, row in line["compared"].items() if k != "violations"
            and (row["value"] == "inf" or row["value"] > row["limit"])]


def test_sound_runs_are_correct():
    for cell in (TRAIN, SERVE):
        line = helpers.execute(cell)
        assert line["correct"], line["compared"]
        assert line["attempted"] > 0 and line["failed"] == 0


def test_float8_control_fails_training():
    import time

    from benchmark import run as runner
    from benchmark.lib import compare, spec
    from benchmark.lib.compile_ledger import CompileLedger

    cell = spec.load_cell(TRAIN, rehearse=True)
    driver = spec.driver(cell.traffic["driver"])
    res = driver.run(runner.Context(cell, 5, 0.5, False, time.perf_counter(),
                                    CompileLedger()))
    rows = driver.readings(cell, 5, res, control=True)
    ok, table = compare.verdict(rows["control_fp8"], cell.limits)
    assert not ok, table
    ok, table = compare.verdict(rows["fault_half_batch"], cell.limits)
    assert not ok, table


def test_float8_control_fails_serving():
    import time

    from benchmark import run as runner
    from benchmark.lib import compare, spec
    from benchmark.lib.compile_ledger import CompileLedger

    cell = spec.load_cell(SERVE, rehearse=True)
    ctx = runner.Context(cell, 5, 1.0, False, time.perf_counter(),
                         CompileLedger(), keep_check=True)
    driver = spec.driver(cell.traffic["driver"])
    res = driver.run(ctx)
    ok, table = compare.verdict(res["compared"], cell.limits)
    assert ok and not res["violations"], (table, res["violations"])
    rows = driver.readings(cell, 5, res, control=True)
    ok, table = compare.verdict(rows["control_fp8"], cell.limits)
    assert not ok, table


def test_step_that_returns_its_state_unchanged(monkeypatch):
    from deeplearning4j_tpu.models.lm_fit import CausalLM

    real = CausalLM.fit_backprop

    def unchanged(self, data, **kw):
        before = self.params
        real(self, data, **kw)
        self.params = before

    monkeypatch.setattr(CausalLM, "fit_backprop", unchanged)
    line = helpers.execute(TRAIN)
    assert not line["correct"]
    assert "change_gap" in over(line)
    assert line["compared"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(monkeypatch):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.lm_fit import CausalLM

    real = CausalLM.fit_backprop

    def half(self, data, **kw):
        cut = [DataSet(d.features[:len(d.features) // 2],
                       d.labels[:len(d.labels) // 2]) for d in data]
        real(self, cut, **kw)

    monkeypatch.setattr(CausalLM, "fit_backprop", half)
    line = helpers.execute(TRAIN)
    assert not line["correct"] and over(line), line["compared"]


def test_token_altered_where_it_is_produced(monkeypatch):
    from deeplearning4j_tpu.serving.decode import DecodeRequest

    real = DecodeRequest._push
    count = [0]

    def altered(self, tok):
        count[0] += 1
        real(self, (tok + 1) % 256 if count[0] % 5 == 0 else tok)

    monkeypatch.setattr(DecodeRequest, "_push", altered)
    line = helpers.execute(SERVE, seconds=1.0)
    assert not line["correct"]
    assert over(line) == ["served_gap"]


def test_request_that_comes_back_short(monkeypatch):
    from deeplearning4j_tpu.serving.decode import ContinuousBatcher

    real = ContinuousBatcher.submit

    def short(self, prompt, max_tokens=None, **kw):
        return real(self, prompt, max_tokens=max(1, max_tokens - 1), **kw)

    monkeypatch.setattr(ContinuousBatcher, "submit", short)
    line = helpers.execute(SERVE, seconds=1.0)
    assert not line["correct"] and line["failed"] == line["attempted"]


def test_worst_leaf_measure():
    from benchmark.lib import compare

    ref = {"a": 1.0, "b": 2.0, "c": 1e-6}
    gap, leaf = compare.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 0.0}, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)
    # a leaf that has not moved reads 1; one that moved double reads 1
    assert compare.worst_leaf_gap({"a": 0.0, "b": 2.0, "c": 1e-6},
                                  ref)[0] == pytest.approx(1.0)
    assert compare.worst_leaf_gap({"a": 1.0, "b": 4.0, "c": 1e-6},
                                  ref)[0] == pytest.approx(1.0)
    # a tiny leaf is measured against the median leaf, not itself
    assert compare.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 2e-6},
                                  ref)[0] == pytest.approx(1e-6)
    assert compare.zero_grad_leaves(ref) == ["c"]
    logits = np.array([[0.0, 3.0, 2.5], [1.0, 0.0, 0.2]])
    assert compare.served_gap(logits, np.array([2, 0])) == pytest.approx(0.5)
