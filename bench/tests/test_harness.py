"""The harness without a chip, at the configurations' rehearsal sizes.

* A cell, mix, limit and per-layer metric added as files alone, in a
  throw-away benchmark directory, run with no edit to a harness file.
* With the timed path broken underneath, ``correct`` comes out false: a
  train step that returns its state unchanged, one that leaves out half of
  its batch, a verdict altered where it is produced, and a bug site
  reported outside the planted layer.
* The controls, in the program's place, come out not correct: the
  reference computed in float8, and the launcher's four-layer gate.
"""
import dataclasses
import json
import shutil
from pathlib import Path

import pytest

from bench import harness

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def rehearse(name: str, **kw):
    return harness.run_cell(harness.load_cell(name), seed=2**31 + 7, seconds=1,
                            trace=False, rehearse=True, **kw)


def test_a_cell_added_as_files_alone(tmp_path):
    b = tmp_path / "bench"
    for d in ("configs", "traffic", "limits", "metrics"):
        (b / d).mkdir(parents=True)
    (b / "drivers").symlink_to(BENCH / "drivers")
    for ext in (".json", ".py"):
        shutil.copy(BENCH / "configs" / f"mamba2_130m{ext}",
                    b / "configs" / f"tiny_ssm{ext}")
    mix = json.loads((BENCH / "traffic" / "train_4k.json").read_text())
    (b / "traffic" / "tiny_train.json").write_text(json.dumps(mix))
    (b / "limits" / "tiny_ssm.tiny_train.json").write_text(json.dumps(
        {"limits": {"loss_gap": 1.0, "grad_gap": 1.0, "change_gap": 1.0}}))
    (b / "metrics" / "tiny.steps.py").write_text(
        "def read(run):\n    return float(run.data['steps'])\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny_ssm", "file": "bench/configs/tiny_ssm.json"}],
        "workloads": [{"name": "tiny_ssm.tiny_train", "config": "tiny_ssm",
                       "traffic": "tiny_train", "chips": 1}],
        "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "tiny.steps", "unit": "steps",
                       "moves": "train_tokens_per_s"}]}))
    cell = harness.load_cell("tiny_ssm.tiny_train", tmp_path / "BENCHMARK.json")
    line = harness.run_cell(cell, seed=5, seconds=1, trace=True, rehearse=True)
    assert line["correct"] is True
    assert line["metrics"]["tiny.steps"]["value"] == line["attempted"] > 0
    assert list(line)[-1] == "checks"


@pytest.fixture
def broken_step(monkeypatch):
    from repro.train import trainer

    real = trainer.make_step_fn

    def use(fault):
        def make(model, tcfg, shard_flags=None):
            return fault(real(model, tcfg, shard_flags))
        monkeypatch.setattr(trainer, "make_step_fn", make)

    return use


def test_a_step_that_returns_its_state_unchanged(broken_step):
    def fault(step):
        def unchanged(params, opt, batch):
            return (params, opt, step(params, opt, batch)[2])
        return unchanged
    broken_step(fault)
    line = rehearse("mamba2_130m.train_4k")
    assert line["correct"] is False
    assert line["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(broken_step):
    def fault(step):
        def half(params, opt, batch):
            rows = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(params, opt, rows)
        return half
    broken_step(fault)
    assert rehearse("mamba2_130m.train_4k")["correct"] is False


def test_a_verdict_altered_where_it_is_produced(monkeypatch):
    from repro.verify import session

    real = session.Session.verify

    def flipped(self, *a, **kw):
        rep = real(self, *a, **kw)
        rep.verified = not rep.verified
        return rep
    monkeypatch.setattr(session.Session, "verify", flipped)
    line = rehearse("granite_moe_3b.verify_tp4")
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0


def test_a_bug_site_outside_the_planted_layer(monkeypatch):
    from repro.verify import session

    real = session.Session.verify

    def one_more_site(self, *a, **kw):
        rep = real(self, *a, **kw)
        if rep.bug_sites:  # a refuted plan: also blame the graph's first node
            rep.bug_sites.append(dataclasses.replace(rep.bug_sites[0], node=0))
        return rep
    monkeypatch.setattr(session.Session, "verify", one_more_site)
    line = rehearse("granite_moe_3b.verify_tp4")
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] // 2


def test_the_float8_control_is_not_correct():
    from bench import readings
    from bench.drivers import train

    cell = harness.load_cell("mamba2_130m.train_4k")
    run = harness.Run(cell, 11, 0, False, rehearse=True)
    s = train.Setup(run)
    ref = train.reference_readings(s)
    ctrl = train.compare(train.reference_readings(s, mm=readings.fp8_matmul), ref)
    assert any(ctrl[k] > lim for k, lim in cell.limits.items())


def test_the_four_layer_gate_control_is_not_correct():
    from bench.drivers import verify

    cell = harness.load_cell("granite_moe_3b.verify_tp4")
    run = harness.Run(cell, 3, 0, False, rehearse=True)
    gate = verify.plan_of(cell.traffic, cell.traffic["control_layers"])
    plants = verify.schedule(run, 4, cell.traffic["plan"]["layers"])
    wrong = sum(verify.wrong(*verify.ask("granite_moe_3b", gate, p), p)
                for p in plants)
    assert wrong > cell.limits["wrong_answers"]
