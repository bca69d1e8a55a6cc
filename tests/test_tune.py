"""Tune tests (reference pattern: ``python/ray/tune/tests/`` — synthetic
trainables, scheduler unit tests with deterministic result streams)."""

import os

import numpy as np
import pytest

import ray_tpu
from conftest import time_scale
from ray_tpu import tune
from ray_tpu.train import RunConfig
from ray_tpu.tune import (ASHAScheduler, PopulationBasedTraining, Trainable,
                          TuneConfig, Tuner)


def test_grid_search_runs_all(ray_start_regular, tmp_path):
    def f(config):
        tune.report({"score": config["a"] * 10 + config["b"]})

    results = Tuner(
        f,
        param_space={"a": tune.grid_search([1, 2, 3]),
                     "b": tune.grid_search([0, 1])},
        tune_config=TuneConfig(metric="score", mode="max"),
        run_config=RunConfig(storage_path=str(tmp_path)),
    ).fit()
    assert len(results) == 6
    best = results.get_best_result("score", "max")
    assert best.metrics["score"] == 31
    assert best.metrics["config"] == {"a": 3, "b": 1}


def test_random_sampling_domains(ray_start_regular, tmp_path):
    def f(config):
        assert 0.0 <= config["lr"] <= 1.0
        assert config["wd"] in (0.1, 0.2)
        assert isinstance(config["n"], int)
        tune.report({"ok": 1})

    results = Tuner(
        f,
        param_space={"lr": tune.uniform(0, 1),
                     "wd": tune.choice([0.1, 0.2]),
                     "n": tune.randint(1, 10)},
        tune_config=TuneConfig(num_samples=5, seed=0),
        run_config=RunConfig(storage_path=str(tmp_path)),
    ).fit()
    assert len(results) == 5
    assert not results.errors


def test_multiple_reports_stream(ray_start_regular, tmp_path):
    def f(config):
        for i in range(4):
            tune.report({"loss": 10 - i})

    results = Tuner(
        f, param_space={},
        tune_config=TuneConfig(metric="loss", mode="min"),
        run_config=RunConfig(storage_path=str(tmp_path)),
    ).fit()
    assert len(results[0].metrics_history) == 4
    assert results[0].metrics["loss"] == 7


def test_trial_error_captured(ray_start_regular, tmp_path):
    def f(config):
        if config["x"] == 1:
            raise ValueError("boom")
        tune.report({"ok": 1})

    results = Tuner(
        f, param_space={"x": tune.grid_search([0, 1])},
        run_config=RunConfig(storage_path=str(tmp_path)),
    ).fit()
    assert len(results.errors) == 1


def test_asha_unit_decisions():
    """Scheduler unit test with a synthetic result stream (reference
    pattern: tune/tests/test_trial_scheduler.py).  ASHA is asynchronous:
    a trial reaching a rung late, below the top-1/rf of recorded values,
    is stopped; early arrivals survive."""
    from ray_tpu.tune.trial import Trial

    sched = ASHAScheduler(metric="score", mode="max", max_t=100,
                          grace_period=4, reduction_factor=2)
    good = [Trial(f"good{i}", {}) for i in range(3)]
    bad = Trial("bad", {})
    # three good trials record rung-4 values first
    for i, t in enumerate(good):
        assert sched.on_trial_result(
            None, t, {"training_iteration": 4,
                      "score": 100 + i}) == sched.CONTINUE
    # the straggler is below the top half at rung 4 → stopped
    assert sched.on_trial_result(
        None, bad, {"training_iteration": 4, "score": 1}) == sched.STOP
    # a new trial above the cutoff continues
    best = Trial("best", {})
    assert sched.on_trial_result(
        None, best, {"training_iteration": 4, "score": 200}) == sched.CONTINUE
    # max_t always stops
    assert sched.on_trial_result(
        None, best, {"training_iteration": 100, "score": 999}) == sched.STOP


def test_asha_integration_stops_straggler(ray_start_regular, tmp_path):
    """Integration: good trials launch first (fill the rungs), then a poor
    trial starts late and must be cut before max_t."""
    def f(config):
        import time
        if config["q"] == 0:      # the poor straggler starts slow
            time.sleep(0.5)
        for i in range(15):
            tune.report({"score": config["q"] * 100 + i})

    results = Tuner(
        f, param_space={"q": tune.grid_search([3, 2, 1, 0])},
        tune_config=TuneConfig(
            metric="score", mode="max", max_concurrent_trials=4,
            scheduler=ASHAScheduler(max_t=15, grace_period=2,
                                    reduction_factor=2)),
        run_config=RunConfig(storage_path=str(tmp_path)),
    ).fit()
    iters = {r.metrics["config"]["q"]: len(r.metrics_history)
             for r in results}
    assert iters[3] == 15       # best runs to completion
    assert iters[0] < 15        # straggler cut at a rung


def test_stop_criteria(ray_start_regular, tmp_path):
    def f(config):
        for i in range(100):
            tune.report({"v": i})

    results = tune.run(f, config={}, stop={"training_iteration": 5},
                       storage_path=str(tmp_path))
    assert len(results[0].metrics_history) <= 8  # stop is cooperative


def test_class_trainable_with_checkpointing(ray_start_regular, tmp_path):
    class MyTrainable(Trainable):
        def setup(self, config):
            self.base = config.get("base", 0)

        def step(self):
            return {"val": self.base + self.iteration}

        def save_checkpoint(self, d):
            with open(os.path.join(d, "s.txt"), "w") as fh:
                fh.write(str(self.iteration))

        def load_checkpoint(self, d):
            with open(os.path.join(d, "s.txt")) as fh:
                self.iteration = int(fh.read())

    results = tune.run(MyTrainable, config={"base": 100},
                       stop={"training_iteration": 3},
                       storage_path=str(tmp_path))
    r = results[0]
    assert r.error is None
    assert r.metrics["val"] >= 103
    assert r.checkpoint is not None


def _climber(tmp_path):
    """A trial that adds its rate to its score 12 times, from its
    checkpoint on.  Of a population of two the slow one (rate 1) is
    launched first, and a perturbation with nobody to clone from passes: on
    a loaded host it ran its 12 steps before the fast one's worker was up,
    and no clone happened.  So it waits for what a clone needs, the fast
    trial's first report (``report`` returns once the controller has the
    score and the checkpoint), with a deadline of its own."""
    reported = tmp_path / "fast_trial_reported"
    patience = 120 * time_scale()

    def f(config):
        import time
        start = 0
        ck = tune.get_checkpoint()
        if ck is not None:
            start = ck.to_dict()["score"]
        deadline = time.monotonic() + patience
        while config["rate"] == 1 and not reported.exists() \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        score = start
        for i in range(12):
            score += config["rate"]          # higher rate = better trial
            tune.report({"score": score},
                        checkpoint=tune.Checkpoint.from_dict(
                            {"score": score}))
            if config["rate"] != 1:
                reported.touch()
    return f


def test_pbt_clones_from_better_trial(ray_start_regular, tmp_path):
    # two trials: "slow" (rate 1) and "fast" (rate 10); PBT should stop the
    # slow one at the perturbation interval and clone from the fast one
    f = _climber(tmp_path)
    results = Tuner(
        f, param_space={"rate": tune.grid_search([1, 10])},
        tune_config=TuneConfig(
            metric="score", mode="max", max_concurrent_trials=2,
            scheduler=PopulationBasedTraining(
                perturbation_interval=4,
                hyperparam_mutations={"rate": [1, 10]}, seed=0)),
        run_config=RunConfig(storage_path=str(tmp_path)),
    ).fit()
    best = results.get_best_result("score", "max")
    assert best.metrics["score"] >= 100
    # the cloned trial must have benefited from the donor's checkpoint
    worst = min(r.metrics["score"] for r in results)
    assert worst > 12  # pure rate-1 for 12 steps would be exactly 12


def test_tuner_restore(ray_start_regular, tmp_path):
    def f(config):
        tune.report({"m": config["x"]})

    Tuner(
        f, param_space={"x": tune.grid_search([5, 7])},
        run_config=RunConfig(storage_path=str(tmp_path), name="exp1"),
    ).fit()
    restored = Tuner.restore(str(tmp_path / "exp1"))
    grid = restored.get_results()
    assert sorted(r.metrics["m"] for r in grid) == [5, 7]


def test_tuner_wraps_trainer(ray_start_regular, tmp_path):
    from ray_tpu import train
    from ray_tpu.train import DataParallelTrainer, ScalingConfig

    def loop(config):
        train.report({"loss": 1.0 / config["lr"]})

    trainer = DataParallelTrainer(
        loop, train_loop_config={"lr": 1.0},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=str(tmp_path)))
    results = Tuner(
        trainer,
        param_space={"train_loop_config": {
            "lr": tune.grid_search([1.0, 2.0])}},
        tune_config=TuneConfig(metric="loss", mode="min",
                               max_concurrent_trials=1),
        run_config=RunConfig(storage_path=str(tmp_path)),
    ).fit()
    assert results.get_best_result("loss", "min").metrics["loss"] == 0.5


def test_pb2_bandit_explore_clones_and_improves(ray_start_regular, tmp_path):
    """PB2 (tune/schedulers/pb2.py): bottom trial exploits the donor's
    checkpoint and the GP-UCB bandit proposes the new hyperparameter
    INSIDE the declared bounds; with enough windows the bandit's dataset
    is populated and the population improves over its worst member."""
    from ray_tpu.tune import PB2

    f = _climber(tmp_path)
    sched = PB2(perturbation_interval=3,
                hyperparam_bounds={"rate": [0.5, 10.0]}, seed=0)
    results = Tuner(
        f, param_space={"rate": tune.grid_search([1.0, 9.0])},
        tune_config=TuneConfig(metric="score", mode="max",
                               max_concurrent_trials=2, scheduler=sched),
        run_config=RunConfig(storage_path=str(tmp_path)),
    ).fit()
    best = results.get_best_result("score", "max")
    assert best.metrics["score"] >= 9 * 12 * 0.8
    # the clone escaped the pure rate-1 trajectory
    assert min(r.metrics["score"] for r in results) > 12
    # bandit recorded reward windows and every proposal stayed in bounds
    assert len(sched._data_y) >= 2
    for r in results:
        assert 0.5 <= r.metrics["config"]["rate"] <= 10.0


def test_pb2_requires_bounds():
    from ray_tpu.tune import PB2
    import pytest as _pytest
    with _pytest.raises(ValueError):
        PB2(hyperparam_bounds=None)
