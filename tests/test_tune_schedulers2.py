"""Round-4 scheduler breadth: synchronous HyperBand (barrier cuts + PAUSE),
PB2 (GP-bandit explore within bounds), PBT replay (recorded policy applied
to one trial). Reference: tune/schedulers/hyperband.py:42, pb2.py,
pbt.py:1035."""
import json
import os
import tempfile

import pytest


def test_sync_hyperband_cuts_at_barrier(ray_start, tmp_path):
    from ray_tpu import tune

    base = str(tmp_path)

    def trainable(config):
        # Every report carries a checkpoint, so a trial that reaches the
        # band's milestone first is PARKED there until its peers arrive and
        # all are judged at the same budget, however late a peer's worker
        # started. A trial without one free-runs past the milestone (the
        # scheduler's fallback) and is then judged at whatever iteration it
        # has reached: with ``acc`` growing by the iteration a trial that
        # started a quarter of a second early outranked a better one (two
        # runs in ten beside five busy workers: ``assert 1.0 == 2.0``).
        import json
        import os
        import time

        from ray_tpu.train import Checkpoint

        start = 0
        ckpt = tune.get_checkpoint()
        if ckpt:
            with open(os.path.join(ckpt.path, "i.json")) as f:
                start = json.load(f)["i"]
        for i in range(start, 16):
            d = os.path.join(base, f"q{config['q']}-{i}")
            os.makedirs(d, exist_ok=True)
            with open(os.path.join(d, "i.json"), "w") as f:
                json.dump({"i": i + 1}, f)
            tune.report({"acc": config["q"] * (i + 1),
                         "training_iteration": i + 1},
                        checkpoint=Checkpoint.from_directory(d))
            time.sleep(0.05)

    results = tune.Tuner(
        trainable,
        param_space={"q": tune.grid_search([0.1, 0.2, 1.0, 2.0])},
        tune_config=tune.TuneConfig(
            metric="acc", mode="max",
            scheduler=tune.HyperBandScheduler(
                grace_period=2, reduction_factor=2, max_t=16),
            max_concurrent_trials=4,
        ),
        run_config=tune.TuneRunConfig(storage_path=tempfile.mkdtemp()),
    ).fit()
    assert not results.errors
    assert results.get_best_result().config["q"] == 2.0
    iters = sorted(r.metrics.get("training_iteration", 0) for r in results)
    # the band cut half the population at an early milestone; winners ran on
    assert iters[0] < 16 and iters[-1] >= 16
    # successive halving: at most half survive each cut
    assert sum(1 for i in iters if i >= 16) <= 2


@pytest.mark.xfail(strict=True, reason=(
    "ROADMAP D10: HyperBandScheduler.on_trial_result never parks a trial "
    "that has no checkpoint, and after a cut takes its NEXT report past the "
    "new milestone as its score there, at a later iteration than its "
    "peers': not the same-budget comparison its docstring promises"))
def test_sync_hyperband_judges_a_free_running_trial_at_its_peers_budget(
        ray_start, tmp_path):
    """The trials report NO checkpoint, so none can be parked at the
    barrier. ``q=1.0`` runs ahead (30 iterations before ``q=2.0`` makes its
    second), ``q=2.0`` is better at every budget and has to win. The order
    is held by files, not by sleeps alone: left to the workers' starts the
    same fault showed in two runs of ten beside five busy workers (the
    checkpointing test above is the steadied one)."""
    import time

    from ray_tpu import tune

    base = str(tmp_path)

    def trainable(config):
        import os
        import time

        def wait(name, then):
            deadline = time.monotonic() + 120
            while (not os.path.exists(os.path.join(base, name))
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            time.sleep(then)  # the controller polls what was reported

        def say(name):
            open(os.path.join(base, name), "w").close()

        q = config["q"]
        for i in range(1, 65):
            if q != 1.0 and i == 2:
                wait("ahead", 2.0)
            if q == 2.0 and i == 4:
                say("judged")
            tune.report({"acc": q * i})
            if q == 1.0 and i == 30:
                say("ahead")
            if q == 1.0 and i == 40:
                wait("judged", 1.0)
            # past its fourth report ``q=2.0`` only waits for the verdict
            time.sleep(0.3 if q == 2.0 and i >= 4 else 0.01)

    results = tune.Tuner(
        trainable,
        param_space={"q": tune.grid_search([0.1, 1.0, 2.0])},
        tune_config=tune.TuneConfig(
            metric="acc", mode="max",
            scheduler=tune.HyperBandScheduler(
                grace_period=2, reduction_factor=2, max_t=64),
            max_concurrent_trials=3,
        ),
        run_config=tune.TuneRunConfig(storage_path=tempfile.mkdtemp()),
    ).fit()
    assert not results.errors
    # at milestone 4 ``q=2.0`` holds 8.0 and ``q=1.0`` held 4.0
    assert results.get_best_result().config["q"] == 2.0


def test_sync_hyperband_unit_barrier_semantics():
    """Pure-scheduler check: the first trial to reach the milestone is
    PAUSED (not judged alone), and the cut happens only when the last
    peer arrives."""
    from ray_tpu.tune.schedulers import (
        CONTINUE, PAUSE, STOP, HyperBandScheduler,
    )
    from ray_tpu.tune.trial import Trial

    sched = HyperBandScheduler(grace_period=4, reduction_factor=2, max_t=64)
    sched.set_search_properties("score", "max")
    good = Trial(config={}, experiment_dir="/tmp", trial_id="good")
    bad = Trial(config={}, experiment_dir="/tmp", trial_id="bad")
    # pausing requires something to resume from; un-checkpointed trials
    # are kept running instead (covered below via `nockpt`)
    good.checkpoint_path = "/tmp/ckpt-good"
    bad.checkpoint_path = "/tmp/ckpt-bad"
    # both below the milestone: free to run
    assert sched.on_trial_result(good, {"training_iteration": 1, "score": 9}) == CONTINUE
    assert sched.on_trial_result(bad, {"training_iteration": 1, "score": 1}) == CONTINUE
    # good reaches the milestone first -> parked, NOT judged
    assert sched.on_trial_result(good, {"training_iteration": 4, "score": 9}) == PAUSE
    assert sched.pending_actions() == {}
    # bad arrives -> barrier complete -> cut: bad (the arriver) is stopped
    assert sched.on_trial_result(bad, {"training_iteration": 4, "score": 1}) == STOP
    # good's verdict is delivered through pending_actions
    assert sched.pending_actions() == {"good": "RESUME"}
    # next milestone doubled
    assert sched.milestone == 8.0
    # a trial with NO checkpoint is never paused (a pause would restart it
    # from scratch); it keeps running with its milestone score frozen
    nockpt = Trial(config={}, experiment_dir="/tmp", trial_id="nockpt")
    sched.on_trial_add(nockpt)
    assert sched.on_trial_result(
        nockpt, {"training_iteration": 8, "score": 5}) == CONTINUE
    assert "nockpt" in sched._scores


def test_sync_hyperband_retires_dead_trials_from_ranking():
    """A trial that hits max_t (or completes) must not keep occupying a
    keep slot at later barrier cuts with its stale milestone score."""
    from ray_tpu.tune.schedulers import (
        CONTINUE, STOP, HyperBandScheduler,
    )
    from ray_tpu.tune.trial import Trial

    sched = HyperBandScheduler(grace_period=4, reduction_factor=2, max_t=8)
    sched.set_search_properties("score", "max")
    trials = {}
    for tid in ("champ", "a", "b"):
        t = Trial(config={}, experiment_dir="/tmp", trial_id=tid)
        t.checkpoint_path = f"/tmp/ckpt-{tid}"
        trials[tid] = t
        sched.on_trial_add(t)
    # champ posts the top score at the milestone, then hits max_t: retired
    sched.on_trial_result(trials["champ"], {"training_iteration": 4, "score": 99})
    assert sched.on_trial_result(
        trials["champ"], {"training_iteration": 8, "score": 99}) == STOP
    assert "champ" not in sched._scores
    # the cut over the two LIVE trials keeps ceil(2/2)=1: `a` must win a
    # keep slot — with champ's stale 99 still ranked, `a` would be cut
    sched.on_trial_result(trials["a"], {"training_iteration": 4, "score": 5})
    verdict_b = sched.on_trial_result(
        trials["b"], {"training_iteration": 4, "score": 1})
    assert verdict_b == STOP
    actions = sched.pending_actions()
    assert actions.get("a") == "RESUME", actions


def test_pb2_explores_within_bounds_and_learns(ray_start):
    from ray_tpu import tune

    def trainable(config):
        import time

        # score improves with lr up to the ceiling — PB2's GP should
        # concentrate exploit-explore steps toward high lr
        for i in range(12):
            tune.report({"acc": config["lr"] * (i + 1)})
            time.sleep(0.05)

    results = tune.Tuner(
        trainable,
        param_space={"lr": tune.uniform(0.0, 0.2)},
        tune_config=tune.TuneConfig(
            metric="acc", mode="max", num_samples=4,
            scheduler=tune.PB2(
                perturbation_interval=3,
                hyperparam_bounds={"lr": [0.0, 1.0]},
                quantile_fraction=0.5, seed=0,
            ),
            max_concurrent_trials=4,
        ),
        run_config=tune.TuneRunConfig(storage_path=tempfile.mkdtemp()),
    ).fit()
    assert not results.errors
    # every explored lr stayed inside the declared bounds
    for r in results:
        assert 0.0 <= r.config["lr"] <= 1.0


def test_pb2_gp_explore_prefers_improving_region():
    """Unit test of the GP-UCB explore: feed observations where high x
    yields high improvement; suggestions must move toward high x."""
    from ray_tpu.tune.schedulers import PB2

    sched = PB2(hyperparam_bounds={"x": [0.0, 1.0]}, seed=3,
                n_candidates=128)
    sched.set_search_properties("score", "max")
    # improvement grows with x
    for v in (0.1, 0.3, 0.5, 0.7, 0.9):
        sched._obs_x.append([v])
        sched._obs_y.append(v * 10.0)
    picks = [sched._explore({"x": 0.5})["x"] for _ in range(5)]
    assert sum(p > 0.6 for p in picks) >= 4, picks


def test_pbt_writes_policy_log_and_replay_applies_it(ray_start, tmp_path):
    from ray_tpu import tune
    from ray_tpu.tune.schedulers import (
        PopulationBasedTraining, PopulationBasedTrainingReplay,
    )
    from ray_tpu.tune.trial import Trial

    # Phase 1: run PBT with a policy log directory. Exploit needs a donor
    # CHECKPOINT, and the checkpoint must carry the accumulated score —
    # otherwise an exploited trial restarts from zero, stays in the bottom
    # quantile forever, and exploits in an endless loop.
    def trainable(config):
        import tempfile as _tf
        import time

        from ray_tpu.train import Checkpoint

        total = 0.0
        ckpt = tune.get_checkpoint()
        if ckpt:
            with open(os.path.join(ckpt.path, "s.json")) as f:
                total = json.load(f)["total"]
        for _ in range(12):
            total += config["lr"]
            d = _tf.mkdtemp()
            with open(os.path.join(d, "s.json"), "w") as f:
                json.dump({"total": total}, f)
            tune.report({"acc": total},
                        checkpoint=Checkpoint.from_directory(d))
            time.sleep(0.05)

    log_dir = str(tmp_path / "policy")
    results = tune.Tuner(
        trainable,
        param_space={"lr": tune.grid_search([0.01, 1.0])},
        tune_config=tune.TuneConfig(
            metric="acc", mode="max",
            scheduler=PopulationBasedTraining(
                perturbation_interval=3, quantile_fraction=0.5,
                hyperparam_mutations={"lr": {"lower": 0.001, "upper": 2.0}},
                seed=1, policy_log_dir=log_dir,
            ),
            max_concurrent_trials=2,
        ),
        run_config=tune.TuneRunConfig(storage_path=tempfile.mkdtemp()),
    ).fit()
    assert not results.errors
    logs = os.listdir(log_dir)
    assert logs, "PBT exploited at least once but wrote no policy log"
    log_path = os.path.join(log_dir, logs[0])
    records = [json.loads(l) for l in open(log_path) if l.strip()]
    assert all("t" in r and "config" in r for r in records)

    # Phase 2: replay the recorded schedule on a fresh trial (pure-scheduler
    # unit: config switches land at the recorded times, from own lineage)
    replay = PopulationBasedTrainingReplay(log_path)
    trial = Trial(config={"lr": 0.5}, experiment_dir="/tmp", trial_id="rp")
    trial.checkpoint_path = "/tmp/ckpt-own"
    switch_t = records[0]["t"]
    assert replay.on_trial_result(
        trial, {"training_iteration": switch_t - 1}) == "CONTINUE"
    decision = replay.on_trial_result(
        trial, {"training_iteration": switch_t})
    assert decision == PopulationBasedTraining.EXPLOIT
    assert trial.config == records[0]["config"]
    assert trial.restore_path == "/tmp/ckpt-own"  # own lineage, not a donor


def test_bohb_unit_budget_pools():
    """TuneBOHB fits its model on the LARGEST budget with >= n_startup
    observations; HyperBandForBOHB feeds it at each barrier crossing."""
    from ray_tpu.tune.schedulers import HyperBandForBOHB
    from ray_tpu.tune.search import TuneBOHB
    from ray_tpu.tune.trial import Trial
    from ray_tpu import tune

    searcher = TuneBOHB({"x": tune.uniform(0.0, 1.0)},
                        metric="acc", mode="max", n_startup=3, seed=0)
    sched = HyperBandForBOHB(grace_period=2, reduction_factor=2, max_t=8,
                             searcher=searcher)
    sched.set_search_properties("acc", "max")  # the controller's job
    import tempfile

    exp_dir = tempfile.mkdtemp()
    trials = []
    for i in range(4):
        cfg = searcher.suggest(f"t{i}")
        tr = Trial(cfg, exp_dir, trial_id=f"t{i}")
        trials.append(tr)
        sched.on_trial_add(tr)
    # all four report at the milestone: scores proportional to x
    for tr in trials:
        tr.iteration = 2
        sched.on_trial_result(tr, {"training_iteration": 2,
                                   "acc": tr.config["x"]})
    pool = searcher._budget_obs.get(2.0)
    assert pool is not None and len(pool) == 4
    # with 4 >= n_startup obs at budget 2, suggestions are model-based:
    # drawn from the good (high-x) region far more often than uniform
    xs = [searcher.suggest(f"m{i}")["x"] for i in range(8)]
    best_x = max(tr.config["x"] for tr in trials)
    assert sum(1 for x in xs if x > 0.5 * best_x) >= 5, xs


def test_bohb_end_to_end(ray_start):
    """Full Tuner run: HyperBandForBOHB + TuneBOHB converge on the good
    region of a deterministic objective (reference: BOHB example)."""
    import tempfile

    from ray_tpu import tune

    def trainable(config):
        for i in range(8):
            tune.report({"acc": (1.0 - abs(config["x"] - 0.7)) * (i + 1)})

    searcher = tune.TuneBOHB({"x": tune.uniform(0.0, 1.0)},
                             metric="acc", mode="max", n_startup=4,
                             max_trials=10, seed=1)
    results = tune.Tuner(
        trainable,
        tune_config=tune.TuneConfig(
            metric="acc", mode="max",
            search_alg=searcher,
            scheduler=tune.HyperBandForBOHB(
                grace_period=2, reduction_factor=2, max_t=8,
                searcher=searcher),
            max_concurrent_trials=5,
        ),
        run_config=tune.TuneRunConfig(storage_path=tempfile.mkdtemp()),
    ).fit()
    assert not results.errors
    best = results.get_best_result()
    assert abs(best.config["x"] - 0.7) < 0.35
    # milestone pools were fed by the scheduler
    assert any(len(v) >= 4 for v in searcher._budget_obs.values())


def test_bayesopt_searcher_concentrates():
    """GP-UCB: after startup, suggestions concentrate near the optimum of
    a smooth 2D objective (reference: tune/search/bayesopt)."""
    from ray_tpu import tune
    from ray_tpu.tune.search import BayesOptSearcher

    s = BayesOptSearcher(
        {"x": tune.uniform(0.0, 1.0), "y": tune.uniform(0.0, 1.0)},
        metric="score", mode="max", n_startup=8, kappa=1.0, seed=3)

    def objective(cfg):
        return -(cfg["x"] - 0.3) ** 2 - (cfg["y"] - 0.8) ** 2

    for i in range(30):
        cfg = s.suggest(f"t{i}")
        s.on_trial_complete(f"t{i}", {"score": objective(cfg)})
    tail = [s.suggest(f"f{i}") for i in range(5)]
    # model-based tail suggestions sit near (0.3, 0.8)
    assert sum(abs(c["x"] - 0.3) < 0.25 and abs(c["y"] - 0.8) < 0.25
               for c in tail) >= 3, tail


def test_bayesopt_rejects_categorical():
    import pytest as _pytest

    from ray_tpu import tune
    from ray_tpu.tune.search import BayesOptSearcher

    with _pytest.raises(ValueError, match="numeric"):
        BayesOptSearcher({"opt": tune.choice(["adam", "sgd"])},
                         metric="score", mode="max")
