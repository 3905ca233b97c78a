"""End-to-end behaviour tests: the paper's six insights must be observable
in this framework's own pipelines, plus serving integration.
"""
import math
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.variance import decompose, variance_reduction
from repro.models import Model
from repro.perception import (
    ApproxTimeSynchronizer,
    SceneConfig,
    run_lane,
    run_lane_static,
    run_one_stage,
    run_two_stage,
)

N_FRAMES = 18


@pytest.fixture(scope="module")
def city():
    return SceneConfig("city", seed=11)


# ------------------------------------------------- Insight 3 (model) -------
def test_one_stage_is_inference_dominated(city):
    rec = run_one_stage(city, n=N_FRAMES)
    dec = decompose(rec)
    post = next(a for a in dec.attributions if a.stage == "post_processing")
    infer = next(a for a in dec.attributions if a.stage == "inference")
    assert infer.covariance_share > post.covariance_share


def test_two_stage_is_post_dominated_and_proposal_correlated(city):
    rec = run_two_stage(city, n=N_FRAMES)
    # post-processing must explain a large covariance share and track the
    # proposal count (the paper's data-dependence claim).  Not asserted as
    # the strict argmax stage: on small shared-CPU runners, hypervisor
    # steal can inflate inference-stage variance past any data-dependent
    # signal, which says nothing about the pipeline itself.
    dec = decompose(rec)
    post = next(a for a in dec.attributions if a.stage == "post_processing")
    assert post.covariance_share > 0.35
    assert rec.correlation_meta("num_proposals") > 0.3


# ------------------------------------------------- Insight 1 (data) --------
def test_scenario_changes_proposal_counts():
    recs = {}
    for scen in ("city", "road"):
        rec = run_two_stage(SceneConfig(scen, seed=5), n=N_FRAMES)
        recs[scen] = rec.meta_series("num_proposals").mean()
    assert recs["city"] > 1.5 * recs["road"]


def test_rain_reduces_proposals():
    dry = run_two_stage(SceneConfig("city", seed=5, rain_mm_per_hour=0), n=N_FRAMES)
    wet = run_two_stage(SceneConfig("city", seed=5, rain_mm_per_hour=200), n=N_FRAMES)
    assert wet.meta_series("num_proposals").mean() < dry.meta_series("num_proposals").mean()


# ---------------------------------------- static-shape mitigation ----------
def test_static_lane_pipeline_kills_post_processing_variance(city):
    dyn = run_lane(city, n=N_FRAMES)
    sta = run_lane_static(city, n=N_FRAMES)
    dyn_post = dyn.stage_series("post_processing")
    sta_post = sta.stage_series("post_processing")
    rep = variance_reduction(dyn_post, sta_post)
    # static post is a fixed-size readback: its std collapses vs dynamic
    assert np.std(sta_post) < 0.5 * np.std(dyn_post)
    assert rep["range_after"] < rep["range_before"]


# ------------------------------------------------- Insight 6 (fusion) ------
def test_synchronizer_queue_size_damps_delay_variance():
    def run(queue):
        sync = ApproxTimeSynchronizer(["a", "b"], queue_size=queue, slop=0.05)
        for i in range(400):
            stamp = i * 0.1
            sync.add("a", stamp, None, now=stamp + 0.01)
            # topic b is slow & bursty: occasionally 15 frames late
            lag = 1.5 if (i % 40) < 3 else 0.02
            sync.add("b", stamp, None, now=stamp + lag)
        return np.array(sync.delays())

    d_small = run(2)
    d_big = run(100)
    assert len(d_big) >= len(d_small)               # fewer lost matches
    assert np.percentile(d_big, 99) <= np.percentile(d_small, 99) * 1.5


def test_synchronizer_queue_overflow_drop_accounting():
    """Insight 6 mechanism: a bounded per-topic queue drops its oldest
    entry on overflow and counts every drop — the paper's fusion-loss
    bookkeeping must be exact."""
    sync = ApproxTimeSynchronizer(["a", "b"], queue_size=3, slop=0.01)
    for i in range(10):
        # topic b never arrives, so nothing can be emitted and topic a's
        # queue must overflow deterministically
        sync.add("a", float(i), None, now=float(i))
    assert sync.dropped == 7                      # 10 pushed into 3 slots
    assert [s for s, _ in sync.queues["a"]] == [7.0, 8.0, 9.0]
    assert not sync.events

    # matched traffic with a roomy queue drops nothing
    sync2 = ApproxTimeSynchronizer(["a", "b"], queue_size=100, slop=0.01)
    for i in range(10):
        sync2.add("a", float(i), None, now=float(i))
        sync2.add("b", float(i), None, now=float(i) + 0.001)
    assert sync2.dropped == 0
    assert len(sync2.events) == 10


def test_engine_generates_and_reports():
    from repro.runtime import Engine, ServeConfig

    cfg = get_config("rwkv6-3b", smoke=True).replace(num_layers=2, vocab_size=64)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, ServeConfig(batch=2, context=64))
    prompt = np.array([[1, 2, 3], [4, 5, 6]], np.int32)
    out, rec = eng.generate(params, prompt, max_new_tokens=6)
    assert out.shape == (2, 6)
    rep = eng.report()
    assert rep["jobs"] == 5 and math.isfinite(rep["mean_s"])


def test_engine_rejects_empty_prompt_and_seeds_policy_from_warmup():
    """Regression: a zero-length prompt used to raise NameError deep in the
    decode loop, and the first post-warmup job was scored against a
    never-observed (infinite/degenerate) deadline."""
    from repro.core.deadline import MeanDeadline
    from repro.runtime import Engine, ServeConfig

    cfg = get_config("rwkv6-3b", smoke=True).replace(num_layers=2, vocab_size=64)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    policy = MeanDeadline(margin=1.5)
    eng = Engine(model, ServeConfig(batch=2, context=64, warmup_steps=2),
                 deadline_policy=policy)

    with pytest.raises(ValueError, match="at least one token"):
        eng.generate(params, np.zeros((2, 0), np.int32), max_new_tokens=4)
    with pytest.raises(ValueError, match="batch"):
        eng.generate(params, np.zeros((3, 2), np.int32), max_new_tokens=4)

    out, _ = eng.generate(params, np.ones((2, 2), np.int32), max_new_tokens=6)
    assert out.shape == (2, 6)
    # all 6 decode steps observed (warmup included: they seed the policy),
    # but only the post-warmup 4 are scored as jobs
    assert policy._w.n == 6
    assert eng.jobs == 4


def test_init_params_deterministic_across_processes():
    """Regression: init_params folded ``hash(name)`` into the PRNG key —
    salted per process by PYTHONHASHSEED, so the same seed produced
    different parameters every run (surfaced as nondeterministic anytime
    ladder quality).  The fold-in must be process-independent."""
    import subprocess
    import sys

    prog = (
        "import jax, jax.numpy as jnp;"
        "from repro.perception.detector import OneStageDetector;"
        "det = OneStageDetector();"
        "p = det.init(jax.random.PRNGKey(7));"
        "print(sum(float(jnp.abs(x).sum()) for x in jax.tree.leaves(p)))"
    )
    sums = []
    for seed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=seed,
                   PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""))
        out = subprocess.run([sys.executable, "-c", prog], env=env,
                             capture_output=True, text=True, check=True)
        sums.append(float(out.stdout.strip()))
    assert sums[0] == sums[1]
