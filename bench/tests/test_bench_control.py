"""The check fails what it must: the control (the plain reference at three
bfloat16 passes, one precision step below the configurations' float32 at
HIGHEST), and runs whose timed path is broken underneath."""
import numpy as np
import pytest

from bench import harness
from bench.configs import cnn_reference as ref

import bench_smoke as smoke


@pytest.mark.parametrize("name,n", [("alexnet", 2), ("vgg16", 1)])
def test_control_fails_the_limit_at_full_size(name, n):
    """The configurations' own size, on the CPU: the control's logits lie
    further from the reference's than each configuration's limit."""
    import jax

    conf = harness.load_config(name)
    w = ref.weights(conf, harness.seed_key(2**31 + 101))
    x = np.random.default_rng(3).standard_normal((n, *conf["in_chw"]), np.float32)
    want = np.asarray(jax.jit(lambda w, x: ref.forward(conf, w, x))(w, x))
    got = np.asarray(jax.jit(lambda w, x: ref.forward(conf, w, x, "bf16x3"))(w, x))
    err = max(harness.row_err(g, r) for g, r in zip(got, want))
    assert err > conf["limits"]["logit_err"]


@pytest.fixture(scope="module")
def sessions(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("faults")
    return {n: smoke.session(tmp / n, n) for n in smoke.CELLS}


def _run(sess, seed, fault=None):
    sess.load(seed)
    if fault is not None:
        sess.batcher.wrap = fault
    try:
        sess.warm()
        seen = sess.measure(1.0)
    finally:
        if fault is not None:
            sess.batcher.wrap = None
    sess.free()
    return sess.verify(seen)


def _half_batch(bucket, fn):
    """Half of the batch left out: its rows get the mean over the rest."""
    def f(params, x):
        y = fn(params, x)
        h = y.shape[0] // 2
        return y.at[h:].set(y[:h].mean(axis=0))
    return f


def _altered_answer(bucket, fn):
    """One answer altered where it is produced: row 0 gets another class."""
    def f(params, x):
        y = fn(params, x)
        return y.at[0, y[0].argmin()].set(y[0].max() + 1.0)
    return f


@pytest.mark.parametrize("cell", sorted(smoke.CELLS))
def test_sound_run_is_correct(sessions, cell):
    numbers = _run(sessions[cell], 2**31 + 5)
    assert harness.passed(numbers), numbers


# the open loop's batches at the smoke rate hold one or two images, so half
# of a batch is left out where batches are full: in the closed loop
@pytest.mark.parametrize("cell,fault", [("smoke.bulk", _half_batch),
                                        ("smoke.serve", _altered_answer),
                                        ("smoke.bulk", _altered_answer)])
def test_broken_timed_path_is_not_correct(sessions, cell, fault):
    numbers = _run(sessions[cell], 2**31 + 6, fault)
    assert not harness.passed(numbers)
    assert numbers["logit_err"]["value"] > numbers["logit_err"]["limit"]


def test_control_in_the_programs_place_is_not_correct(tmp_path):
    sess = smoke.session(tmp_path, "smoke.serve", control=True)
    numbers = _run(sess, 2**31 + 7)
    assert not harness.passed(numbers)
    assert numbers["logit_err"]["value"] > numbers["logit_err"]["limit"]
