"""The plain PIAG reference against the program at a small size on the
CPU: event order, step-sizes and iterates."""
import jax.numpy as jnp
import numpy as np
import pytest

import conftest  # noqa: F401  (puts the checkout on the path)

from bench.reference import piag as ref
from repro.core.engine import (heterogeneous_workers, sample_service_times,
                               simulate_parameter_server)
from repro.core.piag import run_piag
from repro.core.problems import make_logreg
from repro.core.prox import make_prox
from repro.core.stepsize import make_policy


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 3])
def test_event_order_matches_program(seed):
    workers = heterogeneous_workers(5, spread=4.0, seed=1, p_straggle=0.1)
    T = sample_service_times(workers, 301, seed=seed)
    want = simulate_parameter_server(5, 300, workers, service_times=T)
    worker, tau_max = ref.simulate(T)
    np.testing.assert_array_equal(worker, want.worker)
    np.testing.assert_array_equal(tau_max, want.tau_max)


@pytest.mark.parametrize("regime", ["uniform", "hetero2", "hetero4",
                                    "straggler"])
def test_service_times_match_program(regime):
    from repro.sweep.grid import standard_topology_factories
    for seed in (3, 2**33 + 5):
        workers = standard_topology_factories(0)[regime](7)
        np.testing.assert_array_equal(
            ref.service_times(ref.regime_workers(regime, 7), 201, seed),
            sample_service_times(workers, 201, seed=seed))


@pytest.mark.parametrize("policy,kwargs", [
    ("adaptive1", {}), ("adaptive2", {}), ("fixed", {"tau_bound": 9})])
def test_gammas_match_program(policy, kwargs):
    rng = np.random.default_rng(3)
    taus = np.minimum(rng.integers(0, 10, 200), np.arange(200))
    want = np.asarray(make_policy(policy, 0.37, **kwargs).run(taus))
    got = ref.gammas(policy, 0.37, taus, tau_bar=kwargs.get("tau_bound", 0))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)


def test_replay_matches_program():
    prob = make_logreg(300, 12, 3, sparse_like=False, lam1=1e-3, lam2=1e-4,
                       seed=2)
    workers = heterogeneous_workers(3, seed=4)
    T = sample_service_times(workers, 81, seed=5)
    trace = simulate_parameter_server(3, 80, workers, service_times=T)
    policy = make_policy("adaptive1", 0.99 / prob.L)
    Aw, bw = prob.worker_slices()
    want = run_piag(lambda x, A, b: prob.worker_loss(x, A, b),
                    jnp.zeros(12), (Aw, bw), trace, policy,
                    make_prox("l1", lam=prob.lam1), objective=prob.P)
    worker, tau_max = ref.simulate(T)
    steps = ref.gammas("adaptive1", 0.99 / prob.L, tau_max, tau_bar=0)
    obj, x = ref.replay(prob.A, prob.b, jnp.asarray(worker[None]),
                        jnp.asarray(steps[None]), n_workers=3, lam1=1e-3,
                        lam2=1e-4)
    np.testing.assert_allclose(obj[0], want.objective, rtol=1e-5)
    np.testing.assert_allclose(x[0], want.x, rtol=1e-4, atol=1e-6)
