"""RDP moments accountant (core/privacy.py) — the math the reference's
"weak DP" never does (robust_aggregation.py:51-55 has no accounting)."""

import math

import numpy as np
import pytest

from fedml_tpu.core.privacy import (RdpAccountant, eps_from_rdp,
                                    rdp_subsampled_gaussian)


def test_q1_reduces_to_plain_gaussian_rdp():
    """q=1 must give the unsubsampled Gaussian's exact RDP α/(2z²) —
    the j=α term is the only survivor of the binomial sum."""
    orders = (2, 3, 8, 32, 256)
    for z in (0.5, 1.0, 2.7):
        got = rdp_subsampled_gaussian(1.0, z, orders)
        want = np.asarray(orders) / (2.0 * z * z)
        np.testing.assert_allclose(got, want, rtol=1e-12)


def test_alpha2_closed_form():
    """α=2 collapses to log(1 + q²(e^{1/z²} − 1)) (the three binomial
    terms sum to 1 + q²(e^{1/z²}−1))."""
    for q, z in ((0.01, 1.1), (0.3, 0.8), (0.9, 2.0)):
        got = rdp_subsampled_gaussian(q, z, (2,))[0]
        want = math.log(1.0 + q * q * (math.exp(1.0 / (z * z)) - 1.0))
        assert got == pytest.approx(want, rel=1e-10)


def test_subsampling_strictly_helps():
    orders = tuple(range(2, 32))
    full = rdp_subsampled_gaussian(1.0, 1.1, orders)
    sub = rdp_subsampled_gaussian(0.05, 1.1, orders)
    assert np.all(sub < full)


def test_edge_cases():
    orders = (2, 4, 8)
    assert np.all(np.isinf(rdp_subsampled_gaussian(0.1, 0.0, orders)))
    np.testing.assert_array_equal(
        rdp_subsampled_gaussian(0.0, 1.0, orders), np.zeros(3))
    with pytest.raises(ValueError, match="q must be"):
        rdp_subsampled_gaussian(1.5, 1.0, orders)
    with pytest.raises(ValueError, match="orders"):
        rdp_subsampled_gaussian(0.5, 1.0, (1,))
    with pytest.raises(ValueError, match="delta"):
        eps_from_rdp(np.ones(3), orders, 2.0)


def test_eps_conversion_matches_hand_computation():
    """One unsubsampled Gaussian step: ε = min_α [α/(2z²) + ln(1/δ)/(α−1)]
    — compute the minimum by brute force and compare."""
    z, delta = 1.0, 1e-5
    acct = RdpAccountant(1.0, z, delta)
    acct.step()
    alphas = np.arange(2, 1025, dtype=np.float64)
    want = np.min(alphas / (2 * z * z)
                  + math.log(1 / delta) / (alphas - 1))
    # DEFAULT_ORDERS is sparser than the brute-force grid — equal when the
    # argmin lands on a shared order, never better
    assert acct.epsilon() == pytest.approx(want, rel=5e-2)
    assert acct.epsilon() >= want - 1e-12


def test_composition_monotonicity():
    acct = RdpAccountant(0.02, 1.1, 1e-5)
    eps = []
    for _ in range(4):
        acct.step(25)
        eps.append(acct.epsilon())
    assert all(b > a for a, b in zip(eps, eps[1:]))
    # more noise -> less privacy spent at the same step count
    quieter = RdpAccountant(0.02, 2.2, 1e-5)
    quieter.step(100)
    assert quieter.epsilon() < eps[-1]
    # fresh accountant spends nothing
    assert RdpAccountant(0.02, 1.1, 1e-5).epsilon() == 0.0


def test_mnist_dpsgd_regime_ballpark():
    """The classic DP-SGD MNIST regime (q=256/60000, z=1.1, 60 epochs,
    δ=1e-5) lands at ε ≈ 3 in every published accountant; assert a
    generous window as a regression guard against formula typos."""
    q = 256 / 60000
    steps = 60 * (60000 // 256)
    acct = RdpAccountant(q, 1.1, 1e-5)
    acct.step(steps)
    assert 1.5 < acct.epsilon() < 4.5, acct.epsilon()


def test_fixed_size_wor_q1_is_replace_one_gaussian():
    """γ=1 (full participation): the WOR bound must equal the plain
    Gaussian RDP at replace-one sensitivity, α/(2·(z/2)²)."""
    from fedml_tpu.core.privacy import rdp_fixed_size_wor
    orders = (2, 3, 8, 32)
    z = 1.4
    got = rdp_fixed_size_wor(1.0, z, orders)
    want = np.asarray(orders) / (2.0 * (z / 2.0) ** 2)
    np.testing.assert_allclose(got, want)


def test_fixed_size_wor_pins_against_poisson_approximation():
    """The fixed-size bound APPLIES to the sampler dp_fedavg actually
    uses and must be CONSERVATIVE relative to the
    Poisson approximation at the same (q, z) — never optimistic.  Both
    stay finite and positive, and the WOR bound never exceeds its own
    unsubsampled replace-one clamp."""
    from fedml_tpu.core.privacy import (rdp_fixed_size_wor,
                                        rdp_subsampled_gaussian)
    orders = tuple(range(2, 40))
    for q, z in ((0.01, 1.1), (0.1, 1.0), (0.3, 2.0)):
        wor = rdp_fixed_size_wor(q, z, orders)
        poi = rdp_subsampled_gaussian(q, z, orders)
        assert np.all(np.isfinite(wor)) and np.all(wor > 0)
        # replace-one sensitivity doubling makes WOR epsilon the larger
        assert np.all(wor >= poi), (q, z)
        clamp = np.asarray(orders) / (2.0 * (z / 2.0) ** 2)
        assert np.all(wor <= clamp + 1e-12)
    # converted epsilons order the same way
    a_p = RdpAccountant(0.05, 1.2, 1e-5)
    a_f = RdpAccountant(0.05, 1.2, 1e-5, sampling="fixed_size_wor")
    a_p.step(50)
    a_f.step(50)
    assert a_f.epsilon() > a_p.epsilon() > 0


def test_fixed_size_wor_edges_and_validation():
    from fedml_tpu.core.privacy import rdp_fixed_size_wor
    assert np.all(rdp_fixed_size_wor(0.0, 1.0) == 0.0)
    assert np.all(np.isinf(rdp_fixed_size_wor(0.1, 0.0)))
    with pytest.raises(ValueError):
        rdp_fixed_size_wor(1.5, 1.0)
    with pytest.raises(ValueError):
        RdpAccountant(0.1, 1.0, 1e-5, sampling="bogus")
