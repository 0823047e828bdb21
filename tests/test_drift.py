"""Mean-field synaptic drift model (§IV-A): the paper's three numbers."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.drift import (DriftParams, density, drift, drift_analytic,
                              equilibrium, iterate, paper_metrics,
                              update_curve_rmse)


def test_density_normalises():
    p = DriftParams()
    x = jnp.linspace(-80, 80, 64001)
    for w in (0.0, 0.3, 0.9):
        mass = float(jnp.trapezoid(density(x, jnp.asarray(w), p), x))
        assert abs(mass - 1.0) < 5e-3


def test_quadrature_matches_analytic():
    p = DriftParams()
    w = jnp.linspace(0.01, 0.99, 25)
    from repro.core.drift import make_rule
    g_quad = drift(w, make_rule("exact", p), p)
    g_ana = drift_analytic(w, "exact", p)
    np.testing.assert_allclose(np.asarray(g_quad), np.asarray(g_ana),
                               atol=2e-3)


def test_update_curve_rmse_reproduces_paper():
    """Paper §IV-A: 9.4753 % RMSE for uncompensated ITP."""
    rmse = update_curve_rmse(DriftParams())
    assert abs(rmse - 0.094753) < 5e-4


def test_compensated_rmse_is_zero():
    rmse = update_curve_rmse(DriftParams(), "exact", "itp")
    assert rmse < 1e-6


def test_compensated_dynamics_identical():
    """Fig. 5 left column: τ·ln2 compensation → identical trajectories."""
    p = DriftParams()
    w0 = jnp.asarray([0.2, 0.5, 0.8])
    t_exact = iterate(w0, "exact", p, n_steps=300)
    t_itp = iterate(w0, "itp", p, n_steps=300)
    np.testing.assert_allclose(np.asarray(t_exact), np.asarray(t_itp),
                               atol=1e-5)


@pytest.mark.slow
def test_paper_metrics_within_band():
    """The three §IV-A numbers: 9.4753 % / 24.69 % / 7.36 %.

    RMSE is matched tightly (it is protocol-free); the equilibrium and
    convergence errors depend on unpublished protocol details — we assert
    the same order of magnitude (docs/architecture.md §7).
    """
    m = paper_metrics(n_steps=1500)
    assert abs(m["update_curve_rmse"] - 0.094753) < 5e-4
    assert m["update_curve_rmse_compensated"] < 1e-6
    assert 0.10 < m["equilibrium_rel_err"] < 0.40       # paper: 0.2469
    assert 0.02 < m["convergence_time_rel_err"] < 0.20  # paper: 0.0736


def test_equilibrium_is_stable_point():
    p = DriftParams()
    for rule in ("exact", "itp_nocomp"):
        w_star = equilibrium(rule, p)
        assert 0.0 < w_star < 1.0
        g = drift_analytic(jnp.asarray([w_star - 1e-3, w_star + 1e-3]),
                           rule, p)
        assert float(g[0]) > 0 > float(g[1])   # flow converges onto w*
