"""Federated GLM (IRLS): the federated fit must equal the pooled fit, and
the pooled fit is cross-checked against INDEPENDENT references — gaussian
vs the least-squares closed form, binomial vs the logistic-regression
workload's MLE, poisson vs its score equation X'(y-mu)=0."""
import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from vantage6_tpu.core.mesh import FederationMesh
from vantage6_tpu.runtime.federation import federation_from_datasets
from vantage6_tpu.workloads import glm


def _frames(family: str, n_stations=3, n=120, seed=0):
    rng = np.random.default_rng(seed)
    beta_true = np.asarray([0.4, -0.8, 0.5])  # intercept, x0, x1
    frames = []
    for s in range(n_stations):
        x = rng.normal(0, 1, (n, 2))
        eta = beta_true[0] + x @ beta_true[1:]
        if family == "gaussian":
            y = eta + rng.normal(0, 0.5, n)
        elif family == "binomial":
            y = (rng.uniform(size=n) < 1 / (1 + np.exp(-eta))).astype(float)
        else:
            y = rng.poisson(np.exp(eta)).astype(float)
        frames.append(pd.DataFrame({"x0": x[:, 0], "x1": x[:, 1], "y": y}))
    return frames


def _fit_federated(family, frames, **kw):
    fed = federation_from_datasets(frames, {"v6-glm": glm})
    task = fed.create_task(
        "v6-glm",
        {
            "method": "central_glm",
            "kwargs": {
                "family": family,
                "feature_cols": ["x0", "x1"],
                "label_col": "y",
                **kw,
            },
        },
        organizations=[0],
    )
    return fed.wait_for_results(task.id)[0]


class TestHostMode:
    def test_gaussian_matches_least_squares(self):
        frames = _frames("gaussian")
        out = _fit_federated("gaussian", frames)
        pooled = pd.concat(frames)
        X = np.column_stack(
            [np.ones(len(pooled)), pooled[["x0", "x1"]].to_numpy()]
        )
        ref, *_ = np.linalg.lstsq(X, pooled["y"].to_numpy(), rcond=None)
        np.testing.assert_allclose(out["coefficients"], ref, atol=1e-6)
        assert out["converged"] and out["iterations"] <= 3
        assert out["count"] == len(pooled)
        # gaussian SE from dispersion = deviance/(n-p)
        resid = pooled["y"].to_numpy() - X @ ref
        s2 = resid @ resid / (len(pooled) - 3)
        se_ref = np.sqrt(np.diag(s2 * np.linalg.inv(X.T @ X)))
        np.testing.assert_allclose(out["std_errors"], se_ref, rtol=1e-4)

    def test_binomial_matches_logistic_mle(self):
        frames = _frames("binomial")
        out = _fit_federated("binomial", frames)
        assert out["converged"]
        # independent fit: Newton-Raphson on the POOLED log-likelihood, in
        # plain numpy (the exact MLE; federated IRLS must land on it)
        pooled = pd.concat(frames)
        X = np.column_stack(
            [np.ones(len(pooled)), pooled[["x0", "x1"]].to_numpy()]
        )
        y = pooled["y"].to_numpy()
        beta = np.zeros(3)
        for _ in range(25):
            mu = 1.0 / (1.0 + np.exp(-X @ beta))
            hess = X.T @ (X * (mu * (1.0 - mu))[:, None])
            beta = beta + np.linalg.solve(hess, X.T @ (y - mu))
        np.testing.assert_allclose(out["coefficients"], beta, atol=1e-6)

    def test_poisson_score_equation_holds(self):
        frames = _frames("poisson")
        out = _fit_federated("poisson", frames)
        assert out["converged"]
        pooled = pd.concat(frames)
        X = np.column_stack(
            [np.ones(len(pooled)), pooled[["x0", "x1"]].to_numpy()]
        )
        mu = np.exp(X @ np.asarray(out["coefficients"]))
        score = X.T @ (pooled["y"].to_numpy() - mu)
        np.testing.assert_allclose(score, 0.0, atol=1e-4)

    def test_weighted_rows(self):
        # weight 2 == duplicating the row: fit with weights must equal the
        # fit on the physically duplicated dataset
        frames = _frames("gaussian", n_stations=2, n=60, seed=3)
        for f in frames:
            f["wt"] = 2.0
        doubled = [pd.concat([f, f], ignore_index=True) for f in frames]
        out_w = _fit_federated("gaussian", frames, weight_col="wt")
        out_d = _fit_federated("gaussian", doubled)
        np.testing.assert_allclose(
            out_w["coefficients"], out_d["coefficients"], atol=1e-8
        )

    def test_unknown_family_rejected(self):
        with pytest.raises(ValueError, match="unknown family"):
            glm._check_family("gamma")

    def test_n_iter_zero_rejected(self):
        frames = _frames("gaussian", n_stations=2, n=30)
        with pytest.raises(Exception, match="n_iter"):
            _fit_federated("gaussian", frames, n_iter=0)

    def test_poisson_survives_unscaled_covariate(self):
        # values ~50-100 push eta past the exp range mid-IRLS; the mu clip
        # must keep the fit finite instead of carrying NaN to the end
        rng = np.random.default_rng(9)
        frames = []
        for _ in range(2):
            big = rng.uniform(50, 100, 80)
            y = rng.poisson(np.exp(0.02 * big)).astype(float)
            frames.append(pd.DataFrame({"x0": big, "x1": rng.normal(0, 1, 80),
                                        "y": y}))
        out = _fit_federated("poisson", frames, n_iter=50)
        assert np.all(np.isfinite(out["coefficients"]))
        assert np.isfinite(out["deviance"])


class TestDeviceMode:
    @pytest.mark.parametrize("family", ["gaussian", "binomial", "poisson"])
    def test_device_fit_matches_host(self, family):
        frames = _frames(family, seed=11)
        host = _fit_federated(family, frames)
        mesh = FederationMesh(len(frames))
        sx, sy, m = glm.stack_glm_data(frames, ["x0", "x1"], "y")
        dev = glm.fit_glm_device(
            mesh,
            mesh.shard_stacked(jnp.asarray(sx, jnp.float32)),
            mesh.shard_stacked(jnp.asarray(sy, jnp.float32)),
            mesh.shard_stacked(jnp.asarray(m, jnp.float32)),
            family,
            n_iter=25,
        )
        np.testing.assert_allclose(
            np.asarray(dev["beta"], np.float64),
            host["coefficients"],
            atol=2e-3,
        )
        # the scan's delta history shows convergence without host control flow
        assert float(dev["deltas"][-1]) < 1e-3
        assert np.isfinite(float(dev["deviances"][-1]))

    def test_padded_rows_are_inert(self):
        # station sizes differ -> padding; padded rows must not affect beta
        frames = _frames("gaussian", n_stations=2, n=50, seed=5)
        frames[1] = frames[1].iloc[:30]
        mesh = FederationMesh(2)
        sx, sy, m = glm.stack_glm_data(frames, ["x0", "x1"], "y")
        dev = glm.fit_glm_device(
            mesh,
            mesh.shard_stacked(jnp.asarray(sx, jnp.float32)),
            mesh.shard_stacked(jnp.asarray(sy, jnp.float32)),
            mesh.shard_stacked(jnp.asarray(m, jnp.float32)),
            "gaussian",
        )
        pooled = pd.concat(frames)
        X = np.column_stack(
            [np.ones(len(pooled)), pooled[["x0", "x1"]].to_numpy()]
        )
        ref, *_ = np.linalg.lstsq(X, pooled["y"].to_numpy(), rcond=None)
        np.testing.assert_allclose(
            np.asarray(dev["beta"], np.float64), ref, atol=2e-3
        )
