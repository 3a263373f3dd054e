"""Federated generalized linear models — parity with v6-glm-py.

The reference GLM algorithm iterates IRLS (iteratively reweighted least
squares) federally: central broadcasts the coefficient vector, every
organization computes the sufficient statistics of the weighted least-
squares step on its OWN rows — ``X'WX`` and ``X'Wz`` (working response z)
plus its deviance contribution — central sums them and solves. Because the
statistics are additive over rows, the federated fit is MATHEMATICALLY
IDENTICAL to pooled IRLS; only aggregate p×p / p-vectors ever leave a
station (SURVEY.md §2.3 "algorithm repos" row; the same privacy shape as
the logistic/Cox algorithms here).

Families: gaussian (identity link), binomial (logit), poisson (log) — the
reference's supported trio. Both modes live here:

- host mode: reference-shaped task rounds (`partial_glm_stats` per station,
  `central_glm` orchestrating) over pandas DataFrames;
- device mode: `fit_glm_device` — the WHOLE IRLS loop as one jitted program
  (`lax.scan` over iterations, per-station stats under `fed_map`, one
  all-reduce and a p×p solve per iteration, p small).

The keystone tests cross-check against independent fits: gaussian against
the least-squares closed form, binomial against the logistic-regression
workload's MLE, poisson against its score equation X'(y-mu)=0.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from vantage6_tpu.algorithm.decorators import algorithm_client, data
from vantage6_tpu.core.mesh import FederationMesh
from vantage6_tpu.fed.collectives import fed_sum
from vantage6_tpu.runtime.profiling import RunnerCache, observed_jit

FAMILIES = ("gaussian", "binomial", "poisson")
#: tiny ridge on X'WX: IRLS must not explode on separable/collinear data
_JITTER = 1e-8


def _check_family(family: str) -> str:
    if family not in FAMILIES:
        raise ValueError(f"unknown family {family!r} (choose from {FAMILIES})")
    return family


def _irls_pieces(family: str, eta, y, weights):
    """(mu, working response z, IRLS weight W, per-row deviance).

    All jnp expressions — shared verbatim by the host and device paths so
    the two cannot drift numerically.
    """
    if family == "gaussian":
        mu = eta
        z = y  # identity link: z = eta + (y - mu) = y
        w = weights
        dev = weights * (y - mu) ** 2
    elif family == "binomial":
        mu = jax.nn.sigmoid(eta)
        dmu = mu * (1.0 - mu) + 1e-12
        z = eta + (y - mu) / dmu
        w = weights * dmu
        # binomial deviance, y in {0,1}: -2 log p(y) (xlogy handles 0)
        dev = 2.0 * weights * (
            _xlogy(y, y / jnp.clip(mu, 1e-12, 1.0))
            + _xlogy(1.0 - y, (1.0 - y) / jnp.clip(1.0 - mu, 1e-12, 1.0))
        )
    else:  # poisson
        # clip mu away from 0/inf: an unscaled covariate can push eta past
        # the exp range mid-IRLS, and 0*inf in X'Wz would silently carry
        # NaN through every remaining scan iteration (same stance as the
        # binomial branch's dmu floor)
        mu = jnp.clip(jnp.exp(eta), 1e-8, 1e12)
        z = eta + (y - mu) / mu
        w = weights * mu
        dev = 2.0 * weights * (_xlogy(y, y / mu) - (y - mu))
    return mu, z, w, dev


from jax.scipy.special import xlogy as _xlogy  # 0 where x == 0


def _design(df: Any, feature_cols: list[str]) -> np.ndarray:
    """[n, p+1] design matrix with leading intercept column."""
    x = np.asarray(df[feature_cols], np.float64)
    return np.concatenate([np.ones((x.shape[0], 1)), x], axis=1)


# ----------------------------------------------------------------- host mode
@data(1)
def partial_glm_stats(
    df: Any,
    beta: list[float],
    family: str,
    feature_cols: list[str],
    label_col: str,
    weight_col: str | None = None,
) -> dict[str, Any]:
    """One IRLS step's sufficient statistics on this station's rows.

    Returns X'WX [p,p], X'Wz [p], the station's deviance contribution and
    row count — additive aggregates; never rows.
    """
    _check_family(family)
    x = _design(df, feature_cols)
    y = np.asarray(df[label_col], np.float64)
    wts = (
        np.asarray(df[weight_col], np.float64)
        if weight_col
        else np.ones_like(y)
    )
    # host mode matches the reference's float64 IRLS exactly; enable_x64 is
    # scoped so the process-wide x32 default (TPU path) is untouched
    with jax.enable_x64(True):
        b = jnp.asarray(beta, jnp.float64)
        eta = jnp.asarray(x) @ b
        _, z, w, dev = _irls_pieces(
            family, eta, jnp.asarray(y), jnp.asarray(wts)
        )
        xw = jnp.asarray(x) * w[:, None]
        return {
            "xtwx": np.asarray(jnp.asarray(x).T @ xw, np.float64),
            "xtwz": np.asarray(xw.T @ z, np.float64),
            "deviance": float(jnp.sum(dev)),
            "count": int(y.shape[0]),
        }


@algorithm_client
def central_glm(
    client: Any,
    family: str,
    feature_cols: list[str],
    label_col: str,
    weight_col: str | None = None,
    n_iter: int = 25,
    tol: float = 1e-8,
    organizations: list[int] | None = None,
) -> dict[str, Any]:
    """Federated IRLS to convergence — identical to pooled IRLS.

    Returns coefficients (intercept first), standard errors (from the
    inverse Fisher information at the optimum; gaussian dispersion is
    estimated as deviance/(n-p), binomial/poisson use dispersion 1 like
    the reference), final deviance, iteration count and convergence flag.
    """
    _check_family(family)
    if n_iter < 1:
        raise ValueError("n_iter must be >= 1")
    orgs = organizations or [o["id"] for o in client.organization.list()]
    p = len(feature_cols) + 1
    beta = np.zeros(p, np.float64)
    deviance = float("inf")
    converged = False
    it = 0
    kwargs_base = {
        "family": family,
        "feature_cols": feature_cols,
        "label_col": label_col,
        "weight_col": weight_col,
    }
    for it in range(1, n_iter + 1):
        task = client.task.create(
            input_={
                "method": "partial_glm_stats",
                "kwargs": {**kwargs_base, "beta": [float(v) for v in beta]},
            },
            organizations=orgs,
            name=f"glm_irls_{it}",
        )
        parts = client.wait_for_results(task_id=task["id"])
        xtwx = np.sum([np.asarray(r["xtwx"]) for r in parts], axis=0)
        xtwz = np.sum([np.asarray(r["xtwz"]) for r in parts], axis=0)
        deviance = float(np.sum([r["deviance"] for r in parts]))
        count = int(np.sum([r["count"] for r in parts]))
        new_beta = np.linalg.solve(xtwx + _JITTER * np.eye(p), xtwz)
        delta = float(np.max(np.abs(new_beta - beta)))
        beta = new_beta
        if delta < tol:
            converged = True
            break
    # standard errors at the optimum (one more stats round would refresh
    # XtWX at the final beta; the last iteration's is the standard report)
    cov = np.linalg.inv(xtwx + _JITTER * np.eye(p))
    dispersion = (
        deviance / max(count - p, 1) if family == "gaussian" else 1.0
    )
    se = np.sqrt(np.clip(np.diag(cov) * dispersion, 0.0, None))
    return {
        "coefficients": [float(v) for v in beta],
        "std_errors": [float(v) for v in se],
        "deviance": deviance,
        "dispersion": float(dispersion),
        "iterations": it,
        "converged": converged,
        "count": count,
        "family": family,
        "columns": ["(intercept)", *feature_cols],
    }


# --------------------------------------------------------------- device mode
_GLM_RUNNERS = RunnerCache("glm")


def _glm_runner(mesh: FederationMesh, family: str, n_iter: int):
    """Compiled IRLS runner, cached per (mesh.fingerprint(), family,
    n_iter): repeated fits with same-shaped data reuse one executable
    instead of paying XLA compilation of the whole scan every call — and
    callers constructing a FRESH FederationMesh over the same devices hit
    the cache too (object identity would recompile and leak an entry per
    call). Data enters as ARGUMENTS, not trace constants."""

    def build():
        def station_stats(x, y, m, beta):
            eta = x @ beta
            _, z, w, dev = _irls_pieces(family, eta, y, m)
            # row mask rides the IRLS weight: padded rows contribute zero
            xw = x * w[:, None]
            return x.T @ xw, xw.T @ z, jnp.sum(dev)

        def run(beta0, sx, sy, row_mask):
            p = sx.shape[-1]

            def one_iter(beta, _):
                xtwx, xtwz, dev = mesh.fed_map(
                    station_stats, sx, sy, row_mask, replicated_args=(beta,)
                )
                xtwx = fed_sum(xtwx)
                xtwz = fed_sum(xtwz)
                dev = fed_sum(dev)
                new_beta = jnp.linalg.solve(
                    xtwx + _JITTER * jnp.eye(p, dtype=xtwx.dtype), xtwz
                )
                delta = jnp.max(jnp.abs(new_beta - beta))
                return new_beta, (delta, dev)

            return jax.lax.scan(one_iter, beta0, None, length=n_iter)

        return observed_jit(f"glm.irls.{family}", run)

    return _GLM_RUNNERS.get_or_create(
        (mesh.fingerprint(), family, n_iter), build
    )


def fit_glm_device(
    mesh: FederationMesh,
    sx: jax.Array,  # [S, n_max, p] designs (pad rows with zeros)
    sy: jax.Array,  # [S, n_max] labels (pad 0)
    row_mask: jax.Array,  # [S, n_max] 1.0 for real rows
    family: str,
    n_iter: int = 25,
) -> dict[str, jax.Array]:
    """The WHOLE federated IRLS as one jitted program.

    Per iteration: every station computes its (X'WX, X'Wz, deviance) under
    ``fed_map`` (sees only its own shard), one explicit cross-station
    fed_sum, and a p×p solve (p is small — the solve is negligible; the
    per-station GEMMs are where the FLOPs live and they batch on the MXU).
    Fixed ``n_iter`` keeps the loop a static `lax.scan` — convergence is
    read off the returned delta history, not data-dependent control flow.
    """
    _check_family(family)
    beta0 = jnp.zeros((sx.shape[-1],), sx.dtype)
    beta, (deltas, devs) = _glm_runner(mesh, family, n_iter)(
        beta0, sx, sy, row_mask
    )
    return {"beta": beta, "deltas": deltas, "deviances": devs}


def stack_glm_data(
    frames: list[Any], feature_cols: list[str], label_col: str
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-station DataFrames -> padded stacked (designs, labels, row mask).

    Padding delegates to utils.datasets.pad_shards — the single home of the
    SPMD static-shape padding invariant.
    """
    from vantage6_tpu.utils.datasets import pad_shards

    shards = [
        (_design(f, feature_cols), np.asarray(f[label_col], np.float64))
        for f in frames
    ]
    sx, sy, counts = pad_shards(shards)
    n_max = sx.shape[1]
    mask = (np.arange(n_max)[None, :] < counts[:, None]).astype(np.float64)
    return sx, sy, mask
